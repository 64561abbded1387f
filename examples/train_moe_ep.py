"""End-to-end driver: train a MoE LM with expert-parallel dispatch running
over the paper's factorized all-to-all, on a (pod=2, data=2, model=2)
debug mesh (8 virtual devices) — the EP group spans (data, pod), so every
MoE layer executes the d=2 hierarchical schedule each step, forward and
backward.

Shows: sharded init, factorized-A2A MoE, fault-tolerant trainer with
checkpointing, and loss decreasing on a learnable task.  A CPU demo: it
forces 8 virtual host devices (``chip_smoke.py --chips 4`` trains
phi3.5-moe over the chips of a TPU host).

  PYTHONPATH=src python examples/train_moe_ep.py [--steps 150]
"""

import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse                                                 # noqa: E402
import tempfile                                                 # noqa: E402

import jax                                                      # noqa: E402

from repro.data import CopyTaskConfig, SyntheticLM              # noqa: E402
from repro.models import ModelConfig, build_model, make_train_step  # noqa: E402
from repro.models.common import param_shardings                 # noqa: E402
from repro.optim import AdamW, AdamWConfig, cosine_with_warmup  # noqa: E402
from repro.parallel.sharding import ShardingRules               # noqa: E402
from repro.runtime import Trainer, TrainerConfig                # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    args = ap.parse_args()

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    rules = ShardingRules()
    cfg = ModelConfig(
        name="moe-ep-demo", family="moe", n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=2, d_ff=128, vocab=64, n_experts=4,
        top_k=2, capacity_factor=2.0, param_dtype="float32",
        compute_dtype="float32", remat=False)

    model = build_model(cfg)
    shardings = param_shardings(model.specs(), mesh, rules)
    params = jax.jit(model.init, out_shardings=shardings)(
        jax.random.PRNGKey(0))
    opt = AdamW(AdamWConfig(lr=cosine_with_warmup(3e-3, 20, args.steps),
                            weight_decay=0.0))
    step_fn = jax.jit(make_train_step(model, opt, mesh, rules))

    data = SyntheticLM(CopyTaskConfig(vocab=64, seq_len=32,
                                      global_batch=16), mesh=mesh,
                       task="copy")
    ckpt = tempfile.mkdtemp(prefix="moe_ep_")
    tr = Trainer(TrainerConfig(total_steps=args.steps, checkpoint_dir=ckpt,
                               checkpoint_every=50, log_every=25),
                 step_fn, data, params, jax.jit(opt.init)(params))
    tr.run()
    first, last = tr.metrics_log[0], tr.metrics_log[-1]
    print(f"\nEP over (data, pod): d=2 factorized all-to-all per MoE layer")
    print(f"step {first['step']}: ce={first['ce_loss']:.3f}  ->  "
          f"step {last['step']}: ce={last['ce_loss']:.3f}  "
          f"(aux={last['aux_loss']:.3f})")
    assert last["ce_loss"] < first["ce_loss"], "loss did not decrease"
    print("checkpoints at:", ckpt)


if __name__ == "__main__":
    main()
