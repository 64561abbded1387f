"""Training launcher: end-to-end driver over any registered architecture.

Wires config -> model -> sharded init -> fault-tolerant Trainer.  On the
CPU it runs with ``--smoke`` (reduced config); on the chips of a TPU host
``--mesh host`` shards over every chip present (``(pod=2, data=2)`` on
four).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
      --steps 50 --batch 8 --seq 64
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.launch.compile_cache import CHECKOUT, enable_compile_cache
from repro.data import CopyTaskConfig, DataConfig, SyntheticLM
from repro.models import build_model, make_train_step
from repro.models.common import init_params, param_shardings
from repro.optim import AdamW, AdamWConfig, cosine_with_warmup
from repro.parallel.sharding import ShardingRules
from repro.runtime import Trainer, TrainerConfig


def build_training(cfg, mesh, rules, *, lr=3e-4, warmup=100, total=10000,
                   grad_accum=1, seed=0):
    model = build_model(cfg)
    opt = AdamW(AdamWConfig(lr=cosine_with_warmup(lr, warmup, total)))

    if mesh is not None:
        shardings = param_shardings(model.specs(), mesh, rules)
        init_fn = jax.jit(model.init, out_shardings=shardings)
        # the moments are made from shapes alone, so their placement is
        # stated: sharded like the params, not all on the first device
        opt_init = jax.jit(opt.init, out_shardings={
            "mu": shardings, "nu": shardings,
            "step": NamedSharding(mesh, P())})
    else:
        init_fn = jax.jit(model.init)
        opt_init = jax.jit(opt.init)
    params = init_fn(jax.random.PRNGKey(seed))
    opt_state = opt_init(params)
    # params and optimizer state are donated: the step's outputs replace
    # them, so their buffers are reused instead of held twice
    step_fn = jax.jit(make_train_step(model, opt, mesh, rules,
                                      grad_accum=grad_accum),
                      donate_argnums=(0, 1))
    return model, opt, params, opt_state, step_fn


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--task", choices=("lm", "copy"), default="copy")
    ap.add_argument("--ckpt-dir", default=str(CHECKOUT / "runs" / "ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=("none", "host", "debug",
                                       "debug_multi"),
                    default="none",
                    help="host: (pod, data) over every chip present; "
                    "debug/debug_multi: the CPU test meshes")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = None
    rules = ShardingRules()
    if args.mesh == "host":
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
    elif args.mesh != "none":
        from repro.launch.mesh import make_debug_mesh
        mesh = make_debug_mesh(multi_pod=(args.mesh == "debug_multi"))

    model, opt, params, opt_state, step_fn = build_training(
        cfg, mesh, rules, lr=args.lr, total=args.steps,
        warmup=min(20, args.steps // 5 or 1), grad_accum=args.grad_accum)

    dcfg = CopyTaskConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch)
    data = SyntheticLM(dcfg, mesh=mesh, task=args.task)

    tr = Trainer(
        TrainerConfig(total_steps=args.steps,
                      checkpoint_dir=f"{args.ckpt_dir}/{cfg.name}",
                      checkpoint_every=args.ckpt_every, log_every=10),
        step_fn, data, params, opt_state)
    tr.install_preemption_handler()
    if args.resume and tr.try_restore():
        print(f"[train] resumed from step {tr.step}")
    status = tr.run()
    for row in tr.metrics_log:
        print(json.dumps(row))
    print(f"[train] {status} at step {tr.step}; "
          f"median step {tr.watchdog.median * 1e3:.1f} ms")
    return tr


if __name__ == "__main__":
    main()
