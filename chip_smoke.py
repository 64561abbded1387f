"""Bring-up check on the TPU: the main path through the normal entry
points, at full model width.

  python chip_smoke.py [--seed 0] [--out DIR]      # one chip
  python chip_smoke.py --chips 4                   # the four-chip v5e host

One chip runs, in one process and in this order:

1. device: the first device must be a TPU, or the script exits nonzero;
   there is no CPU fallback;
2. serve: qwen2.5-3b at its published config through ``build_model`` ->
   ``make_serve_step`` -> ``ContinuousBatcher``; the logits of every tick
   are compared with the model's uncached full forward over the same
   tokens;
3. train: a few steps of qwen2.5-3b at published widths, depth cut to fit
   one chip, through ``launch.train.build_training`` and the ``Trainer``;
4. kernels: every Pallas kernel compiled for the chip, at real widths,
   against ``kernels/ref.py``.

``--chips 4`` runs only the multi-chip path and what it is compared with:
``comm.all_to_all`` on the 2x2 torus (factorized against direct against
the simulator's permutation), and expert-parallel phi3.5-moe training with
the factorized and the direct dispatch.

Weights, tokens and operands are made from ``--seed``.  Each phase prints
its compile seconds (JAX's backend-compile events) and persistent-cache
hits, so a second run with the same cache directory shows the hits.  The
last line of standard output is the JSON result, and only that.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax                                                        # noqa: E402
import jax.numpy as jnp                                           # noqa: E402
import numpy as np                                                # noqa: E402

from repro.configs import get_config                              # noqa: E402
from repro.core.comm import torus_comm                            # noqa: E402
from repro.core.simulator import (round_datatype,                 # noqa: E402
                                  simulate_factorized_alltoall)
from repro.core.tuning import mesh_links                          # noqa: E402
from repro.data import DataConfig, SyntheticLM                    # noqa: E402
from repro.kernels.block_reorder import (datatype_pack,           # noqa: E402
                                         datatype_unpack)
from repro.kernels.flash_attention_bwd import \
    flash_attention_trainable                                     # noqa: E402
from repro.kernels.moe_gmm import grouped_matmul                  # noqa: E402
from repro.kernels.ref import (ref_attention, ref_block_reorder,  # noqa: E402
                               ref_gmm)
from repro.launch.compile_cache import enable_compile_cache       # noqa: E402
from repro.launch.mesh import make_host_mesh                      # noqa: E402
from repro.launch.serve import batcher_step                       # noqa: E402
from repro.launch.train import build_training                     # noqa: E402
from repro.models import build_model, make_serve_step             # noqa: E402
from repro.parallel.sharding import ShardingRules, ep_axes        # noqa: E402
from repro.runtime import Trainer, TrainerConfig                  # noqa: E402
from repro.runtime.serving import ContinuousBatcher, Request      # noqa: E402

# --- cuts and sizes -------------------------------------------------------
# Depths are chosen from compiled.memory_analysis() of the train step,
# compiled for a described v5e chip (16 GiB HBM) with params and optimizer
# state donated: qwen2.5-3b at 10 layers, batch 2 x 1024 tokens, needs
# 13.09 GiB (8 layers: 11.34 GiB); phi3.5-moe on the 2x2 host at 1 layer,
# all 16 experts, batch 8 x 512, needs 8.51 GiB per chip (2 layers: 15.89
# GiB, too close to the limit).  Widths are never cut.
QWEN_TRAIN_LAYERS = 10
QWEN_TRAIN_BATCH, QWEN_TRAIN_SEQ = 2, 1024
MOE_TRAIN_LAYERS = 1
MOE_TRAIN_BATCH, MOE_TRAIN_SEQ = 8, 512
TRAIN_STEPS = 4
SERVE_REQUESTS, SERVE_PROMPT, SERVE_GEN = 4, 128, 32

# --- tolerances -----------------------------------------------------------
# Serving is compared, as relative L2 over all logits of all ticks, with
# the model's uncached full forward in float32 at the highest matmul
# precision on the same weights.
# * At the published bf16 the forward itself deviates from that by its
#   rounding through 36 layers (0.19 at reduced width on the CPU).  The
#   cached path rounds at other points (its decode attention runs in
#   float32 over the cache) but computes the same function, so it may
#   deviate by at most a quarter more than the bf16 forward does (1.02x on
#   the CPU); a wrong cache slot, position or mask adds a deviation of the
#   order of the logits.
# * A float32 run of the same path, at published widths and 2 layers, must
#   agree to 1e-3: float32 rounding through the layers stays near 1e-4
#   (7e-5 at 36 layers on the CPU), while one bf16 step on the path gives
#   5e-2 already at 2 layers.
SERVE_BF16_RATIO = 1.25
SERVE_F32_TOL = 1e-3
SERVE_F32_LAYERS = 2
# Kernels, each against ref.py evaluated in float32 on the same bf16
# operands, as the largest deviation over the largest reference magnitude.
# The grouped matmul's only roundings are its bf16 output (half a bf16 ulp,
# at most 2^-8 of the value) and float32 accumulation order: the limit is
# twice that, 2^-7.  Attention and its gradients add the bf16 rounding of
# `out` inside delta = rowsum(dO * O) and of the group-summed dk/dv: 1e-2.
GMM_TOL = 2.0 ** -7
# MoE training, factorized against direct dispatch (not bit for bit on the
# chip, though it is on forced CPU devices: the compiler fuses and reduces
# differently around the two exchanges).  The exchange moves bytes
# unchanged, so the first loss, the same forward on the same params and
# tokens, agrees to float32 reduction order: 1e-5 relative (8.1e-7
# measured on the TPU v5e 2x2 host); tokens reaching a wrong expert change
# it far more.  Later losses follow AdamW updates whose normalized steps
# turn rounding-level gradient differences into parameter differences of up
# to the learning rate per entry, so the runs drift: 1e-2 (3.3e-3 measured
# there).
MOE_FIRST_LOSS_RTOL = 1e-5
MOE_LOSS_RTOL = 1e-2
ATTN_TOL = 1e-2


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, summed from
    JAX's monitoring events (a cache hit replaces the compile by a read)."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def since(self, mark):
        return {"compile_s": self.seconds - mark[0],
                "cache_hits": self.hits - mark[1]}

    def mark(self):
        return self.seconds, self.hits


def log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def rel_l2(got, ref) -> float:
    """Relative L2 deviation over the whole array."""
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))


def rel_err(got, ref) -> float:
    """Largest absolute deviation over the largest reference magnitude."""
    got = jnp.asarray(got, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    return float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))


def check(phase, name, value, limit):
    log(phase, f"{name}: {value!r} (limit {limit!r})")
    if not value <= limit:
        raise AssertionError(f"{phase}: {name} = {value!r} > {limit!r}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def require_tpu(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devs[0].platform!r}; there is no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX found {len(devs)}")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    log("device", f"{devs[0].device_kind}, {len(devs)} device(s), "
        f"jax {jax.__version__}, libtpu {libtpu}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def serve_phase(cfg, seed, *, n_req, prompt_len, gen):
    """Answer ``n_req`` requests through the launcher's serving path and
    compare every tick's logits with the uncached full forward, evaluated
    in float32 at the highest matmul precision on the same weights."""
    f32 = cfg.compute_dtype == "float32"
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    step = batcher_step(jax.jit(make_serve_step(model, None,
                                                ShardingRules())))
    seen, stamps = [], []

    def recording_step(params, toks, caches):
        stamps.append(time.perf_counter())
        logits, caches = step(params, toks, caches)
        seen.append(logits[:, -1])
        return logits, caches

    batcher = ContinuousBatcher(model, params, max_batch=n_req,
                                max_seq=prompt_len + gen,
                                serve_step=recording_step)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed + 1), (n_req, prompt_len), 0, cfg.vocab))
    for i in range(n_req):
        batcher.submit(Request(i, prompts[i].tolist(), gen))
    with (jax.default_matmul_precision("highest") if f32
          else contextlib.nullcontext()):
        done = batcher.run()
    t_end = time.perf_counter()
    ticks = batcher.ticks
    ms_per_tick = (t_end - stamps[1]) / (ticks - 1) * 1e3
    log("serve", f"{cfg.name} ({cfg.n_layers} layers, {cfg.compute_dtype}):"
        f" {n_req} requests x ({prompt_len} prompt + {gen} new) tokens, "
        f"{ticks} ticks, {ms_per_tick:.3f} ms/tick after the first "
        f"({stamps[1] - stamps[0]:.2f} s, compile included)")

    # every request ran in lockstep from tick 0, so tick t of slot i is
    # position t of request i's fed tokens: its prompt, then its output
    fed = np.asarray([list(prompts[i]) + done[i][:-1] for i in range(n_req)],
                     np.int32)
    if fed.shape[1] != ticks or any(len(done[i]) != gen
                                    for i in range(n_req)):
        raise AssertionError(f"serve: {ticks} ticks for {fed.shape} tokens")
    fed = jnp.asarray(fed)
    got = jnp.stack(seen, axis=1)                              # (n, L, V)
    if not bool(jnp.isfinite(got).all()):
        raise AssertionError("serve: non-finite logits")
    ref_model = build_model(cfg.replace(compute_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: ref_model.forward(p, t)[0])(params, fed)
    out = {"ticks": ticks, "ms_per_tick": ms_per_tick,
           "rel_l2": rel_l2(got, ref),
           "argmax_agreement": float(jnp.mean(
               jnp.argmax(got, -1) == jnp.argmax(ref, -1)))}
    log("serve", f"served logits vs float32 forward: relative L2 "
        f"{out['rel_l2']!r}, argmax agreement {out['argmax_agreement']!r}")
    if f32:
        check("serve", "float32 path: relative L2 to the forward",
              out["rel_l2"], SERVE_F32_TOL)
        return out
    # the same forward in the configuration's own dtype: the rounding a
    # correct bf16 evaluation of this function shows
    own = jax.jit(lambda p, t: model.forward(p, t)[0])(params, fed)
    out["forward_rel_l2"] = rel_l2(own, ref)
    log("serve", f"{cfg.compute_dtype} forward vs float32 forward: "
        f"relative L2 {out['forward_rel_l2']!r}")
    check("serve", "served deviation / forward deviation",
          out["rel_l2"] / out["forward_rel_l2"], SERVE_BF16_RATIO)
    return out


def train_phase(cfg, seed, ckpt_dir, *, batch, seq, steps, mesh=None,
                published_layers=None):
    """A few steps through ``build_training`` and the ``Trainer``; every
    loss finite, parameters changed."""
    tag = "train" if mesh is None else f"train/{cfg.a2a_backend}"
    if published_layers is not None:
        log(tag, f"{cfg.name}: depth cut {published_layers} -> "
            f"{cfg.n_layers} layers, widths as published "
            f"(d_model {cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads},"
            f" d_ff {cfg.d_ff}, vocab {cfg.vocab}"
            + (f", {cfg.n_experts} experts top-{cfg.top_k}"
               if cfg.n_experts else "") + f"); batch {batch} x seq {seq}")
    _, _, params, opt_state, step_fn = build_training(
        cfg, mesh, ShardingRules(), lr=1e-3, warmup=1, total=steps,
        seed=seed)
    def watched(p):     # the step donates params: keep a host copy
        return p["blocks"]["pos0"]["mixer"]["wq"][0, :64]

    before = np.asarray(watched(params), np.float32)
    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                  global_batch=batch, seed=seed),
                       mesh=mesh, task="lm")
    tr = Trainer(TrainerConfig(total_steps=steps,
                               checkpoint_dir=str(ckpt_dir),
                               checkpoint_every=steps + 1, log_every=1),
                 step_fn, data, params, opt_state)
    del params, opt_state
    status = tr.run()
    losses = [row["total_loss"] for row in tr.metrics_log]
    seconds = [row["seconds"] for row in tr.metrics_log]
    changed = float(np.mean(np.asarray(watched(tr.params), np.float32)
                            != before))
    log(tag, f"{status}: losses {losses!r}, step seconds {seconds!r}, "
        f"share of watched weights changed {changed!r}")
    if len(losses) != steps or not all(map(math.isfinite, losses)):
        raise AssertionError(f"{tag}: losses {losses!r}")
    if changed == 0.0:
        raise AssertionError(f"{tag}: parameters did not change")
    return {"losses": losses, "step_seconds": seconds,
            "weights_changed": changed, "n_layers": cfg.n_layers,
            "batch": batch, "seq": seq}


def kernel_phase(seed, *, attn_shape, gmm_shape, reorder_dims,
                 reorder_width):
    """Each Pallas kernel compiled for the chip against ``kernels/ref.py``
    evaluated in float32 on the same operands."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 8)
    def f32(a):
        return a.astype(jnp.float32)

    out = {}

    B, Hq, Hkv, S, hd = attn_shape
    q = jax.random.normal(keys[0], (B, Hq, S, hd), jnp.bfloat16)
    k = jax.random.normal(keys[1], (B, Hkv, S, hd), jnp.bfloat16)
    v = jax.random.normal(keys[2], (B, Hkv, S, hd), jnp.bfloat16)
    dout = jax.random.normal(keys[3], (B, Hq, S, hd), jnp.float32)

    def loss_of(attn):
        return lambda q, k, v: jnp.sum(f32(attn(q, k, v)) * dout)

    o_pal = jax.jit(flash_attention_trainable)(q, k, v)
    g_pal = jax.jit(jax.grad(loss_of(flash_attention_trainable),
                             argnums=(0, 1, 2)))(q, k, v)
    with jax.default_matmul_precision("float32"):
        o_ref = jax.jit(ref_attention)(f32(q), f32(k), f32(v))
        g_ref = jax.jit(jax.grad(loss_of(ref_attention),
                                 argnums=(0, 1, 2)))(f32(q), f32(k), f32(v))
    out["attn_fwd"] = rel_err(o_pal, o_ref)
    shape = f"B={B} Hq={Hq} Hkv={Hkv} S={S} hd={hd}"
    check("kernels", f"flash_attention_trainable forward ({shape})",
          out["attn_fwd"], ATTN_TOL)
    for name, gp, gr in zip(("dq", "dk", "dv"), g_pal, g_ref):
        out[f"attn_{name}"] = rel_err(gp, gr)
        check("kernels", f"flash_attention_trainable {name}",
              out[f"attn_{name}"], ATTN_TOL)

    E, C, D, F = gmm_shape
    for name, (kd, nd) in (("up", (D, F)), ("down", (F, D))):
        lhs = jax.random.normal(keys[4], (E, C, kd), jnp.bfloat16)
        rhs = (jax.random.normal(keys[5], (E, kd, nd), jnp.float32)
               / math.sqrt(kd)).astype(jnp.bfloat16)
        got = jax.jit(grouped_matmul)(lhs, rhs)
        with jax.default_matmul_precision("float32"):
            ref = jax.jit(ref_gmm)(f32(lhs), f32(rhs))
        out[f"gmm_{name}"] = rel_err(got, ref)
        check("kernels", f"grouped_matmul {name} (E={E} C={C} K={kd} "
              f"N={nd})", out[f"gmm_{name}"], GMM_TOL)

    for dims in reorder_dims:
        p = math.prod(dims)
        x = jax.random.normal(keys[6], (p, reorder_width), jnp.float32)
        for kk in range(len(dims)):
            pos, extent = round_datatype(dims, kk)
            packed = datatype_pack(x, dims=dims, k=kk)
            exact = np.array_equal(np.asarray(packed), np.asarray(
                ref_block_reorder(x, pos, extent, dims[kk])))
            back = datatype_unpack(packed, dims=dims, k=kk)
            exact &= np.array_equal(np.asarray(back), np.asarray(x))
            log("kernels", f"datatype_pack/unpack dims={dims} k={kk}: "
                f"bit-exact {exact}")
            if not exact:
                raise AssertionError(f"kernels: datatype_pack/unpack "
                                     f"dims={dims} k={kk} not exact")
    return out


def alltoall_phase(mesh, axes, seed, *, block_elems):
    """``comm.all_to_all`` on the torus: factorized == direct == the
    simulator's permutation, bit for bit, at each block size."""
    links = mesh_links(mesh, axes)
    comm = torus_comm(mesh, axes)
    final, _ = simulate_factorized_alltoall(comm.dims)
    p = comm.p
    perm = np.asarray([[final[r][i] for i in range(p)] for r in range(p)])
    log("alltoall", f"torus dims {comm.dims} over axes {axes}, links "
        f"{links!r}")
    out = {}
    for n in block_elems:
        x = jax.random.normal(jax.random.PRNGKey(seed + n), (p, p, n),
                              jnp.float32)
        res = {be: np.asarray(comm.all_to_all(
            (n,), jnp.float32, backend=be, links=links).host_fn()(x))
            for be in ("factorized", "direct")}
        xs = np.asarray(x)
        expected = xs[perm[..., 0], perm[..., 1]]     # x[src, dst]
        same = np.array_equal(res["factorized"].view(np.uint32),
                              res["direct"].view(np.uint32))
        sim = all(np.array_equal(r.view(np.uint32),
                                 expected.view(np.uint32))
                  for r in res.values())
        log("alltoall", f"block {n * 4} B: factorized == direct {same}, "
            f"both == simulator {sim}")
        if not (same and sim):
            raise AssertionError(f"alltoall: block {n * 4} B mismatch")
        out[f"{n * 4}B"] = True
    return out


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description="TPU bring-up check")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=str(ROOT / "chiprun_out" /
                                         "chip_smoke"))
    args = ap.parse_args(argv)

    device = require_tpu(args.chips)
    cache = enable_compile_cache()
    log("device", f"compilation cache: {cache}")
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    clock = CompileClock()
    summary = {"device": device}

    def run(name, fn, *a, **kw):
        mark, t0 = clock.mark(), time.perf_counter()
        res = fn(*a, **kw)
        res.update(clock.since(mark), wall_s=time.perf_counter() - t0)
        log(name, f"compile {res['compile_s']:.2f} s, "
            f"{res['cache_hits']} cache hits, wall {res['wall_s']:.2f} s")
        summary[name] = res
        gc.collect()        # release the phase's device buffers

    if args.chips == 1:
        qwen = get_config("qwen2.5-3b")
        run("serve", serve_phase, qwen, args.seed, n_req=SERVE_REQUESTS,
            prompt_len=SERVE_PROMPT, gen=SERVE_GEN)
        run("serve_f32", serve_phase,
            qwen.replace(n_layers=SERVE_F32_LAYERS, param_dtype="float32",
                         compute_dtype="float32"),
            args.seed, n_req=SERVE_REQUESTS, prompt_len=SERVE_PROMPT,
            gen=SERVE_GEN)
        run("train", train_phase, qwen.replace(n_layers=QWEN_TRAIN_LAYERS),
            args.seed, out_dir / "ckpt" / "qwen", batch=QWEN_TRAIN_BATCH,
            seq=QWEN_TRAIN_SEQ, steps=TRAIN_STEPS,
            published_layers=qwen.n_layers)
        run("kernels", kernel_phase, args.seed,
            attn_shape=(1, qwen.n_heads, qwen.n_kv_heads, 2048, qwen.hd),
            gmm_shape=(16, 256, 4096, 6400),
            reorder_dims=((2, 2), (2, 3, 4)), reorder_width=4096)
    else:
        mesh = make_host_mesh(jax.devices()[:4])
        axes = ep_axes(mesh)
        run("alltoall", alltoall_phase, mesh, axes, args.seed,
            block_elems=(1024, 1024 * 1024))
        phi = get_config("phi3.5-moe-42b")
        losses = {}
        for backend in ("factorized", "direct"):
            cfg = phi.replace(n_layers=MOE_TRAIN_LAYERS, a2a_backend=backend)
            run(f"moe_{backend}", train_phase, cfg, args.seed,
                out_dir / "ckpt" / f"moe_{backend}", batch=MOE_TRAIN_BATCH,
                seq=MOE_TRAIN_SEQ, steps=TRAIN_STEPS, mesh=mesh,
                published_layers=phi.n_layers)
            losses[backend] = summary[f"moe_{backend}"]["losses"]
        rel = [abs(f - d) / abs(d)
               for f, d in zip(losses["factorized"], losses["direct"])]
        summary["moe_loss_rel_diff"] = rel
        log("moe", f"losses factorized == direct bit for bit: "
            f"{losses['factorized'] == losses['direct']}")
        check("moe", "first-loss relative difference", rel[0],
              MOE_FIRST_LOSS_RTOL)
        check("moe", "largest loss relative difference", max(rel),
              MOE_LOSS_RTOL)

    (out_dir / f"summary_{args.chips}chip.json").write_text(
        json.dumps(summary, indent=1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
