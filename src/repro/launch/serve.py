"""Serving launcher over the unified serving API.

Colocated continuous batching (default) or prefill/decode disaggregation
(``--disaggregate``: one torus partitioned into the two domains, KV
handoff through the ``KVMigrationPlan``):

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --batch 4 --prompt-len 16 --gen 24
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
      --disaggregate --torus-p 6 --batch 4 --prompt-len 16 --gen 24

The hand-rolled prefill + decode loop this launcher used to carry is
retired; ``legacy_prefill_decode`` remains as a DeprecationWarning shim
delegating to :class:`~repro.runtime.serving.ContinuousBatcher` (the PR 2
policy — external callers keep working, internal call sites fail the
warning-as-error CI leg).
"""

from __future__ import annotations

import argparse
import time
import warnings

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model, make_serve_step
from repro.parallel.sharding import ShardingRules
from repro.runtime.serving import ContinuousBatcher, DisaggregatedServer, \
    Request


def batcher_step(serve, memory=None):
    """Adapt ``make_serve_step``'s ``(params, caches, toks[, memory]) ->
    (nxt, logits, caches)`` to the batcher's ``(params, toks, caches) ->
    (logits, caches)`` contract.  A fixed ``memory`` (enc-dec frontend)
    rides along — valid when slot ``i`` serves request ``i``, i.e.
    ``max_batch == len(requests)``."""
    def step(params, toks, caches):
        _, logits, caches = serve(params, caches, toks, memory)
        return logits, caches
    return step


def legacy_prefill_decode(model, params, serve, prompts, gen, memory=None):
    """Deprecated: the launcher's old ad-hoc prefill + decode loop.

    Delegates to the unified serving API (one
    :class:`~repro.runtime.serving.ContinuousBatcher`); construct that —
    or :class:`~repro.runtime.serving.DisaggregatedServer` — directly.
    """
    warnings.warn(
        "repro.launch.serve.legacy_prefill_decode is deprecated; "
        "construct the unified serving API (runtime.serving"
        ".ContinuousBatcher / DisaggregatedServer) instead",
        DeprecationWarning, stacklevel=2)
    B, L = prompts.shape
    batcher = ContinuousBatcher(
        model, params, max_batch=B, max_seq=L + gen,
        serve_step=batcher_step(serve, memory))
    for i in range(B):
        batcher.submit(Request(i, [int(t) for t in prompts[i]], gen))
    done = batcher.run()
    return jnp.asarray([done[i] for i in range(B)], jnp.int32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--disaggregate", action="store_true",
                    help="serve through a prefill/decode-partitioned "
                    "torus with KV migration between the domains")
    ap.add_argument("--torus-p", type=int, default=6,
                    help="serving torus size for --disaggregate "
                    "(device-agnostic: ranks model the placement)")
    ap.add_argument("--n-prefill", type=int, default=None,
                    help="prefill ranks (default: cost-model split)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    rules = ShardingRules()
    serve = jax.jit(make_serve_step(model, None, rules))

    B = args.batch
    max_seq = args.prompt_len + args.gen
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (B, args.prompt_len), 0, cfg.vocab)

    memory = None
    if cfg.encoder_layers:
        fe = jax.random.normal(jax.random.PRNGKey(2),
                               (B, cfg.n_frontend_tokens, cfg.d_model))
        memory = model.encode(params, fe)

    reqs = [Request(i, [int(t) for t in prompts[i]], args.gen)
            for i in range(B)]
    t0 = time.perf_counter()
    if args.disaggregate:
        if memory is not None:
            raise SystemExit("--disaggregate does not support enc-dec "
                             "archs (frontend memory is not migrated)")
        from repro.core import torus_comm
        from repro.core.dims import dims_create
        dims = tuple(reversed(dims_create(args.torus_p, 2)))
        comm = torus_comm(dims, tuple(f"s{i}" for i in range(len(dims))))
        server = DisaggregatedServer(
            model, params, comm, max_seq=max_seq, decode_batch=B,
            n_prefill=args.n_prefill,
            serve_step=batcher_step(serve))
        for r in reqs:
            server.submit(r)
        done = server.run()
        ticks = server.ticks
        stats = server.stats()
        topo = stats["topology"]
        print(f"[serve] disaggregated: {topo['n_prefill']} prefill + "
              f"{topo['n_decode']} decode ranks on torus {dims}, "
              f"{topo['migrations']} migrations "
              f"({topo['migrated_rows']} KV rows, plan="
              f"{topo['plan']['inner_kind']})")
    else:
        batcher = ContinuousBatcher(
            model, params, max_batch=B, max_seq=max_seq,
            serve_step=batcher_step(serve, memory))
        for r in reqs:
            batcher.submit(r)
        done = batcher.run()
        ticks = batcher.ticks
    elapsed = time.perf_counter() - t0

    out = jnp.asarray([done[i] for i in range(B)], jnp.int32)
    print(f"[serve] arch={cfg.name} batch={B} prompt={args.prompt_len} "
          f"gen={args.gen}")
    print(f"[serve] {ticks} ticks, {elapsed * 1e3 / max(1, ticks):.2f} "
          f"ms/tick, {elapsed:.2f} s total")
    print(f"[serve] sample tokens: {out[0][:12].tolist()}")
    return out


if __name__ == "__main__":
    main()
