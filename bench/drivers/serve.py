"""Serving cells: an open loop of requests into the colocated
``ContinuousBatcher``, one chip.

Set-up makes the weights from the seed on the device (one jitted call, in
the served dtype), builds the program's serve step and batcher, and warms
up every shape the window uses: the step at ``max_batch`` slots and the
reset of every slot.  The window then submits each request at its
scheduled time and drives ``ContinuousBatcher.step`` until every request
that arrived has finished.  Each request is timed from its scheduled
arrival: time to first token, and (last token - first token) / (tokens -
1) between tokens.

Afterwards, with the program's state freed, a sample of the finished
requests drawn from the seed, the longest among them, goes through the
plain float32 reference: for each served token, how far its logit lies
below the reference's best at that position.
"""

from __future__ import annotations

import gc
import time
from collections import deque

import numpy as np

from bench import harness
from bench.harness import Check, Outcome, span
from bench.peaks import decode_tick_least_s
from bench.traffic import gen


class Probe:
    """Reads the batcher's slots at each tick, inside the serve step's
    call: occupied slots, slots feeding a prompt token, and (while a trace
    runs) the least time of the tick on this chip."""

    def __init__(self, config, peaks):
        self.config = config
        self.peaks = peaks
        self.batcher = None
        self.occupied = 0
        self.prefill = 0
        self.tracing = False
        self.traced_ticks = 0
        self.traced_least_s = 0.0

    def wrap(self, step):
        def probed(params, toks, caches):
            b = self.batcher
            ctx = []
            for i, req in enumerate(b.slots):
                if req is None:
                    continue
                cur = b.prefill_cursor[i]
                if cur < len(req.prompt):
                    self.prefill += 1
                    ctx.append(cur + 1)
                else:
                    ctx.append(len(req.prompt) + len(req.generated))
            self.occupied += len(ctx)
            if self.tracing:
                self.traced_ticks += 1
                self.traced_least_s += decode_tick_least_s(
                    self.config, ctx, self.peaks)
            return step(params, toks, caches)
        return probed


def program(config: dict):
    """The program's model for this configuration, its widths checked
    against the configuration file."""
    from repro.configs import get_config
    from repro.models import build_model
    prog = get_config(config["registry"]).replace(**config.get("program", {}))
    want = {"d_model": config["hidden_size"], "d_ff":
            config["intermediate_size"], "vocab": config["vocab_size"],
            "n_layers": config["num_hidden_layers"],
            "n_heads": config["num_attention_heads"],
            "n_kv_heads": config["num_key_value_heads"],
            "window": config.get("sliding_window"),
            "rope_theta": config["rope_theta"],
            "tie_embeddings": config["tie_word_embeddings"]}
    got = {k: getattr(prog, k) for k in want}
    if got != want:
        raise ValueError(f"the program's configuration {got} is not the "
                         f"benchmark's {want}")
    return prog, build_model(prog)


def check_layout(model, weights, key):
    import jax
    want = jax.eval_shape(model.init, key)
    sig = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), want)
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), weights)
    if sig != have:
        raise ValueError(f"weight layout {have} is not the program's {sig}")


def p95(values, default: float) -> float:
    """The 95th percentile; ``default`` where no request finished."""
    if not len(values):
        return default
    return float(np.percentile(np.asarray(values, np.float64), 95))


def p50(values) -> float:
    return float(np.median(values)) if len(values) else float("nan")


def compare(c, mix, seed, reqs, done, weights, max_seq, control=False):
    """Over a sample of finished requests drawn from the seed, with the
    longest among them: by how much each served token's logit lies below
    the float32 reference's best at its position, as the widest gap and
    the mean gap over the served tokens.  With ``control`` the same for
    the tokens that the fp8 reference puts first, on the same prompts and
    tokens.  Returns (requests compared, [program stats, control
    stats])."""
    import jax
    import jax.numpy as jnp
    ref = harness.load_reference(c)
    specs = {r.rid: r for r in reqs}
    finished = sorted(done, key=lambda r: (-(len(specs[r].prompt)
                                             + len(done[r])), r))
    n = min(int(mix["check_requests"]), len(finished))
    if not n:
        return [], []
    pick = finished[:1] + sorted(gen.rng(seed, "check").choice(
        finished[1:], n - 1, replace=False).tolist())
    fed = np.zeros((n, max_seq), np.int32)
    firsts = np.zeros(n, np.int32)
    counts = np.zeros(n, np.int32)
    for i, rid in enumerate(pick):
        toks = list(specs[rid].prompt) + done[rid]
        fed[i, :len(toks)] = toks
        firsts[i] = len(specs[rid].prompt) - 1
        counts[i] = len(done[rid])
    args = (weights(), jnp.asarray(fed), jnp.asarray(firsts),
            jnp.asarray(counts))

    def stats(w, f, a, b, ctl):
        g = ref.served_gaps(c, w, f, a, b, control=ctl)
        return jnp.max(g), jnp.sum(g) / jnp.sum(b)

    run_stats = jax.jit(stats, static_argnames="ctl")
    out = []
    for ctl in (False, True) if control else (False,):
        mx, mean = run_stats(*args, ctl=ctl)
        out.append({"max": float(mx), "mean": float(mean)})
    return pick, out


def run(cell, *, seed, seconds, trace, peaks, hooks, control=False):
    harness.use_program()
    import jax
    from repro.launch.serve import batcher_step
    from repro.models import make_serve_step
    from repro.parallel.sharding import ShardingRules
    from repro.runtime.serving import ContinuousBatcher, Request

    c, mix = cell.config, cell.mix
    ref = harness.load_reference(c)
    device = jax.devices()[0]
    prog, model = program(c)
    max_batch, max_seq = c["serve"]["max_batch"], c["serve"]["max_seq"]
    key = jax.random.key(gen.jax_seed(seed, "weights"))
    make_weights = jax.jit(lambda k: ref.make_weights(c, k, prog.pdtype))
    with jax.default_device(device):
        params = make_weights(key)
        check_layout(model, params, key)
        probe = Probe(c, peaks)
        step = jax.jit(make_serve_step(model, None, ShardingRules()))
        batcher = ContinuousBatcher(
            model, params, max_batch=max_batch, max_seq=max_seq,
            serve_step=probe.wrap(batcher_step(step)))
        probe.batcher = batcher
        # warm-up: every slot is reset and stepped once
        for i in range(max_batch):
            batcher.submit(Request(-1 - i, [1, 2], 2))
        batcher.run()
        batcher.done.clear()
        probe.occupied = probe.prefill = 0
        reqs = gen.requests(mix, seconds, seed, c["vocab_size"], max_seq)
        tw = harness.TracedWindow(trace)
        trace_from = seconds / 2 - mix["trace_seconds"] / 2
        hooks.setup_done()

        pending = deque(reqs)
        inflight: list = []
        first, last = {}, {}
        late = []
        ticks = 0
        t0 = time.perf_counter()
        deadline = seconds + mix["drain_limit_s"]
        while True:
            now = time.perf_counter() - t0
            while pending and pending[0].due_s <= now:
                spec = pending.popleft()
                req = Request(spec.rid, spec.prompt.tolist(), spec.max_new)
                batcher.submit(req)
                inflight.append(req)
                late.append(now - spec.due_s)
            if tw.enabled and not tw.done:
                if not tw.active and now >= trace_from:
                    tw.start()
                    probe.tracing = True
                elif tw.active and now >= trace_from + mix["trace_seconds"]:
                    tw.stop()
                    probe.tracing = False
            if not inflight:
                if not pending:
                    break
                with span("idle"):
                    time.sleep(max(0.0, pending[0].due_s - now))
                continue
            if now > deadline:
                break
            with span("tick"):
                batcher.step()
            ticks += 1
            t = time.perf_counter() - t0
            with span("record"):
                still = []
                for req in inflight:
                    n = len(req.generated)
                    if n and req.rid not in first:
                        first[req.rid] = t
                    if n:
                        last[req.rid] = t
                    if req.rid not in batcher.done:
                        still.append(req)
                inflight = still
        t_end = time.perf_counter() - t0
        tw.stop()
        probe.tracing = False
        hooks.window_done()
        mem = harness.memory_peak_bytes([device])
        tw.reduce()

        due = {r.rid: r.due_s for r in reqs}
        done = dict(batcher.done)
        ttft = [first[r] - due[r] for r in done]
        tpot = [(last[r] - first[r]) / (len(done[r]) - 1)
                for r in done if len(done[r]) > 1]
        failed = len(reqs) - len(done)

        # ---- comparison with the plain reference, after the window ----
        del batcher, params, step, inflight
        probe.batcher = None
        gc.collect()
        pick, gaps = compare(c, mix, seed, reqs, done, lambda: make_weights(
            key), max_seq, control)
        compared = sum(len(done[r]) for r in pick)
        limits = c["serve"]["gap_limits"]
        checks = [Check(f"served_logit_gap_{k}", gaps[0][k], float(v))
                  for k, v in limits.items()] if pick else []
        notes = [
            f"{len(reqs)} requests, {len(done)} finished, {ticks} ticks in "
            f"{t_end:.3f} s; ttft median {p50(ttft) * 1e3:.1f} ms, "
            f"p95 {p95(ttft, t_end) * 1e3:.1f} ms; tpot median "
            f"{p50(tpot) * 1e3:.2f} ms, p95 {p95(tpot, t_end) * 1e3:.2f} ms",
            f"generator lateness: max {max(late, default=0) * 1e3:.2f} ms",
            f"compared {compared} served tokens of requests {pick}: "
            f"gaps {gaps}"]
    counters = {"control_gap": gaps[1] if control and pick else None,
                "ttft_s": ttft, "due_s": [due[r] for r in done],
                "occupied_slot_ticks": probe.occupied,
                "prefill_slot_ticks": probe.prefill,
                "traced_ticks": probe.traced_ticks,
                "traced_least_s": probe.traced_least_s}
    return Outcome(
        e2e={"ttft_p95_ms": p95(ttft, t_end) * 1e3,
             "tpot_p95_ms": p95(tpot, t_end) * 1e3},
        checks=checks, attempted=len(reqs), failed=failed,
        memory_peak_bytes=mem, counters=counters, traced=tw, notes=notes)
