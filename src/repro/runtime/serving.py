"""Serving spine: continuous batching plus prefill/decode disaggregation.

Two serving modes share one model contract (``init_caches`` /
``decode_step`` with per-slot positions — all decoder archs in this
repo, incl. ring-buffer SWA caches):

* **Colocated** — :class:`ContinuousBatcher`: slot-based serving loop
  (vLLM-style scheduling, dense slots).  One jitted ``decode_step``
  advances every active slot one token per tick; slots in *prefill*
  phase consume their next prompt token (logits ignored), slots in
  *decode* phase consume their previously generated token.  Finished
  slots are reset (per-slot cache re-init) and refilled from the queue —
  no global pipeline stall when one request ends, which is the whole
  point vs static batching.

* **Disaggregated** — :class:`DisaggregatedServer`: one
  :class:`~repro.core.comm.TorusComm` partitioned into a prefill domain
  and a decode domain (:class:`ServingTopology`, via
  ``TorusComm.partition``), prompt ingestion chunked through
  :class:`PrefillWorker` instances, the same :class:`ContinuousBatcher`
  as the decode side, and the KV-cache handoff between the domains
  expressed as a :class:`~repro.core.plan.KVMigrationPlan` — per-slot KV
  rows are the Alltoallv elements (:class:`KVRowCodec`), per-sequence
  variable lengths the send counts, the scheduler's placement the
  router.  A multi-tenant :class:`AdmissionController` applies
  per-tenant quotas and FIFO-within-tenant ordering, and free decode
  slots backpressure prompt admission.  Elasticity composes with PR 6:
  ``DisaggregatedServer.rebuild`` re-partitions both domains over the
  survivors and replays every in-flight request (``requeue_inflight``
  token folding) — nothing dropped, outputs unchanged.

Because ``decode_step`` advances each batch row independently, a
request's generated tokens depend only on its own token feed and cache
rows — so disaggregated serving is bit-exact with the colocated
reference under any scheduling (device-tested, incl. across a
mid-stream rebuild).
"""

from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    eos_id: int | None = None
    tenant: str = "default"
    generated: list[int] = field(default_factory=list)
    # how many generated tokens are already folded into ``prompt`` by
    # ``requeue_inflight`` — keeps a second requeue from re-folding them
    folded: int = 0
    # ``time.perf_counter`` stamps: submitted to the server, and given a
    # decode slot (the last admission, after a requeue)
    submitted_s: float | None = None
    admitted_s: float | None = None


def _finished(req: Request) -> bool:
    return len(req.generated) >= req.max_new or (
        req.eos_id is not None and bool(req.generated)
        and req.generated[-1] == req.eos_id)


def _reset_slot(caches, fresh, b: int):
    """Copy slot b's state from a freshly initialized cache tree.
    Layer-state leaves carry batch on axis 1 (stacked layers first);
    the position vector carries it on axis 0."""
    def f(cur, new):
        if cur.ndim >= 2:
            return cur.at[:, b].set(new[:, b])
        return cur.at[b].set(new[b])
    states = jax.tree.map(f, caches["states"], fresh["states"])
    pos = caches["pos"].at[b].set(0)
    return {"states": states, "pos": pos}


# ---------------------------------------------------------------------------
# The KV-row datatype: per-slot cache rows <-> flat Alltoallv elements
# ---------------------------------------------------------------------------


class KVRowCodec:
    """The derived-datatype layer of the KV handoff: one *row* per
    sequence slot of the cache, across every layer-state leaf.

    Built from ``cache_logical_axes`` — each state leaf with a
    ``"seq_sp"`` logical axis contributes its per-slot features
    (``slot_pos`` included, so ring-buffer SWA caches migrate exactly).
    ``pack`` flattens one batch slot's first ``n_rows`` sequence slots to
    an ``(n_rows, row_features)`` float32 array — the element type of
    the :class:`~repro.core.plan.KVMigrationPlan`; ``unpack`` is the
    exact inverse into a freshly reset destination slot.

    Families whose recurrent state has no sequence axis (SSM / xLSTM)
    cannot split a sequence between domains; construction fails with a
    clear error rather than migrating silently-wrong state.
    """

    def __init__(self, model, max_seq: int):
        from ..models.transformer import cache_logical_axes
        logical = cache_logical_axes(model.cfg)["states"]
        shapes = jax.eval_shape(
            lambda: model.init_caches(1, int(max_seq)))["states"]
        axes_leaves = jax.tree.leaves(
            logical, is_leaf=lambda x: isinstance(x, tuple))
        shape_leaves = jax.tree.leaves(shapes)
        if len(axes_leaves) != len(shape_leaves):
            raise ValueError("cache_logical_axes does not match "
                             "init_caches structure")
        self._specs: list[tuple[int, int, int]] = []
        seq = None
        feats = 0
        for ax, sh in zip(axes_leaves, shape_leaves):
            if "seq_sp" not in ax or "batch" not in ax:
                raise ValueError(
                    "disaggregated serving needs per-slot sequence-sliced "
                    f"caches; a state leaf with logical axes {ax} has no "
                    "seq_sp axis (recurrent-state family, e.g. SSM/xLSTM "
                    "— its state cannot be split into KV rows)")
            bi, si = ax.index("batch"), ax.index("seq_sp")
            if seq is None:
                seq = int(sh.shape[si])
            elif int(sh.shape[si]) != seq:
                raise ValueError(f"unequal sequence extents across state "
                                 f"leaves: {sh.shape[si]} != {seq}")
            feat = 1
            for i, s in enumerate(sh.shape):
                if i not in (bi, si):
                    feat *= int(s)
            self._specs.append((bi, si, feat))
            feats += feat
        self.seq_slots = int(seq)
        self.row_features = int(feats)

    @property
    def row_shape(self) -> tuple[int, ...]:
        return (self.row_features,)

    def rows_for(self, prompt_len: int) -> int:
        """Sequence slots holding live state after prefilling
        ``prompt_len`` tokens — the per-sequence send count (ring-buffer
        SWA caps it at the window)."""
        return min(int(prompt_len), self.seq_slots)

    def pack(self, states, b: int, n_rows: int) -> np.ndarray:
        """Flatten batch slot ``b``'s first ``n_rows`` sequence slots of
        every state leaf into ``(n_rows, row_features)`` float32."""
        segs = []
        for (bi, si, feat), a in zip(self._specs, jax.tree.leaves(states)):
            moved = jnp.moveaxis(a, (bi, si), (0, 1))[b, :n_rows]
            segs.append(np.asarray(moved).reshape(n_rows, feat)
                        .astype(np.float32))
        return np.concatenate(segs, axis=1) if segs \
            else np.zeros((n_rows, 0), np.float32)

    def unpack(self, states, b: int, rows) -> object:
        """The exact inverse of :meth:`pack`: write ``rows`` into batch
        slot ``b``'s leading sequence slots (the slot must have been
        freshly reset, so untouched trailing slots match the source)."""
        rows = np.asarray(rows, np.float32)
        n = rows.shape[0]
        leaves, treedef = jax.tree.flatten(states)
        out, off = [], 0
        for (bi, si, feat), a in zip(self._specs, leaves):
            seg = rows[:, off:off + feat]
            off += feat
            moved = jnp.moveaxis(a, (bi, si), (0, 1))
            seg = jnp.asarray(seg, np.float32).reshape(
                (n,) + moved.shape[2:]).astype(a.dtype)
            moved = moved.at[b, :n].set(seg)
            out.append(jnp.moveaxis(moved, (0, 1), (bi, si)))
        return treedef.unflatten(out)


# ---------------------------------------------------------------------------
# Colocated serving (the decode side of the disaggregated topology)
# ---------------------------------------------------------------------------


class ContinuousBatcher:
    def __init__(self, model, params, *, max_batch: int, max_seq: int,
                 serve_step=None, comm=None):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        # The communicator this batcher serves over (optional): the
        # comm-rooted construction surfaces its cache picture through
        # ``stats()`` and scopes a later ``comm.free()`` teardown.
        self.comm = comm
        self.caches = model.init_caches(max_batch, max_seq)
        self._fresh = self.caches
        self.slots: list[Request | None] = [None] * max_batch
        self.prefill_cursor = [0] * max_batch
        self.queue: list[Request] = []
        self.done: dict[int, list[int]] = {}
        if serve_step is None:
            def serve_step(params, toks, caches):
                return model.decode_step(params, toks, caches)
            serve_step = jax.jit(serve_step)
        self._step = serve_step
        self.ticks = 0

    # ---- scheduling ----
    def submit(self, req: Request):
        req.submitted_s = time.perf_counter()
        self.queue.append(req)

    @property
    def pending(self) -> int:
        """Requests not yet finished: queued plus in-flight."""
        return len(self.queue) + sum(s is not None for s in self.slots)

    @property
    def free_slots(self) -> int:
        return sum(s is None for s in self.slots)

    def admit_prefilled(self, req: Request, rows, pos: int, *,
                        codec: KVRowCodec) -> bool:
        """Admit a request whose prompt was prefilled elsewhere: reset a
        free slot, unpack the migrated KV rows into it, and resume in
        decode phase (cursor past the prompt, position at ``pos``).
        Returns False when no slot is free."""
        for b in range(self.max_batch):
            if self.slots[b] is None:
                break
        else:
            return False
        self.caches = _reset_slot(self.caches, self._fresh, b)
        states = codec.unpack(self.caches["states"], b, rows)
        self.caches = {"states": states,
                       "pos": self.caches["pos"].at[b].set(int(pos))}
        self.slots[b] = req
        self.prefill_cursor[b] = len(req.prompt)
        req.admitted_s = time.perf_counter()
        return True

    # ---- elasticity ----
    def requeue_inflight(self) -> int:
        """Pull every in-flight request back to the front of the queue
        for deterministic replay after a device loss: the tokens already
        generated are folded into the prompt, so re-admission replays
        the exact token feed (prompt, then prior generations) through
        prefill and resumes decoding where the request left off —
        nothing is dropped, outputs are unchanged.  Returns how many
        requests were requeued."""
        moved = []
        for b, req in enumerate(self.slots):
            if req is None:
                continue
            req.prompt = list(req.prompt) + list(req.generated[req.folded:])
            req.folded = len(req.generated)
            moved.append(req)
            self.slots[b] = None
            self.prefill_cursor[b] = 0
        self.queue[:0] = moved
        return len(moved)

    def rebuild(self, *, model=None, params=None, serve_step=None) -> int:
        """After device loss: requeue all in-flight requests, then
        rebuild the slot caches (and optionally swap model / resharded
        params / jitted step) on the surviving device set.  The queue —
        including the requeued in-flight work — drains on the next
        ``step()``/``run()``; no request is dropped."""
        n = self.requeue_inflight()
        if model is not None:
            self.model = model
        if params is not None:
            self.params = params
        self.caches = self.model.init_caches(self.max_batch, self.max_seq)
        self._fresh = self.caches
        self.prefill_cursor = [0] * self.max_batch
        if serve_step is not None:
            self._step = serve_step
        elif model is not None or params is not None:
            model_ = self.model

            def default_step(params, toks, caches):
                return model_.decode_step(params, toks, caches)
            self._step = jax.jit(default_step)
        return n

    def _admit(self):
        for b in range(self.max_batch):
            if self.slots[b] is None and self.queue:
                req = self.queue.pop(0)
                self.caches = _reset_slot(self.caches, self._fresh, b)
                self.slots[b] = req
                self.prefill_cursor[b] = 0
                req.admitted_s = time.perf_counter()

    def _next_tokens(self) -> np.ndarray:
        toks = np.zeros((self.max_batch, 1), np.int32)
        for b, req in enumerate(self.slots):
            if req is None:
                continue
            c = self.prefill_cursor[b]
            if c < len(req.prompt):
                toks[b, 0] = req.prompt[c]
            else:
                toks[b, 0] = req.generated[-1]
        return toks

    # ---- main loop ----
    def step(self):
        """One tick: admit, feed one token per occupied slot, dispatch
        the step, pull the next tokens, book them.  Each phase is a
        ``serve.step.*`` span under ``serve.step``."""
        tr = telemetry.get_tracer()
        with tr.span("serve.step", cat="serving", tick=self.ticks):
            with tr.span("serve.step.admit", cat="serving"):
                self._admit()
            if all(s is None for s in self.slots):
                return False
            with tr.span("serve.step.feed", cat="serving"):
                toks = jnp.asarray(self._next_tokens())
            with tr.span("serve.step.dispatch", cat="serving"):
                logits, self.caches = self._step(self.params, toks,
                                                 self.caches)
            with tr.span("serve.step.pull", cat="serving"):
                nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1),
                                 np.int32)
            with tr.span("serve.step.bookkeep", cat="serving"):
                for b, req in enumerate(self.slots):
                    if req is None:
                        continue
                    c = self.prefill_cursor[b]
                    if c < len(req.prompt) - 1:
                        self.prefill_cursor[b] = c + 1     # still prefilling
                        continue
                    if c == len(req.prompt) - 1:
                        self.prefill_cursor[b] = c + 1     # first generation
                    req.generated.append(int(nxt[b]))
                    if _finished(req):
                        self.done[req.rid] = list(req.generated)
                        self.slots[b] = None               # free -> re-admit
            self.ticks += 1
        return True

    def run(self, max_ticks: int = 100_000):
        while self.step() and self.ticks < max_ticks:
            pass
        return self.done

    # ---- introspection ----
    def stats(self) -> dict:
        """One call for the serving picture: scheduling counters plus the
        unified all-to-all cache state (``a2a_comm_stats``) — scoped to
        this batcher's comm when it owns one, registry-wide otherwise."""
        from ..core.comm import unified_stats
        return {
            "ticks": self.ticks,
            "max_batch": self.max_batch,
            "queued": len(self.queue),
            "active": sum(s is not None for s in self.slots),
            "done": len(self.done),
            "a2a_comm_stats": unified_stats() if self.comm is None
            else self.comm.stats(),
        }


# ---------------------------------------------------------------------------
# Disaggregated serving: prefill domain, admission, topology, server
# ---------------------------------------------------------------------------


class PrefillWorker:
    """One prefill rank: chunked prompt ingestion into its own slot
    caches.  ``step()`` advances up to ``chunk`` tokens per serving tick
    (bounding prefill latency injected between decode ticks); a sequence
    whose prompt is fully consumed produces its first generated token,
    is packed to KV rows immediately (before any later tick could
    ring-wrap over them), and leaves the worker — the handoff payload.
    """

    def __init__(self, model, params, *, max_batch: int, max_seq: int,
                 codec: KVRowCodec, chunk: int = 4, serve_step=None):
        self.model = model
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.codec = codec
        self.chunk = max(1, int(chunk))
        self.caches = model.init_caches(max_batch, max_seq)
        self._fresh = self.caches
        self.slots: list[Request | None] = [None] * max_batch
        self.cursor = [0] * max_batch
        if serve_step is None:
            def serve_step(params, toks, caches):
                return model.decode_step(params, toks, caches)
            serve_step = jax.jit(serve_step)
        self._step = serve_step
        self.ticks = 0

    @property
    def active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def free_slots(self) -> int:
        return sum(s is None for s in self.slots)

    def admit(self, req: Request) -> bool:
        for b in range(self.max_batch):
            if self.slots[b] is None:
                self.caches = _reset_slot(self.caches, self._fresh, b)
                self.slots[b] = req
                self.cursor[b] = 0
                return True
        return False

    def step(self) -> list[tuple[Request, np.ndarray, int]]:
        """Run up to ``chunk`` prefill ticks; returns the completed
        handoffs as ``(request, kv_rows, position)`` triples."""
        out = []
        for _ in range(self.chunk):
            if all(s is None for s in self.slots):
                break
            toks = np.zeros((self.max_batch, 1), np.int32)
            for b, req in enumerate(self.slots):
                if req is not None:
                    toks[b, 0] = req.prompt[self.cursor[b]]
            logits, self.caches = self._step(self.params,
                                             jnp.asarray(toks), self.caches)
            nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
            for b, req in enumerate(self.slots):
                if req is None:
                    continue
                c = self.cursor[b]
                if c < len(req.prompt) - 1:
                    self.cursor[b] = c + 1             # still prefilling
                    continue
                # last prompt token consumed: first generation, then pack
                # the KV rows before any later tick can overwrite them
                self.cursor[b] = c + 1
                req.generated.append(int(nxt[b]))
                n_rows = self.codec.rows_for(len(req.prompt))
                rows = self.codec.pack(self.caches["states"], b, n_rows)
                out.append((req, rows, len(req.prompt)))
                self.slots[b] = None
            self.ticks += 1
        return out

    def requeue_inflight(self) -> list[Request]:
        """Drain in-flight prompts for replay on a rebuilt topology (a
        prefilling request has no folded state to preserve — its prompt
        simply replays from the start)."""
        moved = [req for req in self.slots if req is not None]
        self.slots = [None] * self.max_batch
        self.cursor = [0] * self.max_batch
        return moved


class AdmissionController:
    """Multi-tenant admission: FIFO within each tenant, round-robin
    across tenants, per-tenant in-flight quotas (``quotas`` overrides
    per tenant; ``default_quota`` applies otherwise, ``None`` =
    unlimited).  The server's decode-slot backpressure sets how many
    requests each ``admit`` call may release."""

    def __init__(self, *, quotas=None, default_quota: int | None = None):
        self.quotas = dict(quotas or {})
        self.default_quota = default_quota
        self.queues: dict[str, deque] = {}
        self.inflight: dict[str, int] = {}
        self._order: list[str] = []
        self._rr = 0

    def submit(self, req: Request):
        if req.tenant not in self.queues:
            self.queues[req.tenant] = deque()
            self._order.append(req.tenant)
        self.queues[req.tenant].append(req)

    def requeue_front(self, reqs) -> None:
        """Push replayed requests back to the *front* of their tenants'
        queues (deterministic replay after a rebuild: requeued work
        precedes anything newly submitted)."""
        for req in reversed(list(reqs)):
            if req.tenant not in self.queues:
                self.queues[req.tenant] = deque()
                self._order.append(req.tenant)
            self.queues[req.tenant].appendleft(req)

    def quota(self, tenant: str) -> int | None:
        return self.quotas.get(tenant, self.default_quota)

    @property
    def pending(self) -> int:
        return sum(len(q) for q in self.queues.values())

    def admit(self, n: int) -> list[Request]:
        """Release up to ``n`` requests, rotating across tenants."""
        out: list[Request] = []
        while len(out) < n and self._order:
            progressed = False
            for _ in range(len(self._order)):
                t = self._order[self._rr % len(self._order)]
                self._rr += 1
                q = self.queues.get(t)
                if not q:
                    continue
                quota = self.quota(t)
                if quota is not None and self.inflight.get(t, 0) >= quota:
                    continue
                out.append(q.popleft())
                self.inflight[t] = self.inflight.get(t, 0) + 1
                progressed = True
                if len(out) >= n:
                    break
            if not progressed:
                break
        return out

    def release(self, req: Request) -> None:
        self.inflight[req.tenant] = max(
            0, self.inflight.get(req.tenant, 0) - 1)


class ServingTopology:
    """One serving torus partitioned into prefill and decode domains.

    ``comm.partition(n_prefill)`` yields the two domain sub-comms
    (``MPI_Comm_split`` by device range); the KV handoff between them is
    one :class:`~repro.core.plan.KVMigrationPlan` over the *full* comm —
    ranks ``0..n_prefill-1`` are prefill sources, the rest decode
    destinations.  When ``n_prefill`` is omitted the split is sized by
    the alpha-beta model (``core.tuning.choose_serving_split``): the
    predicted migration cost is part of the per-tick objective, so a
    torus with slow links leans toward fewer, longer-lived migrations.
    """

    def __init__(self, comm, *, row_shape, max_count: int,
                 dtype="float32", n_prefill: int | None = None,
                 migrations_per_tick: float = 1.0, backend: str = "tuned",
                 links=None):
        from ..core.tuning import choose_serving_split
        self.split = None
        if n_prefill is None:
            row_bytes = math.prod(tuple(row_shape)) \
                * jnp.dtype(dtype).itemsize
            self.split = choose_serving_split(
                comm.dims, links, row_bytes=float(row_bytes),
                max_count=int(max_count),
                migrations_per_tick=migrations_per_tick)
            n_prefill = self.split.n_prefill
        self.comm = comm
        self.n_prefill = int(n_prefill)
        self.prefill_comm, self.decode_comm = comm.partition(self.n_prefill)
        self.plan = comm.kv_migration(
            tuple(row_shape), dtype, max_count=int(max_count),
            n_prefill=self.n_prefill,
            migrations_per_tick=migrations_per_tick, backend=backend,
            links=links)
        self.migrated_rows = 0
        self.migrations = 0

    @property
    def n_decode(self) -> int:
        return self.comm.p - self.n_prefill

    def migrate(self, rows_by_pair: dict, *, device=None) -> dict:
        """Execute one KV handoff tick: ``{(src, dst): [row, ...]}`` in,
        the delivered rows per pair out — ONE collective through the
        plan, never a per-sequence copy loop.  Device-backed comms run
        the bucketed jitted ``host_fn``; device-agnostic comms run the
        plan's exact host path (``device=`` overrides)."""
        if not rows_by_pair:
            return {}
        counts = self.plan.pair_counts(
            {k: len(v) for k, v in rows_by_pair.items()})
        p = self.comm.p
        use_device = (self.comm.mesh is not None) if device is None \
            else bool(device)
        if use_device:
            dt = jnp.dtype(self.plan.dtype)
            x = np.zeros((p, p, self.plan.bucket) + self.plan.row_shape, dt)
            for (s, d), rs in rows_by_pair.items():
                x[s, d, :len(rs)] = np.asarray(rs, dt)
            recv, _ = self.plan.host_fn()(jnp.asarray(x),
                                          jnp.asarray(counts))
            recv = np.asarray(recv)
            out = {(s, d): [recv[d, s, j] for j in range(counts[s, d])]
                   for (s, d) in rows_by_pair}
        else:
            rows = [[[] for _ in range(p)] for _ in range(p)]
            for (s, d), rs in rows_by_pair.items():
                rows[s][d] = list(rs)
            recv, _ = self.plan.exact(rows)
            out = {(s, d): recv[d][s] for (s, d) in rows_by_pair}
        self.migrations += 1
        self.migrated_rows += int(counts.sum())
        return out

    def rebuild(self, surviving_devices, *,
                n_prefill: int | None = None) -> "ServingTopology":
        """Elastic re-partition: rebuild the underlying comm over the
        survivors (PR 6 semantics — this topology's plan slice is
        freed), then split the fresh torus into new prefill/decode
        domains (re-sized by the cost model unless pinned)."""
        fresh = self.comm.rebuild(surviving_devices)
        return ServingTopology(
            fresh, row_shape=self.plan.row_shape,
            max_count=self.plan.max_count, dtype=self.plan.dtype,
            n_prefill=n_prefill,
            migrations_per_tick=self.plan.migrations_per_tick,
            backend=self.plan.requested_backend)

    def describe(self) -> dict:
        return {
            "kind": "serving_topology",
            "comm": self.comm.describe(),
            "n_prefill": self.n_prefill,
            "n_decode": self.n_decode,
            "prefill_axes": list(self.prefill_comm.axis_names),
            "prefill_dims": list(self.prefill_comm.dims),
            "decode_axes": list(self.decode_comm.axis_names),
            "decode_dims": list(self.decode_comm.dims),
            "plan": self.plan.describe(),
            "split": None if self.split is None else {
                "predicted_seconds": self.split.predicted_seconds,
                "migration_kind": self.split.migration_kind,
            },
            "migrations": self.migrations,
            "migrated_rows": self.migrated_rows,
        }


class DisaggregatedServer:
    """The unified serving API over one torus: admission -> prefill
    domain -> KV migration -> decode domain, one tick at a time.

    Each prefill rank is a :class:`PrefillWorker`; the decode domain is
    one :class:`ContinuousBatcher` rooted on the decode sub-comm.  Per
    tick: the admission controller releases as many prompts as the
    decode domain has headroom for (decode-slot backpressure throttles
    prefill), workers advance their chunks, completed prefills stage for
    migration, at most one staged sequence per (src, dst) pair moves in
    ONE plan collective, and the decode batcher ticks.  ``rebuild``
    replays every in-flight request across a re-partitioned survivor
    topology — zero dropped requests, identical outputs.
    """

    def __init__(self, model, params, comm, *, max_seq: int,
                 decode_batch: int, prefill_batch: int = 2,
                 n_prefill: int | None = None, chunk: int = 4,
                 quotas=None, default_quota: int | None = None,
                 backend: str = "tuned", migrations_per_tick=None,
                 serve_step=None):
        self.model = model
        self.params = params
        self.max_seq = int(max_seq)
        self.decode_batch = int(decode_batch)
        self.prefill_batch = int(prefill_batch)
        self.chunk = int(chunk)
        self._serve_step = serve_step
        self.codec = KVRowCodec(model, max_seq)
        if migrations_per_tick is None:
            migrations_per_tick = 1.0
        self.topology = ServingTopology(
            comm, row_shape=self.codec.row_shape,
            max_count=self.codec.seq_slots, n_prefill=n_prefill,
            migrations_per_tick=migrations_per_tick, backend=backend)
        self.admission = AdmissionController(quotas=quotas,
                                             default_quota=default_quota)
        self._build_domains()
        self.staged: list[tuple[int, Request, np.ndarray, int]] = []
        self._decoding: dict[int, Request] = {}
        self.done: dict[int, list[int]] = {}
        self.ticks = 0
        self._rr_dst = 0

    def _build_domains(self):
        mk_step = (lambda: self._serve_step) if self._serve_step is not None \
            else (lambda: None)
        self.workers = [
            PrefillWorker(self.model, self.params,
                          max_batch=self.prefill_batch,
                          max_seq=self.max_seq, codec=self.codec,
                          chunk=self.chunk, serve_step=mk_step())
            for _ in range(self.topology.n_prefill)]
        self.batcher = ContinuousBatcher(
            self.model, self.params, max_batch=self.decode_batch,
            max_seq=self.max_seq, comm=self.topology.decode_comm,
            serve_step=mk_step())

    # ---- scheduling ----
    def submit(self, req: Request):
        req.submitted_s = time.perf_counter()
        self.admission.submit(req)

    @property
    def pending(self) -> int:
        return (self.admission.pending + len(self.staged)
                + sum(w.active for w in self.workers)
                + self.batcher.pending)

    # ---- main loop ----
    def tick(self) -> bool:
        """One serving tick; returns False once the system is drained."""
        if self.pending == 0:
            return False
        tr = telemetry.get_tracer()
        with tr.span("serve.tick", cat="serving", tick=self.ticks):
            # 1. admission, throttled by decode headroom: never release
            # more prompts than the decode domain can absorb beyond what
            # is already in flight through prefill/migration.
            with tr.span("serve.admission", cat="serving") as sp:
                headroom = self.batcher.max_batch - self.batcher.pending \
                    - len(self.staged) - sum(w.active for w in self.workers)
                budget = min(max(0, headroom),
                             sum(w.free_slots for w in self.workers))
                # drift backpressure: while any plan's measured round
                # times sit above the cost-model threshold (the same
                # signal the watchdog turns into a re-tune), halve the
                # admission budget — don't pile new load onto a comm
                # that is running off its tuned operating point.
                drift = telemetry.drift_detector().summary()
                if budget > 0 and any(v["drifted"] for v in drift.values()):
                    budget //= 2
                    telemetry.metrics().counter(
                        "serving.admission_throttled").inc()
                    sp.set(drift_throttled=True)
                admitted = 0
                for req in self.admission.admit(budget):
                    # least-loaded prefill worker = the placement router
                    worker = max(self.workers, key=lambda w: w.free_slots)
                    assert worker.admit(req)
                    admitted += 1
                sp.set(budget=budget, admitted=admitted)
            # 2. prefill chunks; completed prompts stage for migration (a
            # request finished by its very first token skips the decode
            # domain entirely).
            with tr.span("serve.prefill", cat="serving") as sp:
                completed = 0
                for src, worker in enumerate(self.workers):
                    for req, rows, pos in worker.step():
                        completed += 1
                        if _finished(req):
                            self.done[req.rid] = list(req.generated)
                            self.admission.release(req)
                        else:
                            self.staged.append((src, req, rows, pos))
                sp.set(completed=completed)
            # 3. KV migration: at most one staged sequence per (src, dst)
            # pair per tick (counts stay within the plan's max_count
            # bound), gated on free decode slots — one collective for
            # all of them.
            with tr.span("serve.kv_migrate", cat="serving") as sp:
                free = self.batcher.free_slots
                batch: dict[tuple[int, int], tuple] = {}
                remaining = []
                for entry in self.staged:
                    src, req, rows, pos = entry
                    dst = self.topology.n_prefill \
                        + self._rr_dst % self.topology.n_decode
                    if len(batch) < free and (src, dst) not in batch:
                        batch[(src, dst)] = entry
                        self._rr_dst += 1
                    else:
                        remaining.append(entry)
                self.staged = remaining
                if batch:
                    delivered = self.topology.migrate(
                        {pair: e[2] for pair, e in batch.items()})
                    for pair, (_, req, _, pos) in batch.items():
                        ok = self.batcher.admit_prefilled(
                            req, np.asarray(delivered[pair]), pos,
                            codec=self.codec)
                        assert ok, "migration was gated on free decode slots"
                        self._decoding[req.rid] = req
                sp.set(migrated=len(batch))
            # 4. decode tick + completion bookkeeping.
            with tr.span("serve.decode", cat="serving") as sp:
                self.batcher.step()
                finished = 0
                for rid, toks in list(self.batcher.done.items()):
                    if rid not in self.done:
                        self.done[rid] = toks
                        finished += 1
                    req = self._decoding.pop(rid, None)
                    if req is not None:
                        self.admission.release(req)
                sp.set(finished=finished)
        self.ticks += 1
        return True

    def run(self, max_ticks: int = 100_000):
        while self.tick() and self.ticks < max_ticks:
            pass
        return self.done

    # ---- elasticity ----
    def rebuild(self, surviving_devices, *,
                params=None, n_prefill: int | None = None) -> int:
        """Detect -> degrade -> rebuild -> resume, serving edition:
        requeue every in-flight request (decode in-flight folds its
        generated tokens; prefill in-flight and staged migrations simply
        replay), re-partition the survivor torus into fresh domains, and
        let the admission queue drain through the new topology — zero
        dropped requests, outputs unchanged.  Returns the requeue count.
        """
        if params is not None:
            self.params = params
        # decode in-flight: fold generated tokens, then drain the queue
        self.batcher.requeue_inflight()
        decode_reqs = list(self.batcher.queue)
        self.batcher.queue.clear()
        staged_reqs = [req for (_, req, _, _) in self.staged]
        self.staged = []
        prefill_reqs = []
        for worker in self.workers:
            prefill_reqs.extend(worker.requeue_inflight())
        reqs = decode_reqs + staged_reqs + prefill_reqs
        self._decoding.clear()
        for req in reqs:
            self.admission.release(req)
        self.admission.requeue_front(reqs)
        self.topology = self.topology.rebuild(surviving_devices,
                                              n_prefill=n_prefill)
        self._build_domains()
        return len(reqs)

    # ---- introspection ----
    def stats(self) -> dict:
        out = self.batcher.stats()
        out.update({
            "server_ticks": self.ticks,
            "pending": self.pending,
            "staged": len(self.staged),
            "prefill_active": [w.active for w in self.workers],
            "topology": self.topology.describe(),
        })
        return out
