"""A2APlan — the cached, compiled plan-object API for every all-to-all.

The paper's central engineering lesson is that the expensive setup —
factorizing ``p`` into torus dimensions, building the ``d``-dimensional
Cartesian communicators, and picking the per-round datatypes — is done
**once, cached, and reused** across all-to-all calls (Listings 1–2 plus
the §5 tuning conclusion).  ``plan_all_to_all`` is that setup step for
this repo: it resolves, exactly once per ``(devices, axes, shape, dtype,
knobs)`` key,

* the torus factorization (``core.cache.get_factorization``, keyed by the
  stable ``(device.id, platform)`` fingerprint when a ``Mesh`` is given),
* the backend — ``direct`` | ``factorized`` | ``overlap``, either
  requested explicitly or chosen by the alpha-beta cost model
  (``backend="tuned"`` → ``tuning.choose_algorithm``/``choose_chunks``);
  ``pipelined`` is a deprecated alias of ``factorized``,
* the per-round peer-axis sequence (forward and reverse/drain orders) and
  the payload chunk count,

and returns an :class:`A2APlan` whose methods — ``forward``, ``reverse``,
``tiled``, ``overlap`` — are the single execution surface every internal
consumer (MoE dispatch/combine, Ulysses re-shards, benchmarks, device
scripts) goes through.  Plans are cached in a bounded LRU registry, so
repeated calls with the same key return the same object: the analogue of
MPI's communicator attribute caching, measured in
``benchmarks/alltoall_cmp.py``'s plan-reuse column.

Execution methods must run inside ``jax.shard_map`` over the torus axes
(they lower to per-axis collectives); construction runs anywhere — at
trace time, at module setup, or from the legacy free-function shims in
``core.factorized`` / ``core.overlap`` (which now just build-or-fetch a
plan and warn).

Since the ``TorusComm`` redesign (``core.comm``) the communicator is the
API root: ``torus_comm(mesh, axes).all_to_all(...)`` is the primary
spelling, and :func:`plan_all_to_all` / :func:`plan_ragged_all_to_all`
are thin delegators that build or reuse the *implicit* comm — same
registry entries, same describe dicts, zero migration pressure for PR 2
era callers.  This module keeps the plan classes, the resolution
machinery (``_build_dense_plan`` / ``_build_ragged_plan``), and the
shared LRU registry with its teardown callback (evicting a composite
plan drops its nested dense entries and releases factorization refs).

``plan.describe()`` returns a stable dict (dims, backend, predicted cost,
chunks, cache hit/miss) for logging, goldens, and the dry-run artifacts.

:func:`plan_ragged_all_to_all` / :class:`RaggedA2APlan` extend the same
plan-object design to MPI_Alltoallv semantics (non-uniform per-pair
counts): a tiny int32 counts plan plus a bucket-padded data plan over the
identical torus, cached in the same registry — see ``core.ragged``.
"""

from __future__ import annotations

import math
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import telemetry
from .cache import (
    LRUCache,
    TorusFactorization,
    device_fingerprint,
    get_factorization,
)
from .factorized import (
    _as_tuple,
    _direct_impl,
    _direct_tiled_impl,
    _factorized_impl,
    _factorized_round_impl,
    _factorized_tiled_impl,
    _skip_trivial,
)
from .overlap import _check_order, _overlapped_impl
from .tuning import (
    DCN_AXES,            # noqa: F401  (re-exported; moved to core.tuning)
    LinkModel,
    Schedule,
    choose_algorithm,
    default_links,   # noqa: F401  (re-exported; moved to core.tuning)
    per_axis_round_seconds,
    predict_direct,
    predict_factorized,
    predict_overlapped,
    resolve_links,
    slowest_active_link,
)

# "pipelined" is a deprecated alias of "factorized" (resolved in _resolve)
BACKENDS = ("tuned", "autotune", "direct", "factorized", "pipelined",
            "overlap")


def _scope(kind: str):
    """The ``a2a[<kind>]`` name scope over one exchange: it names every op
    of the exchange, collectives and local packing alike, in the compiled
    HLO's ``op_name`` metadata.  Metadata only — the program is the same."""
    return jax.named_scope(f"a2a[{kind}]")


class A2APlan:
    """A resolved, reusable all-to-all execution plan.

    Construct via :func:`plan_all_to_all`; never directly.  All resolution
    (factorization, backend, chunk count, round orders, predicted cost)
    happens at construction; the execution methods only replay the chosen
    kernel.  Plans are plain static Python objects — closing over one
    inside ``shard_map``/``jit`` is free.
    """

    def __init__(self, fact: TorusFactorization, *, requested_backend: str,
                 backend: str, variant: str, order: tuple[int, ...],
                 rev_order: tuple[int, ...], n_chunks: int,
                 block_shape: tuple[int, ...] | None, dtype,
                 links: tuple[LinkModel, ...], schedule: Schedule | None,
                 mesh: Mesh | None, tuned_from: str | None = None,
                 measured: dict | None = None):
        self.fact = fact
        self.requested_backend = requested_backend
        self.backend = backend
        self.variant = variant
        self.order = order
        self.rev_order = rev_order
        self.n_chunks = n_chunks
        self.block_shape = block_shape
        self.dtype = dtype
        self.links = links
        self.schedule = schedule
        # Provenance of the backend/chunk choice: "measured" (tuning-DB
        # record from core.autotune), "model" (alpha-beta cost model), or
        # None (caller requested an explicit backend).
        self.tuned_from = tuned_from
        # For measured plans: the winner median + full measured table.
        self.measured = measured
        self._mesh = mesh
        self._from_cache = False
        self._fetches = 1
        self._host_fns: dict[Mesh, object] = {}
        self._round_fns: dict[Mesh, list] = {}

    # -- identity ----------------------------------------------------------

    @property
    def axis_names(self) -> tuple[str, ...]:
        return self.fact.axis_names

    @property
    def dims(self) -> tuple[int, ...]:
        return self.fact.dims

    @property
    def p(self) -> int:
        return self.fact.p

    @property
    def d(self) -> int:
        return self.fact.d

    @property
    def block_bytes(self) -> int | None:
        if self.block_shape is None or self.dtype is None:
            return None
        return math.prod(self.block_shape) * jnp.dtype(self.dtype).itemsize

    @property
    def predicted_seconds(self) -> float | None:
        """Model seconds of one ``forward`` / ``reverse``: what ``host_fn``
        runs and what drift is measured against.  An ``overlap`` plan's
        schedule prices the chunked rounds that ``overlap`` runs with a
        compute stage; with none the rounds run unchunked, priced as
        ``factorized``."""
        if self.schedule is None:
            return None
        if self.backend == "overlap":
            return predict_factorized(self.dims, self.links,
                                      float(self.block_bytes), self.p)
        return self.schedule.predicted_seconds

    # -- execution surface (inside shard_map) ------------------------------

    def forward(self, x):
        """Blockwise all-to-all: ``x`` is ``(p, *block)``, block ``i``
        destined for torus rank ``i``; returns ``out[i]`` = block received
        from rank ``i``."""
        return self._run(x, self.order)

    def reverse(self, x):
        """The combine-direction all-to-all: same semantics as ``forward``
        but rounds run in the drain order (``rev_order``), so a
        forward+reverse pair fills and empties the dimension links in
        opposite sequence.  Bit-identical to ``forward`` for any order —
        the collective is pure data movement and rounds commute."""
        return self._run(x, self.rev_order)

    def _run(self, x, order):
        # overlap too: with no compute stage there is nothing for chunks
        # to hide behind, so the whole buffer runs the d rounds; chunks
        # are for ``overlap`` with a compute stage (``core.overlap``).
        with _scope(self.backend):
            if self.backend == "direct":
                return _direct_impl(x, self.axis_names)
            return _factorized_impl(x, self.axis_names, variant=self.variant,
                                    round_order=order)

    def tiled(self, x, split_axis: int, concat_axis: int, *,
              reverse: bool = False):
        """Tiled-semantics all-to-all — drop-in for ``lax.all_to_all(x,
        reversed(axis_names), split_axis, concat_axis, tiled=True)``; the
        MoE-dispatch and Ulysses re-shard form."""
        order = self.rev_order if reverse else self.order
        with _scope(self.backend):
            if self.backend == "direct":
                return _direct_tiled_impl(x, self.axis_names, split_axis,
                                          concat_axis)
            return _factorized_tiled_impl(
                x, self.axis_names, split_axis, concat_axis,
                variant=self.variant, round_order=order)

    def overlap(self, x, compute_fn: Callable | None = None, *,
                reverse: bool = True, chunk_axis: int | None = None):
        """Fused forward / per-chunk compute / reverse pipeline
        (``core.overlap``): chunk ``c``'s forward rounds are emitted next
        to chunk ``c-1``'s compute and chunk ``c-2``'s reverse rounds.
        Bit-exact with ``reverse(compute_fn(forward(x)))`` since chunks
        never interact.  Without ``compute_fn`` there is nothing to hide
        the rounds behind and nothing is chunked: ``forward``, then
        ``reverse`` if asked."""
        if compute_fn is None:
            y = self.forward(x)
            return self.reverse(y) if reverse else y
        return _overlapped_impl(x, self.axis_names, compute_fn,
                                n_chunks=self.n_chunks,
                                variant=self.variant, round_order=self.order,
                                reverse=reverse,
                                reverse_round_order=self.rev_order,
                                chunk_axis=chunk_axis)

    # -- host-level convenience -------------------------------------------

    def host_fn(self, mesh: Mesh | None = None):
        """Jitted host-level all-to-all over a global ``(p, p, *block)``
        operand (``x[r, i]`` = rank r's block for rank i), the benchmark
        harness form.  The jitted callable is cached on the plan keyed by
        mesh *value* (Mesh is hashable), so plan reuse amortizes
        retracing even when the caller rebuilds an equal Mesh.

        The returned callable checks the telemetry tracer per call: off
        (the default), it dispatches the cached fused jit directly; on,
        every plan but a direct one executes the *stepped* per-round path
        (one jitted step per dimension-wise round — bit-exact, rounds
        commute) so every round gets a measured span and a drift
        observation."""
        mesh = self._mesh if mesh is None else mesh
        if mesh is None:
            raise ValueError("plan was built without a Mesh; pass one")
        if mesh not in self._host_fns:
            spec = P(tuple(reversed(self.axis_names)))

            def local(x):   # x: (1, p, *block) per device
                return self.forward(x[0])[None]

            self._host_fns[mesh] = jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=spec, out_specs=spec))
        fast = self._host_fns[mesh]

        # The tracer singleton is never rebound (enable/disable mutate it
        # in place), so bind it once here: the disabled fast path is one
        # attribute load + branch per call, not a registry lookup.
        tr = telemetry.get_tracer()

        def run(x):
            if not tr.enabled:
                return fast(x)
            return self._traced_execute(tr, mesh, fast, x)

        return run

    # -- telemetry-traced execution ----------------------------------------

    def _drift_key(self) -> str:
        """Stable drift-detector key: one time series per resolved plan
        identity (axes x dims x backend x block)."""
        dims = "x".join(str(s) for s in self.dims)
        return (f"dense[{','.join(self.axis_names)}]{dims}:{self.backend}"
                f":{self.block_bytes}")

    def _per_axis_predictions(self) -> dict[str, float] | None:
        """``{axis_name: model seconds}`` for the active rounds, or None
        without a sized block (tiled plans carry no block shape)."""
        if self.block_bytes is None:
            return None
        per_axis = per_axis_round_seconds(self.dims, self.links,
                                          float(self.block_bytes))
        return {name: t for name, Dk, t
                in zip(self.axis_names, self.dims, per_axis) if Dk > 1}

    def _round_host_fns(self, mesh):
        """Per-round jitted host fns in forward round order — the
        stepped traced path (every backend but direct)."""
        if mesh not in self._round_fns:
            spec = P(tuple(reversed(self.axis_names)))
            names, sizes = _skip_trivial(self.axis_names, self.dims)
            fns = []
            for k in self.order:
                def local(x, _k=k):
                    return _factorized_round_impl(
                        x[0], self.axis_names, _k,
                        variant=self.variant)[None]
                fns.append((k, names[k], sizes[k],
                            jax.jit(jax.shard_map(
                                local, mesh=mesh, in_specs=spec,
                                out_specs=spec))))
            self._round_fns[mesh] = fns
        return self._round_fns[mesh]

    def _traced_execute(self, tr, mesh, fast, x):
        det = telemetry.drift_detector()
        key = self._drift_key()
        preds = self._per_axis_predictions()
        predicted = self.predicted_seconds
        telemetry.metrics().counter("plan.traced_executions").inc()
        # Installed fault injectors (core.faults) expose a per-round
        # guard so injected slow rounds land inside the round spans.
        check = getattr(self, "_round_fault_check", None)
        with tr.span("plan.execute", cat="plan", kind="dense",
                     backend=self.backend, axes=",".join(self.axis_names),
                     dims="x".join(str(s) for s in self.dims),
                     predicted_seconds=predicted, tuned_from=self.tuned_from,
                     drift_key=key) as ex:
            t0 = time.perf_counter()
            if self.backend != "direct":
                y = x
                for k, name, Dk, fn in self._round_host_fns(mesh):
                    pred_k = None if preds is None else preds.get(name)
                    with tr.span("plan.round", cat="plan", axis=name,
                                 round=k, dim=Dk,
                                 predicted_seconds=pred_k):
                        if check is not None:
                            check()
                        tr0 = time.perf_counter()
                        y = jax.block_until_ready(fn(y))
                        if pred_k:
                            det.observe(f"{key}:axis={name}", pred_k,
                                        time.perf_counter() - tr0)
            else:
                # direct = a single product-communicator round: it
                # does not split into host-steppable rounds.
                with tr.span("plan.round", cat="plan", axis="*",
                             backend=self.backend, timing="fused",
                             predicted_seconds=predicted):
                    if check is not None:
                        check()
                    y = jax.block_until_ready(fast(x))
            measured = time.perf_counter() - t0
            ratio = det.observe(key, predicted, measured) \
                if predicted else None
            ex.set(measured_seconds=measured, drift_ratio=ratio)
        return y

    # -- introspection -----------------------------------------------------

    def describe(self) -> dict:
        """Stable, JSON-serializable summary of the resolved plan."""
        return {
            "kind": "dense",
            "axis_names": list(self.axis_names),
            "dims": list(self.dims),
            "p": self.p,
            "d": self.d,
            "backend": self.backend,
            "requested_backend": self.requested_backend,
            "variant": self.variant,
            "round_order": list(self.order),
            "reverse_round_order": list(self.rev_order),
            "n_chunks": self.n_chunks,
            # forward / reverse / tiled have no compute stage to hide
            # rounds behind: one chunk, the whole buffer (``n_chunks`` is
            # what ``overlap`` uses with a compute stage).  Held to the
            # compiled program by tests/device_scripts/check_zero_copy.py.
            "forward_chunks": 1,
            "block_shape": None if self.block_shape is None
            else list(self.block_shape),
            "dtype": None if self.dtype is None
            else jnp.dtype(self.dtype).name,
            "block_bytes": self.block_bytes,
            "predicted_seconds": self.predicted_seconds,
            "blocks_sent_per_device": self.fact.blocks_sent_per_device(),
            "links": [{"alpha": l.alpha, "bandwidth": l.bandwidth}
                      for l in self.links],
            "tuned_from": self.tuned_from,
            "measured": self.measured,
            "drift_ratio": telemetry.drift_detector()
            .drift_ratio(self._drift_key()),
            "cache": "hit" if self._from_cache else "miss",
        }

    def __repr__(self):
        return (f"A2APlan(dims={self.dims}, axes={self.axis_names}, "
                f"backend={self.backend!r}, n_chunks={self.n_chunks}, "
                f"variant={self.variant!r})")


# ---------------------------------------------------------------------------
# Construction + the plan registry
# ---------------------------------------------------------------------------


def _sub_plans(plan) -> tuple:
    """Nested plans a composite plan owns (ragged: data + counts; sparse:
    counts only — its data rounds are its own kernel; kv_migrate: the
    inner ragged/sparse plan, whose own nested entries drop recursively
    when it does)."""
    if isinstance(plan, RaggedA2APlan):
        return (plan.data, plan.counts_plan)
    if isinstance(plan, SparseA2APlan):
        return (plan.counts_plan,)
    if isinstance(plan, (KVMigrationPlan, TransposePlan)):
        return (plan.inner,)
    return ()


def _plan_fact(plan):
    """The factorization descriptor behind any plan kind."""
    fact = getattr(plan, "fact", None)
    return plan.data.fact if fact is None else fact


def _release_fact(fact) -> None:
    """Drop the factorization registry entries for ``fact`` once no live
    plan uses it — the paper's delete callback (Listing 2's ``torusdel``),
    run from the plan layer so the two registries tear down together."""
    for q in _PLANS.values():
        if _plan_fact(q) == fact:
            return
    from . import cache as _cache
    _cache.free(fact)


def _on_plan_evict(plan) -> None:
    """Teardown symmetry for the plan registry.

    Evicting (or explicitly dropping) a composite plan also drops its
    nested dense plans' registry entries — unless another live composite
    still owns one (two ragged plans over the same torus share a counts
    plan) — and the last plan over a factorization releases the
    descriptor cache entry.  Without this, LRU churn through ragged plans
    left orphaned ``(bucket, *row)`` / counts entries pinned in the
    registry and factorization refs that ``cache_stats`` counted forever.
    """
    for subp in _sub_plans(plan):
        key = getattr(subp, "_registry_key", None)
        # only drop the entry if the registry still holds *this* object:
        # after LRU churn a fresh equal-key plan (possibly a live
        # composite's nested member) may occupy the slot
        if key is None or _PLANS._data.get(key) is not subp:
            continue
        if any(subp in _sub_plans(q) for q in _PLANS.values()):
            continue
        dropped = _PLANS.pop(key)
        if dropped is not None:
            _on_plan_evict(dropped)
    _release_fact(_plan_fact(plan))


_PLANS: LRUCache = LRUCache(capacity=256, on_evict=_on_plan_evict)


def _registry_fetch(key):
    cached = _PLANS.get(key)
    if cached is not None:
        cached._from_cache = True
        cached._fetches += 1
    return cached


def _registry_store(key, plan):
    plan._registry_key = key
    _PLANS.put(key, plan)
    return plan


def _drop_plan(key) -> None:
    """Explicitly remove one plan entry, with the same teardown as LRU
    eviction (used by ``TorusComm.free``)."""
    plan = _PLANS.pop(key)
    if plan is not None:
        _on_plan_evict(plan)


def _resolve(dims, axis_names, block_shape, dtype, requested_backend,
             variant, round_order, reverse_round_order, n_chunks,
             max_chunks, links, compute_seconds):
    """All the once-per-plan decisions, in one place."""
    if requested_backend not in BACKENDS:
        raise ValueError(f"unknown a2a backend {requested_backend!r}; "
                         f"expected one of {BACKENDS}")
    if variant not in ("natural", "paper"):
        raise ValueError(f"unknown variant {variant!r}")
    links = resolve_links(links, dims, axis_names)

    # Round orders act on the *active* (size > 1) dimensions, matching the
    # kernels' skip-trivial semantics; validated here, at plan time.
    _, active = _skip_trivial(axis_names, dims)
    d_active = len(active)
    order = _check_order(round_order, d_active)
    rev_order = (tuple(reversed(order)) if reverse_round_order is None
                 else _check_order(reverse_round_order, d_active))

    p = math.prod(dims)
    block_bytes = None
    if block_shape is not None and dtype is not None:
        block_bytes = math.prod(block_shape) * jnp.dtype(dtype).itemsize

    if requested_backend == "tuned":
        if block_bytes is None:
            raise ValueError('backend="tuned" needs block_shape and dtype '
                             "for the cost model")
        sched = choose_algorithm(dims, links, float(block_bytes),
                                 max_chunks=max_chunks,
                                 compute_seconds=compute_seconds)
        backend = sched.kind
        n = n_chunks or sched.n_chunks
        return backend, order, rev_order, max(1, n), links, sched

    backend = requested_backend
    if backend == "pipelined":
        # deprecated alias: chunks without a compute stage hide nothing
        backend, n_chunks = "factorized", 0
    n = n_chunks or (2 if backend == "overlap" else 1)
    n = max(1, n)
    sched = None
    if block_bytes is not None:
        if backend == "direct":
            slowest = slowest_active_link(dims, links)
            t = predict_direct(p, float(block_bytes), slowest) \
                + compute_seconds
        elif backend == "factorized":
            t = predict_factorized(dims, links, float(block_bytes), p) \
                + compute_seconds
        else:
            t = predict_overlapped(dims, links, float(block_bytes), p, n,
                                   compute_seconds)
        sched = Schedule(backend, dims, links, t, n_chunks=n)
    return backend, order, rev_order, n, links, sched


def plan_all_to_all(mesh_or_axis_dims, axis_names, block_shape=None,
                    dtype=None, *, backend: str = "tuned",
                    variant: str = "natural", round_order=None,
                    reverse_round_order=None, n_chunks: int = 0,
                    max_chunks: int = 8, links=None,
                    compute_seconds: float = 0.0, db=None) -> A2APlan:
    """Build (or fetch from the LRU registry) an :class:`A2APlan`.

    A thin delegator since the ``TorusComm`` redesign: it builds or
    reuses the *implicit communicator* for ``(devices, axes, variant)``
    (``core.comm.torus_comm``) and constructs the plan through it, so
    legacy callers and the PR 2 deprecation shims share the comm-rooted
    path with no behavior change — new code should hold a
    :class:`~repro.core.comm.TorusComm` and call ``comm.all_to_all``.

    Args:
      mesh_or_axis_dims: a ``Mesh`` (the torus axes are looked up on it and
        the plan is keyed by the stable device fingerprint) or an explicit
        tuple of per-axis sizes, fastest digit first (device-agnostic key —
        the inside-``shard_map`` shim path).
      axis_names: torus dimensions, fastest digit first.
      block_shape, dtype: shape/dtype of one per-rank block — feeds the
        alpha-beta cost model.  Optional unless ``backend="tuned"`` or
        ``"autotune"``.
      backend: "tuned" (cost-model choice), "autotune" (measured choice
        from the persistent tuning DB — a hit rebuilds the recorded
        winner, a miss falls back to the cost model without measuring;
        see ``core.autotune``), or an explicit kernel:
        "direct" | "factorized" | "overlap" ("pipelined": deprecated
        alias of "factorized").
      variant: per-round formulation, "natural" (zero-copy) or "paper".
      round_order / reverse_round_order: permutations of the active rounds
        (default: identity, and its reversal for the drain direction).
      n_chunks: payload chunks for ``overlap`` with a compute stage; 0 =
        resolve (cost model under "tuned", else 2).
      max_chunks: search bound for the tuned chunk count.
      links: per-axis :class:`LinkModel` overrides (default: DCN for
        ``pod``-like axes, ICI otherwise; measured per-axis fits under a
        tuning-DB hit).
      compute_seconds: per-call interleaved compute estimate for tuning.
      db: tuning-DB handle for ``backend="autotune"`` (default: the
        ``REPRO_TUNING_DB`` / ``~/.cache/repro/tuning.json`` database).
    """
    from .comm import torus_comm
    return torus_comm(mesh_or_axis_dims, axis_names,
                      variant=variant).all_to_all(
        block_shape, dtype, backend=backend, round_order=round_order,
        reverse_round_order=reverse_round_order, n_chunks=n_chunks,
        max_chunks=max_chunks, links=links,
        compute_seconds=compute_seconds, db=db)


def _build_dense_plan(mesh_or_axis_dims, axis_names, block_shape=None,
                      dtype=None, *, backend: str = "tuned",
                      variant: str = "natural", round_order=None,
                      reverse_round_order=None, n_chunks: int = 0,
                      max_chunks: int = 8, links=None,
                      compute_seconds: float = 0.0, db=None) -> A2APlan:
    """The resolution machinery behind ``TorusComm.all_to_all`` (and the
    :func:`plan_all_to_all` delegator): all once-per-plan decisions plus
    the LRU registry."""
    axis_names = _as_tuple(axis_names)
    mesh = None
    if isinstance(mesh_or_axis_dims, Mesh):
        mesh = mesh_or_axis_dims
        fact = get_factorization(mesh, axis_names, variant=variant)
        dims = fact.dims
        dev_key = device_fingerprint(mesh)
    else:
        dims = tuple(int(s) for s in mesh_or_axis_dims)
        if len(dims) != len(axis_names):
            raise ValueError(f"{len(dims)} dims for {len(axis_names)} axes")
        fact = TorusFactorization(axis_names, dims, variant)
        dev_key = None

    # None stays None in the key (under "autotune" it means measured
    # links may substitute); anything else is normalized so a uniform
    # LinkModel and its broadcast tuple key identically.
    links_key = None if links is None else resolve_links(links, dims)
    key = (dev_key, dims, axis_names, None if block_shape is None
           else tuple(block_shape),
           None if dtype is None else jnp.dtype(dtype).name,
           backend, variant,
           None if round_order is None else tuple(round_order),
           None if reverse_round_order is None
           else tuple(reverse_round_order),
           int(n_chunks), int(max_chunks), links_key,
           float(compute_seconds))
    if backend == "autotune":
        # Cached autotune plans must be re-resolved when the DB changes
        # (a new measurement landed, or the file was deleted): key on the
        # DB identity + its per-path write generation.
        from .autotune import get_default_db
        db = db if db is not None else get_default_db()
        key = key + (db.path_key, db.generation())
    cached = _registry_fetch(key)
    if cached is not None:
        return cached

    def build(req_backend, order_, chunks_, links_):
        return _resolve(dims, axis_names, block_shape, dtype, req_backend,
                        variant, order_, reverse_round_order, chunks_,
                        max_chunks, links_, compute_seconds)

    tuned_from, measured = None, None
    if backend == "tuned":
        tuned_from = "model"
        parts = build("tuned", round_order, n_chunks, links)
    elif backend == "autotune":
        if block_shape is None or dtype is None:
            raise ValueError('backend="autotune" needs block_shape and '
                             "dtype (the tuning-DB key)")
        from .autotune import lookup_measured, measured_links
        rec = lookup_measured(dev_key, dims, axis_names,
                              tuple(block_shape), dtype, variant, db=db)
        parts = None
        if rec is not None:
            w = rec["winner"]
            rec_order = round_order if round_order is not None else \
                (tuple(w["round_order"]) if w.get("round_order") is not None
                 else None)
            rec_chunks = n_chunks or int(w.get("n_chunks", 0))
            rec_links = links
            if rec_links is None:
                rec_links = measured_links(rec)
            try:
                parts = build(w["backend"], rec_order, rec_chunks,
                              rec_links)
                tuned_from = "measured"
                measured = {"median_us": w.get("median_us"),
                            "table": rec.get("table", []),
                            "best_factorization":
                                rec.get("best_factorization"),
                            "db_path": str(db.path)}
            except ValueError as e:
                from .autotune import demote_hit_to_miss
                demote_hit_to_miss()   # telemetry: this plan is model-built
                warnings.warn(f"tuning-DB record unusable for this plan "
                              f"({e}); falling back to the cost model")
        if parts is None:   # DB miss (or unusable record): analytic choice,
            tuned_from = "model"   # never a blocking measurement
            parts = build("tuned", round_order, n_chunks, links)
    else:
        parts = build(backend, round_order, n_chunks, links)

    resolved, order, rev_order, n, link_models, sched = parts
    plan = A2APlan(fact, requested_backend=backend, backend=resolved,
                   variant=variant, order=order, rev_order=rev_order,
                   n_chunks=n, block_shape=None if block_shape is None
                   else tuple(block_shape), dtype=dtype, links=link_models,
                   schedule=sched, mesh=mesh, tuned_from=tuned_from,
                   measured=measured)
    return _registry_store(key, plan)


# ---------------------------------------------------------------------------
# Pencil-transpose plans (distributed-FFT re-shard)
# ---------------------------------------------------------------------------


class TransposePlan:
    """A resolved, reusable pencil↔pencil transpose plan.

    Construct via :meth:`TorusComm.transpose` (or :func:`plan_transpose`);
    never directly.  The global transpose of a pencil-decomposed FFT
    (``workloads.fft``) is an all-to-all of *uniform contiguous* chunks:
    the local pencil ``in_shape`` is split into ``p`` chunks along
    ``split_axis`` (chunk ``t`` -> torus rank ``t``) and the received
    chunks are concatenated source-major along ``concat_axis`` — the
    tiled collective semantics.  The plan composes the block-shape
    metadata for that re-shard with an inner dense :class:`A2APlan` over
    the same torus whose per-peer block is one chunk, so the transpose
    resolves through any dense backend — ``direct`` / ``factorized`` /
    ``overlap`` / ``tuned`` / ``autotune`` — and shares
    the registry, cost model, tuning DB, and telemetry machinery.

    Correctness oracle: ``core.simulator.simulate_pencil_transpose``.
    """

    kind = "transpose"

    def __init__(self, inner: A2APlan, *, in_shape: tuple[int, ...],
                 split_axis: int, concat_axis: int, parent=None):
        self.inner = inner
        self.in_shape = tuple(in_shape)
        self.split_axis = int(split_axis)
        self.concat_axis = int(concat_axis)
        out = list(self.in_shape)
        out[self.split_axis] //= inner.p
        out[self.concat_axis] *= inner.p
        self.out_shape = tuple(out)
        self.parent = parent
        self._from_cache = False
        self._fetches = 1
        self._host_fns: dict[Mesh, object] = {}
        self._step_fns: dict[Mesh, tuple] = {}

    # -- identity ----------------------------------------------------------

    @property
    def fact(self):
        return self.inner.fact

    @property
    def axis_names(self) -> tuple[str, ...]:
        return self.inner.axis_names

    @property
    def dims(self) -> tuple[int, ...]:
        return self.inner.dims

    @property
    def p(self) -> int:
        return self.inner.p

    @property
    def d(self) -> int:
        return self.inner.d

    @property
    def variant(self) -> str:
        return self.inner.variant

    @property
    def backend(self) -> str:
        return self.inner.backend

    @property
    def dtype(self):
        return self.inner.dtype

    @property
    def block_shape(self) -> tuple[int, ...]:
        """One per-peer chunk: ``in_shape`` with ``split_axis`` divided by
        ``p`` — the inner dense plan's block."""
        return self.inner.block_shape

    @property
    def block_bytes(self) -> int | None:
        return self.inner.block_bytes

    @property
    def pencil_bytes(self) -> int | None:
        bb = self.inner.block_bytes
        return None if bb is None else bb * self.p

    # -- execution surface (inside shard_map) ------------------------------

    def apply(self, x):
        """The forward re-shard: ``x`` is this device's ``in_shape``
        pencil; returns its ``out_shape`` pencil (``split_axis`` sharded,
        ``concat_axis`` gathered).  Runs inside ``jax.shard_map`` over
        the torus axes."""
        if x.shape != self.in_shape:
            raise ValueError(f"pencil shape {x.shape} != plan in_shape "
                             f"{self.in_shape}")
        with _scope(self.kind):
            return self.inner.tiled(x, self.split_axis, self.concat_axis)

    def inverse_apply(self, y):
        """The exact inverse re-shard (the tiled collective with split and
        concat swapped, rounds in the drain order): bit-identical
        round-trip with :meth:`apply` for any backend."""
        if y.shape != self.out_shape:
            raise ValueError(f"pencil shape {y.shape} != plan out_shape "
                             f"{self.out_shape}")
        with _scope(self.kind):
            return self.inner.tiled(y, self.concat_axis, self.split_axis,
                                    reverse=True)

    # -- host-level convenience -------------------------------------------

    def specs(self) -> tuple[P, P]:
        """Default global PartitionSpecs for :meth:`host_fn`: the
        distributed pencil axis (``concat_axis`` in, ``split_axis`` out)
        sharded over the plan's torus axes, everything else replicated.
        Only complete when the plan spans *all* mesh axes (the slab /
        full-torus transpose); sub-group transposes must pass specs that
        also shard the other pencil axes."""
        nd = len(self.in_shape)
        axes = tuple(reversed(self.axis_names))
        in_spec = [None] * nd
        in_spec[self.concat_axis] = axes
        out_spec = [None] * nd
        out_spec[self.split_axis] = axes
        return P(*in_spec), P(*out_spec)

    def host_fn(self, mesh: Mesh | None = None, *, in_spec: P | None = None,
                out_spec: P | None = None):
        """Jitted transpose over the *stage-global* array (the full
        logical array at this FFT stage, sharded per ``in_spec``);
        returns it re-sharded per ``out_spec``.  Defaults to
        :meth:`specs`.  Like ``A2APlan.host_fn`` the callable is
        tracer-aware: tracing off dispatches one fused jit; tracing on
        runs the stepped per-round path (every backend but direct) so
        every dimension-wise round gets a measured span and a drift
        observation."""
        mesh = self.inner._mesh if mesh is None else mesh
        if mesh is None:
            raise ValueError("plan was built without a Mesh; pass one")
        d_in, d_out = self.specs()
        in_spec = d_in if in_spec is None else in_spec
        out_spec = d_out if out_spec is None else out_spec
        fkey = (mesh, in_spec, out_spec)
        if fkey not in self._host_fns:
            self._host_fns[fkey] = jax.jit(jax.shard_map(
                self.apply, mesh=mesh, in_specs=in_spec,
                out_specs=out_spec))
        fast = self._host_fns[fkey]
        tr = telemetry.get_tracer()

        def run(x):
            if not tr.enabled:
                return fast(x)
            return self._traced_execute(tr, mesh, fast, x, in_spec,
                                        out_spec)

        return run

    # -- telemetry-traced execution ----------------------------------------

    def _drift_key(self) -> str:
        dims = "x".join(str(s) for s in self.dims)
        shape = "x".join(str(s) for s in self.in_shape)
        return (f"transpose[{','.join(self.axis_names)}]{dims}"
                f":{self.backend}:{shape}:{self.split_axis}"
                f"->{self.concat_axis}")

    def _stepped_fns(self, mesh, in_spec, out_spec):
        """Pre/post jitted re-layout fns bracketing the inner plan's
        per-round host fns: pencil -> harness block form ``(p, p,
        *block)`` -> rounds -> pencil.  Valid when the plan spans all
        mesh axes (the default-spec harness form)."""
        fkey = (mesh, in_spec, out_spec)
        if fkey not in self._step_fns:
            import jax.numpy as _jnp
            p, s, c = self.p, self.split_axis, self.concat_axis
            block_spec = P(tuple(reversed(self.axis_names)))

            def pre(xl):
                sh = xl.shape
                xb = xl.reshape(sh[:s] + (p, sh[s] // p) + sh[s + 1:])
                return _jnp.moveaxis(xb, s, 0)[None]

            def post(yl):
                y = _jnp.moveaxis(yl[0], 0, c)
                sh = y.shape
                return y.reshape(sh[:c] + (sh[c] * sh[c + 1],)
                                 + sh[c + 2:])

            self._step_fns[fkey] = (
                jax.jit(jax.shard_map(pre, mesh=mesh, in_specs=in_spec,
                                      out_specs=block_spec)),
                jax.jit(jax.shard_map(post, mesh=mesh,
                                      in_specs=block_spec,
                                      out_specs=out_spec)))
        return self._step_fns[fkey]

    def _traced_execute(self, tr, mesh, fast, x, in_spec, out_spec):
        det = telemetry.drift_detector()
        key = self._drift_key()
        preds = self.inner._per_axis_predictions()
        predicted = self.inner.predicted_seconds
        telemetry.metrics().counter("plan.traced_executions").inc()
        stepped = (self.backend != "direct"
                   and set(self.axis_names) == set(mesh.axis_names))
        with tr.span("plan.execute", cat="plan", kind="transpose",
                     backend=self.backend,
                     axes=",".join(self.axis_names),
                     dims="x".join(str(n) for n in self.dims),
                     pencil="x".join(str(n) for n in self.in_shape),
                     predicted_seconds=predicted,
                     tuned_from=self.inner.tuned_from,
                     drift_key=key) as ex:
            t0 = time.perf_counter()
            if stepped:
                pre, post = self._stepped_fns(mesh, in_spec, out_spec)
                y = jax.block_until_ready(pre(x))
                for k, name, Dk, fn in self.inner._round_host_fns(mesh):
                    pred_k = None if preds is None else preds.get(name)
                    with tr.span("plan.round", cat="plan", axis=name,
                                 round=k, dim=Dk,
                                 predicted_seconds=pred_k):
                        tr0 = time.perf_counter()
                        y = jax.block_until_ready(fn(y))
                        if pred_k:
                            det.observe(f"{key}:axis={name}", pred_k,
                                        time.perf_counter() - tr0)
                y = jax.block_until_ready(post(y))
            else:
                with tr.span("plan.round", cat="plan", axis="*",
                             backend=self.backend, timing="fused",
                             predicted_seconds=predicted):
                    y = jax.block_until_ready(fast(x))
            measured = time.perf_counter() - t0
            ratio = det.observe(key, predicted, measured) \
                if predicted else None
            ex.set(measured_seconds=measured, drift_ratio=ratio)
        return y

    # -- introspection -----------------------------------------------------

    def describe(self) -> dict:
        """Stable, JSON-serializable summary of the resolved plan."""
        inner = self.inner.describe()
        return {
            "kind": "transpose",
            "axis_names": list(self.axis_names),
            "dims": list(self.dims),
            "p": self.p,
            "d": self.d,
            "backend": self.backend,
            "requested_backend": self.inner.requested_backend,
            "variant": self.variant,
            "in_shape": list(self.in_shape),
            "out_shape": list(self.out_shape),
            "split_axis": self.split_axis,
            "concat_axis": self.concat_axis,
            "block_shape": None if self.block_shape is None
            else list(self.block_shape),
            "dtype": inner["dtype"],
            "pencil_bytes": self.pencil_bytes,
            "block_bytes": self.block_bytes,
            "predicted_seconds": inner["predicted_seconds"],
            "tuned_from": self.inner.tuned_from,
            "parent": None if self.parent is None else list(self.parent),
            "drift_ratio": telemetry.drift_detector()
            .drift_ratio(self._drift_key()),
            "cache": "hit" if self._from_cache else "miss",
        }

    def __repr__(self):
        return (f"TransposePlan(dims={self.dims}, axes={self.axis_names}, "
                f"in_shape={self.in_shape}, split={self.split_axis}, "
                f"concat={self.concat_axis}, backend={self.backend!r})")


def plan_transpose(mesh_or_axis_dims, axis_names, local_shape, dtype, *,
                   split_axis: int, concat_axis: int,
                   backend: str = "tuned", variant: str = "natural",
                   round_order=None, reverse_round_order=None,
                   n_chunks: int = 0, max_chunks: int = 8, links=None,
                   db=None) -> TransposePlan:
    """Build (or fetch) a :class:`TransposePlan` — thin delegator to
    ``torus_comm(...).transpose(...)``, mirroring :func:`plan_all_to_all`."""
    from .comm import torus_comm
    return torus_comm(mesh_or_axis_dims, axis_names,
                      variant=variant).transpose(
        local_shape, dtype, split_axis=split_axis, concat_axis=concat_axis,
        backend=backend, round_order=round_order,
        reverse_round_order=reverse_round_order, n_chunks=n_chunks,
        max_chunks=max_chunks, links=links, db=db)


def _build_transpose_plan(mesh_or_axis_dims, axis_names, local_shape, dtype,
                          *, split_axis: int, concat_axis: int,
                          backend: str = "tuned", variant: str = "natural",
                          round_order=None, reverse_round_order=None,
                          n_chunks: int = 0, max_chunks: int = 8,
                          links=None, db=None,
                          parent=None) -> TransposePlan:
    """Resolution + registry for pencil-transpose plans: validate the
    re-shard geometry, resolve the inner dense plan over the per-peer
    chunk (any backend, including the tuning DB), and key the composite
    off the inner's registry key so autotune DB-generation invalidation
    propagates for free."""
    local_shape = tuple(int(n) for n in local_shape)
    nd = len(local_shape)
    if not 0 <= split_axis < nd or not 0 <= concat_axis < nd:
        raise ValueError(f"split/concat axes ({split_axis}, {concat_axis}) "
                         f"outside pencil rank {nd}")
    if split_axis == concat_axis:
        raise ValueError("split_axis and concat_axis must differ")
    axis_names = _as_tuple(axis_names)
    if isinstance(mesh_or_axis_dims, Mesh):
        dims = get_factorization(mesh_or_axis_dims, axis_names,
                                 variant=variant).dims
    else:
        dims = tuple(int(s) for s in mesh_or_axis_dims)
    p = math.prod(dims)
    if local_shape[split_axis] % p:
        raise ValueError(f"split axis size {local_shape[split_axis]} not "
                         f"divisible by p={p} (dims {dims})")
    block_shape = list(local_shape)
    block_shape[split_axis] //= p
    inner = _build_dense_plan(
        mesh_or_axis_dims, axis_names, tuple(block_shape), dtype,
        backend=backend, variant=variant, round_order=round_order,
        reverse_round_order=reverse_round_order, n_chunks=n_chunks,
        max_chunks=max_chunks, links=links, db=db)
    key = ("transpose", inner._registry_key, local_shape, int(split_axis),
           int(concat_axis), parent)
    cached = _registry_fetch(key)
    if cached is not None:
        return cached
    plan = TransposePlan(inner, in_shape=local_shape,
                         split_axis=split_axis, concat_axis=concat_axis,
                         parent=parent)
    return _registry_store(key, plan)


# ---------------------------------------------------------------------------
# Ragged (MPI_Alltoallv) plans
# ---------------------------------------------------------------------------


class RaggedA2APlan:
    """A resolved, reusable ragged all-to-all (Alltoallv) plan.

    Construct via :func:`plan_ragged_all_to_all`; never directly.  The
    plan composes two dense :class:`A2APlan` resolutions over the same
    torus — the tiny int32 *counts* plan and the bucket-padded *data*
    plan — plus the bucket itself (the power-of-two row bound that keeps
    every dimension-wise round fixed-shape and jit-stable; see
    ``core.ragged``).  Like dense plans it is a static Python object,
    cached in the same LRU registry, free to close over inside
    ``shard_map``/``jit``.
    """

    def __init__(self, data: A2APlan, counts: A2APlan, *, max_count: int,
                 avg_count: float, row_shape: tuple[int, ...], dtype,
                 predicted_seconds: float | None):
        self.data = data
        self.counts_plan = counts
        self.max_count = max_count
        self.avg_count = avg_count
        self.row_shape = row_shape
        self.dtype = dtype
        self.predicted_seconds = predicted_seconds
        self._from_cache = False
        self._fetches = 1
        self._host_fns: dict[Mesh, object] = {}
        self._counts_fns: dict[Mesh, object] = {}

    # -- identity ----------------------------------------------------------

    @property
    def axis_names(self) -> tuple[str, ...]:
        return self.data.axis_names

    @property
    def dims(self) -> tuple[int, ...]:
        return self.data.dims

    @property
    def p(self) -> int:
        return self.data.p

    @property
    def d(self) -> int:
        return self.data.d

    @property
    def bucket(self) -> int:
        return self.data.block_shape[0]

    @property
    def backend(self) -> str:
        return self.data.backend

    @property
    def variant(self) -> str:
        return self.data.variant

    @property
    def n_chunks(self) -> int:
        return self.data.n_chunks

    @property
    def tuned_from(self) -> str | None:
        return self.data.tuned_from

    @property
    def row_bytes(self) -> int:
        return math.prod(self.row_shape) * jnp.dtype(self.dtype).itemsize

    @property
    def expected_occupancy(self) -> float:
        return float(self.avg_count) / float(self.bucket)

    # -- execution surface (inside shard_map) ------------------------------

    def counts_matrix(self, send_counts):
        """The counts phase alone: ``(p,)`` int32 send counts -> the full
        ``(p, p)`` matrix, identical on every device."""
        from .ragged import _counts_matrix_impl
        return _counts_matrix_impl(send_counts, self.counts_plan)

    def forward(self, x, send_counts):
        """Bucketed ragged all-to-all: ``x`` is ``(p, m, *row)`` with
        ``m <= bucket``, block ``i``'s rows destined for torus rank ``i``;
        returns ``(recv, recv_counts)`` — ``recv[i]`` the ``(bucket,
        *row)`` window received from rank ``i``."""
        from .ragged import _bucketed_impl
        with _scope("ragged"):
            return _bucketed_impl(x, send_counts, data_plan=self.data,
                                  counts_plan=self.counts_plan,
                                  axis_names=self.axis_names)

    def reverse(self, x, send_counts):
        """The combine-direction bucketed exchange (drain round order);
        ``send_counts`` is typically the ``recv_counts`` of the matching
        ``forward``."""
        from .ragged import _bucketed_impl
        with _scope("ragged"):
            return _bucketed_impl(x, send_counts, data_plan=self.data,
                                  counts_plan=self.counts_plan,
                                  axis_names=self.axis_names, reverse=True)

    def occupancy(self, send_counts):
        """Measured occupancy of one call (traced scalar): useful rows
        over ``p * bucket`` padded rows."""
        from .ragged import bucket_occupancy
        return bucket_occupancy(send_counts, self.bucket)

    # -- host-level paths --------------------------------------------------

    def exact(self, rows):
        """The exact two-phase host/debug path (``core.ragged
        .exact_alltoallv``): global nested ``rows[s][d]`` arrays in, exact
        per-pair arrays out — no bucket, no padding.  Runs the plan's
        forward round order over the active dimensions."""
        from .ragged import exact_alltoallv
        active = [i for i, Dk in enumerate(self.dims) if Dk > 1]
        trivial = [i for i, Dk in enumerate(self.dims) if Dk == 1]
        full_order = [active[k] for k in self.data.order] + trivial
        return exact_alltoallv(rows, self.dims, round_order=full_order)

    def host_fn(self, mesh: Mesh | None = None):
        """Jitted host-level ragged all-to-all over global ``(p, p,
        bucket, *row)`` data and ``(p, p)`` int32 counts operands
        (``x[r, i]`` = rank r's bucket window for rank i); returns the
        exchanged windows plus per-rank recv counts.

        With the telemetry tracer enabled the two phases split at host
        level — a measured ``ragged.counts`` span around the tiny int32
        exchange, then the data rounds through the dense plan's traced
        path (per-round spans for the factorized backend) — bit-exact
        with the fused jit, which still serves the disabled path."""
        mesh = self.data._mesh if mesh is None else mesh
        if mesh is None:
            raise ValueError("plan was built without a Mesh; pass one")
        if mesh not in self._host_fns:
            axes = tuple(reversed(self.axis_names))
            x_spec = P(axes)
            c_spec = P(axes)

            def local(x, c):    # x: (1, p, bucket, *row); c: (1, p)
                recv, rc = self.forward(x[0], c[0])
                return recv[None], rc[None]

            self._host_fns[mesh] = jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=(x_spec, c_spec),
                out_specs=(x_spec, c_spec)))
        fast = self._host_fns[mesh]

        tr = telemetry.get_tracer()   # stable singleton; bind once

        def run(x, c):
            if not tr.enabled:
                return fast(x, c)
            return self._traced_execute(tr, mesh, x, c)

        return run

    # -- telemetry-traced execution ----------------------------------------

    def _drift_key(self) -> str:
        dims = "x".join(str(s) for s in self.dims)
        return (f"ragged[{','.join(self.axis_names)}]{dims}"
                f":{self.backend}:b{self.bucket}")

    def _counts_host_fn(self, mesh):
        """Jitted counts phase alone: global ``(p, p)`` send counts ->
        global ``(p, p)`` per-rank recv counts."""
        if mesh not in self._counts_fns:
            from .ragged import (_counts_matrix_impl,
                                 _recv_counts_from_matrix)
            spec = P(tuple(reversed(self.axis_names)))

            def local(c):       # c: (1, p) per device
                matrix = _counts_matrix_impl(c[0], self.counts_plan)
                return _recv_counts_from_matrix(
                    matrix, self.axis_names)[None]

            self._counts_fns[mesh] = jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=spec, out_specs=spec))
        return self._counts_fns[mesh]

    def _traced_execute(self, tr, mesh, x, c):
        det = telemetry.drift_detector()
        key = self._drift_key()
        with tr.span("plan.execute", cat="plan", kind="ragged",
                     backend=self.backend,
                     axes=",".join(self.axis_names),
                     dims="x".join(str(s) for s in self.dims),
                     bucket=self.bucket,
                     predicted_seconds=self.predicted_seconds,
                     tuned_from=self.tuned_from, drift_key=key) as ex:
            t0 = time.perf_counter()
            counts_sched = self.counts_plan.schedule
            with tr.span("ragged.counts", cat="plan",
                         backend=self.counts_plan.backend,
                         block_bytes=self.counts_plan.block_bytes,
                         predicted_seconds=None if counts_sched is None
                         else counts_sched.predicted_seconds):
                rc = jax.block_until_ready(self._counts_host_fn(mesh)(c))
            self.data.host_fn(mesh)           # ensure the fused jit exists
            recv = self.data._traced_execute(
                tr, mesh, self.data._host_fns[mesh], x)
            measured = time.perf_counter() - t0
            ratio = det.observe(key, self.predicted_seconds, measured) \
                if self.predicted_seconds else None
            ex.set(measured_seconds=measured, drift_ratio=ratio)
        return recv, rc

    # -- introspection -----------------------------------------------------

    def describe(self) -> dict:
        """Stable, JSON-serializable summary of the resolved ragged plan.

        ``expected_occupancy`` is the plan-time estimate ``avg_count /
        bucket`` — the useful fraction of the bucketed data phase's
        traffic (1.0 means no padding waste); per-call measured occupancy
        comes from :meth:`occupancy`.  ``tuned_from`` is the data plan's
        provenance ("measured" under a tuning-DB hit, "model" for the
        analytic choice, None for an explicit backend).
        """
        return {
            "kind": "ragged",
            "axis_names": list(self.axis_names),
            "dims": list(self.dims),
            "p": self.p,
            "d": self.d,
            "backend": self.backend,
            "requested_backend": self.data.requested_backend,
            "variant": self.variant,
            "round_order": list(self.data.order),
            "reverse_round_order": list(self.data.rev_order),
            "n_chunks": self.n_chunks,
            "row_shape": list(self.row_shape),
            "dtype": jnp.dtype(self.dtype).name,
            "row_bytes": self.row_bytes,
            "max_count": self.max_count,
            "avg_count": self.avg_count,
            "bucket": self.bucket,
            "bucket_block_bytes": self.data.block_bytes,
            "expected_occupancy": self.expected_occupancy,
            "counts_backend": self.counts_plan.backend,
            "counts_block_bytes": self.counts_plan.block_bytes,
            "predicted_seconds": self.predicted_seconds,
            "blocks_sent_per_device": self.data.fact
            .blocks_sent_per_device(),
            "links": [{"alpha": l.alpha, "bandwidth": l.bandwidth}
                      for l in self.data.links],
            "tuned_from": self.tuned_from,
            "measured": self.data.measured,
            "drift_ratio": telemetry.drift_detector()
            .drift_ratio(self._drift_key()),
            "cache": "hit" if self._from_cache else "miss",
        }

    def __repr__(self):
        return (f"RaggedA2APlan(dims={self.dims}, axes={self.axis_names}, "
                f"backend={self.backend!r}, bucket={self.bucket}, "
                f"max_count={self.max_count})")


def plan_ragged_all_to_all(mesh_or_axis_dims, axis_names, row_shape=(),
                           dtype="float32", *, max_count: int,
                           avg_count: float | None = None,
                           backend: str = "tuned", variant: str = "natural",
                           round_order=None, reverse_round_order=None,
                           n_chunks: int = 0, max_chunks: int = 8,
                           links=None, compute_seconds: float = 0.0,
                           db=None) -> RaggedA2APlan:
    """Build (or fetch from the LRU registry) a :class:`RaggedA2APlan`.

    Like :func:`plan_all_to_all`, a thin delegator since the ``TorusComm``
    redesign: it builds or reuses the implicit communicator and delegates
    to ``comm.ragged_all_to_all`` — new code should construct through a
    :class:`~repro.core.comm.TorusComm` directly.

    Args mirror :func:`plan_all_to_all` with the ragged additions:

      row_shape, dtype: shape/dtype of ONE ragged row (the unit the
        per-pair counts count); ``()`` means scalar rows.
      max_count: static upper bound on any single ``send_counts`` entry —
        the jit-stability contract.  The bucket is its power-of-two
        round-up, so every dimension-wise exchange has a fixed shape.
      avg_count: expected mean per-pair count, for the plan's
        ``expected_occupancy`` estimate and the tuner's ragged cost term
        (default: ``max_count``, i.e. occupancy = max_count/bucket).
      backend: resolves the *data* plan (padded blocks of ``(bucket,
        *row_shape)``) exactly like the dense API — "tuned" prices
        candidates at the padded size (``tuning.choose_ragged_algorithm``
        semantics), "autotune" replays the measured winner recorded for
        the padded block shape.  The counts plan is always resolved as
        "tuned" over its ``(p,)`` int32 block.
    """
    from .comm import torus_comm
    return torus_comm(mesh_or_axis_dims, axis_names,
                      variant=variant).ragged_all_to_all(
        row_shape, dtype, max_count=max_count, avg_count=avg_count,
        backend=backend, round_order=round_order,
        reverse_round_order=reverse_round_order, n_chunks=n_chunks,
        max_chunks=max_chunks, links=links,
        compute_seconds=compute_seconds, db=db)


def _build_ragged_plan(mesh_or_axis_dims, axis_names, row_shape=(),
                       dtype="float32", *, max_count: int,
                       avg_count: float | None = None,
                       backend: str = "tuned", variant: str = "natural",
                       round_order=None, reverse_round_order=None,
                       n_chunks: int = 0, max_chunks: int = 8,
                       links=None, compute_seconds: float = 0.0,
                       db=None) -> RaggedA2APlan:
    """The resolution machinery behind ``TorusComm.ragged_all_to_all``
    (and the :func:`plan_ragged_all_to_all` delegator): the bucket, the
    nested dense data/counts plans, and the shared LRU registry."""
    axis_names = _as_tuple(axis_names)
    if isinstance(mesh_or_axis_dims, Mesh):
        dims = tuple(mesh_or_axis_dims.shape[n] for n in axis_names)
        dev_key = device_fingerprint(mesh_or_axis_dims)
    else:
        dims = tuple(int(s) for s in mesh_or_axis_dims)
        if len(dims) != len(axis_names):
            raise ValueError(f"{len(dims)} dims for {len(axis_names)} axes")
        dev_key = None
    from .ragged import next_pow2
    max_count = int(max_count)
    # Power-of-two bucket: any static bound keeps the rounds fixed-shape,
    # but snapping to pow2 bounds the set of distinct compiled shapes (and
    # plan-cache entries) across workloads whose max_count drifts — the
    # padding it adds beyond max_count is reported in expected_occupancy.
    bucket = next_pow2(max_count)
    avg = float(max_count if avg_count is None else avg_count)
    if not 0.0 < avg <= bucket:
        raise ValueError(f"avg_count {avg} outside (0, bucket={bucket}]")
    row_shape = tuple(int(s) for s in row_shape)
    p = math.prod(dims)

    links_key = None if links is None else resolve_links(links, dims)
    key = ("ragged", dev_key, dims, axis_names, row_shape,
           jnp.dtype(dtype).name, max_count, avg, backend, variant,
           None if round_order is None else tuple(round_order),
           None if reverse_round_order is None
           else tuple(reverse_round_order),
           int(n_chunks), int(max_chunks), links_key,
           float(compute_seconds))
    if backend == "autotune":
        from .autotune import get_default_db
        db = db if db is not None else get_default_db()
        key = key + (db.path_key, db.generation())
    cached = _registry_fetch(key)
    if cached is not None:
        return cached

    data = _build_dense_plan(mesh_or_axis_dims, axis_names,
                             (bucket,) + row_shape, dtype, backend=backend,
                             variant=variant, round_order=round_order,
                             reverse_round_order=reverse_round_order,
                             n_chunks=n_chunks, max_chunks=max_chunks,
                             links=links, compute_seconds=compute_seconds,
                             db=db)
    counts = _build_dense_plan(mesh_or_axis_dims, axis_names, (p,),
                               jnp.int32, backend="tuned", variant=variant,
                               round_order=round_order,
                               reverse_round_order=reverse_round_order,
                               max_chunks=1, links=links)
    predicted = None
    if data.schedule is not None and counts.schedule is not None:
        predicted = data.schedule.predicted_seconds \
            + counts.schedule.predicted_seconds
    plan = RaggedA2APlan(data, counts, max_count=max_count, avg_count=avg,
                         row_shape=row_shape, dtype=dtype,
                         predicted_seconds=predicted)
    return _registry_store(key, plan)


# ---------------------------------------------------------------------------
# Sparse neighborhood (message-combining) Alltoallv plans
# ---------------------------------------------------------------------------


class SparseA2APlan:
    """A resolved, reusable sparse-neighborhood Alltoallv plan.

    Construct via :func:`plan_sparse_all_to_all` (or
    ``TorusComm.sparse_all_to_all``); never directly.  The sparse family
    (``core.sparse``) keeps the ragged subsystem's counts phase and
    bucket contract but replaces the dense data rounds with
    message-combined, *skippable* per-peer lanes: each dimension-wise
    round decomposes into its ``D[k] - 1`` peer exchanges, and a lane
    whose combined payload is empty — determined from the replicated
    counts matrix against the plan-time ``round_message_masks`` — is
    skipped identically on every device (SPMD-safe ``lax.cond``).

    The execution surface duck-types :class:`RaggedA2APlan`'s
    ``forward``/``reverse`` (``(x, send_counts) -> (recv, recv_counts)``)
    so callers like the dropless MoE path can swap plans without code
    changes; the window contract is relaxed — rows beyond
    ``recv_counts[i]`` are unspecified (see ``core.sparse``).
    """

    def __init__(self, fact: TorusFactorization, counts: A2APlan, *,
                 max_count: int, avg_count: float, expected_density: float,
                 row_shape: tuple[int, ...], dtype, order: tuple[int, ...],
                 rev_order: tuple[int, ...], masks_fwd, masks_rev,
                 links: tuple[LinkModel, ...],
                 predicted_seconds: float | None, mesh: Mesh | None):
        self.fact = fact
        self.counts_plan = counts
        self.max_count = max_count
        self.avg_count = avg_count
        self.expected_density = expected_density
        self.row_shape = row_shape
        self.dtype = dtype
        self.order = order
        self.rev_order = rev_order
        self._masks_fwd = masks_fwd
        self._masks_rev = masks_rev
        self.links = links
        self.predicted_seconds = predicted_seconds
        # Traffic stats of the last host-side analyze()/exact() call
        # (density, skipped/combined messages, skipped rounds) — the jit
        # path never materializes them; None until first analysis.
        self.last_stats: dict | None = None
        self._mesh = mesh
        self._from_cache = False
        self._fetches = 1
        self._host_fns: dict[Mesh, object] = {}

    # -- identity ----------------------------------------------------------

    @property
    def axis_names(self) -> tuple[str, ...]:
        return self.fact.axis_names

    @property
    def dims(self) -> tuple[int, ...]:
        return self.fact.dims

    @property
    def p(self) -> int:
        return self.fact.p

    @property
    def d(self) -> int:
        return self.fact.d

    @property
    def variant(self) -> str:
        return self.fact.variant

    @property
    def backend(self) -> str:
        return "sparse"

    @property
    def bucket(self) -> int:
        from .ragged import next_pow2
        return next_pow2(self.max_count)

    @property
    def round_order(self) -> tuple[int, ...]:
        return self.order

    @property
    def reverse_round_order(self) -> tuple[int, ...]:
        return self.rev_order

    @property
    def row_bytes(self) -> int:
        return math.prod(self.row_shape) * jnp.dtype(self.dtype).itemsize

    @property
    def expected_occupancy(self) -> float:
        return float(self.avg_count) / float(self.bucket)

    # -- execution surface (inside shard_map) ------------------------------

    def counts_matrix(self, send_counts):
        """The counts phase alone: ``(p,)`` int32 send counts -> the full
        ``(p, p)`` matrix, identical on every device."""
        from .ragged import _counts_matrix_impl
        return _counts_matrix_impl(send_counts, self.counts_plan)

    def forward(self, x, send_counts):
        """Bucketed sparse all-to-all: same signature and return
        convention as :meth:`RaggedA2APlan.forward`, with empty per-peer
        lanes skipped; rows beyond ``recv_counts[i]`` are unspecified."""
        from .sparse import _sparse_bucketed_impl
        with _scope("sparse"):
            return _sparse_bucketed_impl(x, send_counts, plan=self)

    def reverse(self, x, send_counts):
        """The combine-direction sparse exchange (drain round order)."""
        from .sparse import _sparse_bucketed_impl
        with _scope("sparse"):
            return _sparse_bucketed_impl(x, send_counts, plan=self,
                                         reverse=True)

    def occupancy(self, send_counts):
        """Measured occupancy of one call (traced scalar): useful rows
        over ``p * bucket`` padded rows."""
        from .ragged import bucket_occupancy
        return bucket_occupancy(send_counts, self.bucket)

    # -- host-level paths --------------------------------------------------

    def _full_order(self, order) -> list[int]:
        active = [i for i, Dk in enumerate(self.dims) if Dk > 1]
        trivial = [i for i, Dk in enumerate(self.dims) if Dk == 1]
        return [active[k] for k in order] + trivial

    def analyze(self, counts) -> dict:
        """Host-side traffic analysis of a concrete ``(p, p)`` count
        matrix via the simulator's sparse oracle: density, per-message
        skip accounting, whole skipped rounds.  Caches the result on the
        plan (surfaced by :meth:`describe` and the dry-run artifacts)."""
        from .sparse import sparse_traffic_stats
        self.last_stats = sparse_traffic_stats(
            self.dims, counts, round_order=self._full_order(self.order))
        return self.last_stats

    def exact(self, rows):
        """The exact sparse host/debug path (``core.sparse
        .sparse_exact_alltoallv``): global nested ``rows[s][d]`` arrays
        in, exact per-pair arrays out plus the per-round skip accounting
        (also cached onto :attr:`last_stats`)."""
        from .sparse import sparse_exact_alltoallv
        recv, counts, vol = sparse_exact_alltoallv(
            rows, self.dims, round_order=self._full_order(self.order))
        self.analyze(counts)
        return recv, counts, vol

    def host_fn(self, mesh: Mesh | None = None):
        """Jitted host-level sparse all-to-all over global ``(p, p,
        bucket, *row)`` data and ``(p, p)`` int32 counts operands; the
        benchmark-harness form.  Replication checking is disabled
        (``check_vma=False``): the skip predicates wrap collectives in
        ``lax.cond``, which the older shard_map replication checker
        cannot see through."""
        mesh = self._mesh if mesh is None else mesh
        if mesh is None:
            raise ValueError("plan was built without a Mesh; pass one")
        if mesh not in self._host_fns:
            axes = tuple(reversed(self.axis_names))
            x_spec = P(axes)
            c_spec = P(axes)

            def local(x, c):    # x: (1, p, bucket, *row); c: (1, p)
                recv, rc = self.forward(x[0], c[0])
                return recv[None], rc[None]

            self._host_fns[mesh] = jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=(x_spec, c_spec),
                out_specs=(x_spec, c_spec), check_vma=False))
        fast = self._host_fns[mesh]

        tr = telemetry.get_tracer()   # stable singleton; bind once

        def run(x, c):
            if not tr.enabled:
                return fast(x, c)
            return self._traced_execute(tr, fast, x, c)

        return run

    # -- telemetry-traced execution ----------------------------------------

    def _drift_key(self) -> str:
        dims = "x".join(str(s) for s in self.dims)
        return (f"sparse[{','.join(self.axis_names)}]{dims}"
                f":b{self.bucket}:rho{self.expected_density}")

    def _traced_execute(self, tr, fast, x, c):
        """One measured execute span around the fused jit — the sparse
        rounds' ``lax.cond``-guarded lanes cannot be stepped at host
        level (the skip predicates live inside the trace), so per-round
        device attribution comes from the ``named_scope`` annotations in
        the profile, not host spans."""
        det = telemetry.drift_detector()
        key = self._drift_key()
        with tr.span("plan.execute", cat="plan", kind="sparse",
                     backend="sparse", axes=",".join(self.axis_names),
                     dims="x".join(str(s) for s in self.dims),
                     bucket=self.bucket,
                     expected_density=self.expected_density,
                     predicted_seconds=self.predicted_seconds,
                     drift_key=key, timing="fused") as ex:
            t0 = time.perf_counter()
            out = jax.block_until_ready(fast(x, c))
            measured = time.perf_counter() - t0
            ratio = det.observe(key, self.predicted_seconds, measured) \
                if self.predicted_seconds else None
            ex.set(measured_seconds=measured, drift_ratio=ratio)
        return out

    # -- introspection -----------------------------------------------------

    def describe(self) -> dict:
        """Stable, JSON-serializable summary of the resolved sparse plan.

        ``expected_density`` is the plan-time estimate of the non-zero
        fraction of the count matrix (what the tuner priced); ``density``
        / ``skipped_rounds`` / ``combined_messages`` /
        ``skipped_exchanges`` reflect the last host-side
        :meth:`analyze` / :meth:`exact` call (None before one runs).
        """
        stats = self.last_stats or {}
        return {
            "kind": "sparse",
            "axis_names": list(self.axis_names),
            "dims": list(self.dims),
            "p": self.p,
            "d": self.d,
            "backend": "sparse",
            "requested_backend": "sparse",
            "variant": self.variant,
            "round_order": list(self.order),
            "reverse_round_order": list(self.rev_order),
            "n_chunks": 1,
            "row_shape": list(self.row_shape),
            "dtype": jnp.dtype(self.dtype).name,
            "row_bytes": self.row_bytes,
            "max_count": self.max_count,
            "avg_count": self.avg_count,
            "bucket": self.bucket,
            "expected_occupancy": self.expected_occupancy,
            "expected_density": self.expected_density,
            "density": stats.get("density"),
            "skipped_rounds": stats.get("skipped_rounds"),
            "combined_messages": stats.get("combined_messages"),
            "skipped_exchanges": stats.get("skipped_exchanges"),
            "total_exchanges": stats.get("total_exchanges"),
            "counts_backend": self.counts_plan.backend,
            "counts_block_bytes": self.counts_plan.block_bytes,
            "predicted_seconds": self.predicted_seconds,
            "blocks_sent_per_device": self.fact.blocks_sent_per_device(),
            "links": [{"alpha": l.alpha, "bandwidth": l.bandwidth}
                      for l in self.links],
            "tuned_from": None,
            "measured": None,
            "drift_ratio": telemetry.drift_detector()
            .drift_ratio(self._drift_key()),
            "cache": "hit" if self._from_cache else "miss",
        }

    def __repr__(self):
        return (f"SparseA2APlan(dims={self.dims}, axes={self.axis_names}, "
                f"bucket={self.bucket}, max_count={self.max_count}, "
                f"expected_density={self.expected_density})")


def plan_sparse_all_to_all(mesh_or_axis_dims, axis_names, row_shape=(),
                           dtype="float32", *, max_count: int,
                           avg_count: float | None = None,
                           density: float | None = None,
                           variant: str = "natural", round_order=None,
                           reverse_round_order=None,
                           links=None) -> SparseA2APlan:
    """Build (or fetch from the LRU registry) a :class:`SparseA2APlan`.

    A thin delegator to ``TorusComm.sparse_all_to_all`` (the comm is the
    API root).  Args mirror :func:`plan_ragged_all_to_all` minus the
    backend knobs — the sparse data rounds are one kernel — plus:

      density: expected non-zero fraction of the ``p x p`` count matrix
        (default 1.0 — i.e. price as if dense).  Feeds
        ``tuning.predict_sparse`` and the plan key; must be in (0, 1].
    """
    from .comm import torus_comm
    return torus_comm(mesh_or_axis_dims, axis_names,
                      variant=variant).sparse_all_to_all(
        row_shape, dtype, max_count=max_count, avg_count=avg_count,
        density=density, round_order=round_order,
        reverse_round_order=reverse_round_order, links=links)


def _build_sparse_plan(mesh_or_axis_dims, axis_names, row_shape=(),
                       dtype="float32", *, max_count: int,
                       avg_count: float | None = None,
                       density: float | None = None,
                       variant: str = "natural", round_order=None,
                       reverse_round_order=None,
                       links=None) -> SparseA2APlan:
    """The resolution machinery behind ``TorusComm.sparse_all_to_all``:
    bucket, counts plan, plan-time message masks, and the shared LRU
    registry."""
    axis_names = _as_tuple(axis_names)
    mesh = None
    if isinstance(mesh_or_axis_dims, Mesh):
        mesh = mesh_or_axis_dims
        fact = get_factorization(mesh, axis_names, variant=variant)
        dims = fact.dims
        dev_key = device_fingerprint(mesh)
    else:
        dims = tuple(int(s) for s in mesh_or_axis_dims)
        if len(dims) != len(axis_names):
            raise ValueError(f"{len(dims)} dims for {len(axis_names)} axes")
        fact = TorusFactorization(axis_names, dims, variant)
        dev_key = None
    if variant not in ("natural", "paper"):
        raise ValueError(f"unknown variant {variant!r}")

    from .ragged import next_pow2
    max_count = int(max_count)
    bucket = next_pow2(max_count)
    avg = float(max_count if avg_count is None else avg_count)
    if not 0.0 < avg <= bucket:
        raise ValueError(f"avg_count {avg} outside (0, bucket={bucket}]")
    rho = float(1.0 if density is None else density)
    if not 0.0 < rho <= 1.0:
        raise ValueError(f"density {rho} outside (0, 1]")
    row_shape = tuple(int(s) for s in row_shape)
    p = math.prod(dims)

    _, active = _skip_trivial(axis_names, dims)
    d_active = len(active)
    order = _check_order(round_order, d_active)
    rev_order = (tuple(reversed(order)) if reverse_round_order is None
                 else _check_order(reverse_round_order, d_active))

    links_key = None if links is None else resolve_links(links, dims)
    key = ("sparse", dev_key, dims, axis_names, row_shape,
           jnp.dtype(dtype).name, max_count, avg, rho, variant, order,
           rev_order, links_key)
    cached = _registry_fetch(key)
    if cached is not None:
        return cached

    # Same counts-plan resolution as the ragged family, so a ragged and a
    # sparse plan over one torus share the registry entry.
    counts = _build_dense_plan(mesh_or_axis_dims, axis_names, (p,),
                               jnp.int32, backend="tuned", variant=variant,
                               round_order=round_order,
                               reverse_round_order=reverse_round_order,
                               max_chunks=1, links=links)

    from .sparse import round_message_masks
    masks_fwd = round_message_masks(active, order)
    masks_rev = masks_fwd if rev_order == order \
        else round_message_masks(active, rev_order)

    from .tuning import predict_sparse
    link_models = resolve_links(links, dims, axis_names)
    row_bytes = math.prod(row_shape) * jnp.dtype(dtype).itemsize
    predicted = predict_sparse(dims, link_models, float(row_bytes), bucket,
                               p, density=rho)

    plan = SparseA2APlan(fact, counts, max_count=max_count, avg_count=avg,
                         expected_density=rho, row_shape=row_shape,
                         dtype=dtype, order=order, rev_order=rev_order,
                         masks_fwd=masks_fwd, masks_rev=masks_rev,
                         links=link_models, predicted_seconds=predicted,
                         mesh=mesh)
    return _registry_store(key, plan)


# ---------------------------------------------------------------------------
# KV-migration (prefill -> decode handoff) plans
# ---------------------------------------------------------------------------


class KVMigrationPlan:
    """A resolved, reusable prefill->decode KV-cache migration plan.

    Construct via :func:`plan_kv_migration` (or
    ``TorusComm.kv_migration``); never directly.  The KV handoff of a
    disaggregated serving topology is an Alltoallv over the *full*
    serving comm whose count matrix is non-zero only in the
    prefill->decode block (rows ``< n_prefill``, columns ``>=
    n_prefill``): per-sequence variable lengths are the send counts and
    the scheduler's placement is the router.  The plan wraps the
    matching exchange machinery — a :class:`RaggedA2APlan` or, in the
    few-migrations-per-tick regime the cost model prices via the block
    density, a :class:`SparseA2APlan` — and adds the block-structure
    validation (:meth:`pair_counts`) so a misplaced sequence fails at
    the datatype layer, not as silent corruption.

    Like every plan it is a static Python object in the shared LRU
    registry; evicting it drops the inner plan (and its nested entries)
    via the same teardown symmetry.
    """

    kind = "kv_migrate"

    def __init__(self, inner, *, requested_backend: str, n_prefill: int,
                 migrations_per_tick: float, expected_density: float,
                 predicted_seconds: float | None, tuned_from: str | None):
        self.inner = inner
        self.requested_backend = requested_backend
        self.n_prefill = int(n_prefill)
        self.migrations_per_tick = float(migrations_per_tick)
        self.expected_density = float(expected_density)
        self.predicted_seconds = predicted_seconds
        self.tuned_from = tuned_from
        # the factorization descriptor, for the registry teardown
        self.fact = inner.fact if hasattr(inner, "fact") else inner.data.fact
        self._from_cache = False
        self._fetches = 1

    # -- identity ----------------------------------------------------------

    @property
    def axis_names(self) -> tuple[str, ...]:
        return self.inner.axis_names

    @property
    def dims(self) -> tuple[int, ...]:
        return self.inner.dims

    @property
    def p(self) -> int:
        return self.inner.p

    @property
    def d(self) -> int:
        return self.inner.d

    @property
    def n_decode(self) -> int:
        return self.p - self.n_prefill

    @property
    def inner_kind(self) -> str:
        return "sparse" if isinstance(self.inner, SparseA2APlan) \
            else "ragged"

    @property
    def backend(self) -> str:
        return self.inner.backend

    @property
    def variant(self) -> str:
        return self.inner.variant

    @property
    def bucket(self) -> int:
        return self.inner.bucket

    @property
    def max_count(self) -> int:
        return self.inner.max_count

    @property
    def avg_count(self) -> float:
        return self.inner.avg_count

    @property
    def row_shape(self) -> tuple[int, ...]:
        return self.inner.row_shape

    @property
    def dtype(self):
        return self.inner.dtype

    @property
    def row_bytes(self) -> int:
        return self.inner.row_bytes

    @property
    def expected_occupancy(self) -> float:
        return self.inner.expected_occupancy

    # -- the datatype layer ------------------------------------------------

    def pair_counts(self, pairs) -> "np.ndarray":
        """Validate scheduler placements and build the ``(p, p)`` int32
        count matrix: ``pairs`` maps ``(src, dst) -> row count``.  Every
        source must be a prefill rank (``src < n_prefill``), every
        destination a decode rank (``dst >= n_prefill``), and every
        count within the plan's ``max_count`` bound — the jit-stability
        contract of the bucketed exchange."""
        import numpy as np
        counts = np.zeros((self.p, self.p), np.int32)
        for (src, dst), n in pairs.items():
            src, dst, n = int(src), int(dst), int(n)
            if not 0 <= src < self.n_prefill:
                raise ValueError(f"migration source {src} is not a prefill "
                                 f"rank (n_prefill={self.n_prefill})")
            if not self.n_prefill <= dst < self.p:
                raise ValueError(f"migration destination {dst} is not a "
                                 f"decode rank (n_prefill="
                                 f"{self.n_prefill}, p={self.p})")
            if not 0 <= n <= self.max_count:
                raise ValueError(f"migration count {n} for pair "
                                 f"({src}, {dst}) outside [0, max_count="
                                 f"{self.max_count}]")
            counts[src, dst] = n
        return counts

    # -- execution surface -------------------------------------------------

    def forward(self, x, send_counts):
        """Bucketed exchange inside ``shard_map`` — delegates to the
        inner ragged/sparse plan (same signature and window contract)."""
        return self.inner.forward(x, send_counts)

    def reverse(self, x, send_counts):
        return self.inner.reverse(x, send_counts)

    def counts_matrix(self, send_counts):
        return self.inner.counts_matrix(send_counts)

    def occupancy(self, send_counts):
        return self.inner.occupancy(send_counts)

    def exact(self, rows):
        """The exact host path: nested ``rows[s][d]`` in, ``(recv,
        counts)`` out with ``recv[r][s]`` the rows rank ``r`` received
        from ``s`` — the sparse inner plan's volume accounting lands on
        ``inner.last_stats``."""
        out = self.inner.exact(rows)
        if len(out) == 3:            # sparse: (recv, counts, vol)
            recv, counts, _ = out
            return recv, counts
        return out

    def host_fn(self, mesh: Mesh | None = None):
        """Jitted host-level exchange over global ``(p, p, bucket,
        *row)`` data and ``(p, p)`` int32 counts operands — the one
        collective a serving tick executes.  Telemetry spans and drift
        tracking ride the inner ragged/sparse plan's instrumented
        path."""
        return self.inner.host_fn(mesh)

    def _drift_key(self) -> str:
        return self.inner._drift_key()

    # -- introspection -----------------------------------------------------

    def describe(self) -> dict:
        """Stable, JSON-serializable summary of the resolved plan —
        ``kind="kv_migrate"`` plus occupancy / ``tuned_from`` like every
        other plan, and the serving-topology fields (``n_prefill`` /
        ``n_decode`` / ``expected_density`` / ``inner_kind``)."""
        return {
            "kind": "kv_migrate",
            "inner_kind": self.inner_kind,
            "axis_names": list(self.axis_names),
            "dims": list(self.dims),
            "p": self.p,
            "d": self.d,
            "backend": self.backend,
            "requested_backend": self.requested_backend,
            "variant": self.variant,
            "row_shape": list(self.row_shape),
            "dtype": jnp.dtype(self.dtype).name,
            "row_bytes": self.row_bytes,
            "max_count": self.max_count,
            "avg_count": self.avg_count,
            "bucket": self.bucket,
            "expected_occupancy": self.expected_occupancy,
            "n_prefill": self.n_prefill,
            "n_decode": self.n_decode,
            "migrations_per_tick": self.migrations_per_tick,
            "expected_density": self.expected_density,
            "predicted_seconds": self.predicted_seconds,
            "tuned_from": self.tuned_from,
            "drift_ratio": telemetry.drift_detector()
            .drift_ratio(self._drift_key()),
            "cache": "hit" if self._from_cache else "miss",
        }

    def __repr__(self):
        return (f"KVMigrationPlan(dims={self.dims}, "
                f"axes={self.axis_names}, inner={self.inner_kind!r}, "
                f"n_prefill={self.n_prefill}, bucket={self.bucket})")


def plan_kv_migration(mesh_or_axis_dims, axis_names, row_shape=(),
                      dtype="float32", *, max_count: int, n_prefill: int,
                      avg_count: float | None = None,
                      migrations_per_tick: float = 1.0,
                      backend: str = "tuned", variant: str = "natural",
                      round_order=None, reverse_round_order=None,
                      links=None, db=None) -> KVMigrationPlan:
    """Build (or fetch from the LRU registry) a :class:`KVMigrationPlan`.

    A thin delegator to ``TorusComm.kv_migration`` (the comm is the API
    root).  Args mirror :func:`plan_ragged_all_to_all` plus:

      n_prefill: ranks ``0..n_prefill-1`` are the prefill domain, the
        rest the decode domain — the block structure
        :meth:`KVMigrationPlan.pair_counts` enforces.
      migrations_per_tick: expected concurrently migrating sequences per
        serving tick; with ``backend="tuned"`` it sets the count-matrix
        density the cost model prices (``tuning.predict_kv_migration``)
        to pick the ragged vs sparse inner exchange.
      backend: ``"tuned"`` (cost-model choice between the dense-bucketed
        ragged exchange and the sparse-neighborhood one), ``"ragged"`` /
        ``"sparse"`` (explicit inner kind), or any dense data backend
        (``"direct"`` | ``"factorized"`` | ``"overlap"`` |
        ``"pipelined"`` | ``"autotune"`` — an explicit ragged data
        phase).
    """
    from .comm import torus_comm
    return torus_comm(mesh_or_axis_dims, axis_names,
                      variant=variant).kv_migration(
        row_shape, dtype, max_count=max_count, n_prefill=n_prefill,
        avg_count=avg_count, migrations_per_tick=migrations_per_tick,
        backend=backend, round_order=round_order,
        reverse_round_order=reverse_round_order, links=links, db=db)


def _build_kv_plan(mesh_or_axis_dims, axis_names, row_shape=(),
                   dtype="float32", *, max_count: int, n_prefill: int,
                   avg_count: float | None = None,
                   migrations_per_tick: float = 1.0,
                   backend: str = "tuned", variant: str = "natural",
                   round_order=None, reverse_round_order=None,
                   links=None, db=None) -> KVMigrationPlan:
    """The resolution machinery behind ``TorusComm.kv_migration`` (and
    the :func:`plan_kv_migration` delegator): the block-density estimate,
    the ragged-vs-sparse inner choice, and the shared LRU registry."""
    axis_names = _as_tuple(axis_names)
    if isinstance(mesh_or_axis_dims, Mesh):
        dims = tuple(mesh_or_axis_dims.shape[n] for n in axis_names)
        dev_key = device_fingerprint(mesh_or_axis_dims)
    else:
        dims = tuple(int(s) for s in mesh_or_axis_dims)
        if len(dims) != len(axis_names):
            raise ValueError(f"{len(dims)} dims for {len(axis_names)} axes")
        dev_key = None
    p = math.prod(dims)
    n_prefill = int(n_prefill)
    if not 0 < n_prefill < p:
        raise ValueError(f"n_prefill {n_prefill} outside (0, p={p}); a "
                         "disaggregated topology needs at least one rank "
                         "in each domain")
    migrations = float(migrations_per_tick)
    if migrations <= 0:
        raise ValueError(f"migrations_per_tick must be > 0, got "
                         f"{migrations}")
    pairs = min(migrations, float(n_prefill * (p - n_prefill)))
    density = max(pairs, 1.0) / float(p * p)

    from .ragged import next_pow2
    bucket = next_pow2(int(max_count))
    row_shape = tuple(int(s) for s in row_shape)
    links_key = None if links is None else resolve_links(links, dims)
    key = ("kv_migrate", dev_key, dims, axis_names, row_shape,
           jnp.dtype(dtype).name, int(max_count),
           None if avg_count is None else float(avg_count), n_prefill,
           migrations, backend, variant,
           None if round_order is None else tuple(round_order),
           None if reverse_round_order is None
           else tuple(reverse_round_order), links_key)
    cached = _registry_fetch(key)
    if cached is not None:
        return cached

    from .tuning import predict_kv_migration
    link_models = resolve_links(links, dims, axis_names)
    row_bytes = math.prod(row_shape) * jnp.dtype(dtype).itemsize
    sched = predict_kv_migration(dims, link_models, float(row_bytes),
                                 bucket, n_prefill=n_prefill,
                                 migrations_per_tick=migrations)

    inner_kind = backend
    tuned_from = None
    if backend == "tuned":
        inner_kind = "sparse" if sched.kind == "sparse" else "ragged"
        tuned_from = "model"
    if inner_kind == "sparse":
        inner = _build_sparse_plan(
            mesh_or_axis_dims, axis_names, row_shape, dtype,
            max_count=max_count, avg_count=avg_count, density=density,
            variant=variant, round_order=round_order,
            reverse_round_order=reverse_round_order, links=links)
    else:
        # "ragged" resolves the data phase through the cost model; any
        # other name is an explicit dense data backend, passed through.
        data_backend = "tuned" if inner_kind == "ragged" else inner_kind
        inner = _build_ragged_plan(
            mesh_or_axis_dims, axis_names, row_shape, dtype,
            max_count=max_count, avg_count=avg_count,
            backend=data_backend, variant=variant,
            round_order=round_order,
            reverse_round_order=reverse_round_order, links=links, db=db)
        if tuned_from is None:
            tuned_from = inner.tuned_from

    plan = KVMigrationPlan(inner, requested_backend=backend,
                           n_prefill=n_prefill,
                           migrations_per_tick=migrations,
                           expected_density=density,
                           predicted_seconds=sched.predicted_seconds,
                           tuned_from=tuned_from)
    return _registry_store(key, plan)


def free_plans() -> None:
    """Evict every cached plan, running the delete callback on each — so
    composite plans drop their nested entries and the factorization refs
    they pinned are released symmetrically with LRU eviction."""
    while True:
        keys = _PLANS.keys()
        if not keys:
            return
        _drop_plan(keys[0])


def set_plan_cache_capacity(capacity: int) -> None:
    """Bound the plan registry (evicting LRU entries if needed)."""
    _PLANS.set_capacity(capacity)


def plan_cache_stats() -> dict[str, int]:
    out = dict(_PLANS.stats)
    out["size"] = len(_PLANS)
    out["capacity"] = _PLANS.capacity
    return out


def plan_cache_entries() -> list[A2APlan]:
    """Snapshot of the live plans, LRU-oldest first (for logging/artifacts;
    does not touch recency or stats)."""
    return _PLANS.values()


# The plan-cache slice of the unified telemetry snapshot
# (core.telemetry.metrics_snapshot -> "plan_cache.*").
telemetry.register_stats_provider("plan_cache", plan_cache_stats)
