"""Empirical autotuner: measured plan selection with a persistent tuning DB.

The paper's §5 conclusion — "by choosing the factorization of p and
selecting appropriate implementations for the component MPI_Alltoall
operations, the presented implementation gives ample opportunities for
algorithm tuning and adaptation to the particular high-performance
system" — is exploited analytically by ``tuning.choose_algorithm``
(alpha-beta model) and *empirically* here: :func:`autotune` times real
executions of candidate configurations for one ``(mesh, axes,
block_shape, dtype)`` plan key and records the measured winner in a
persistent JSON database, keyed by the memoized device fingerprint from
``core.cache`` plus the plan key, so the search cost is paid once per
machine x shape, ever.

Search space (bounded by ``budget_seconds``):

* backend per plan — ``direct`` | ``factorized`` (the search times the
  compute-free exchange, where an ``overlap`` plan runs the factorized
  rounds unchunked, so it is no separate candidate),
* round order — permutations of the active per-dimension rounds
  (exhaustive for d <= 3, identity + reversal beyond),
* candidate torus factorizations from ``tuning.candidate_factorizations``
  over the same devices (measured on auxiliary Cartesian meshes; recorded
  for mesh-construction decisions, never applied behind the caller's
  axes).

Timing discipline: per candidate, ``warmup`` untimed executions then
``repeats`` timed ones; the score is the median (robust to scheduler
noise); every executed call is counted in ``autotune_stats()
["timing_executions"]`` so tests can prove a DB hit performs zero
measurements.

Per-axis link feedback (the analytic-model bridge): a two-point
alpha-beta fit over each active axis turns measured single-axis
all-to-all times into per-axis :class:`~repro.core.tuning.LinkModel`
overrides, recorded in the DB and fed back into the cost model (which
accepts per-axis links end-to-end) — so the
cost model a DB-hit plan reports is priced with *this machine's*
bandwidths, not the TPU-flavoured defaults.

Integration: ``plan_all_to_all(..., backend="autotune")`` consults the
DB — hit → build the recorded winner instantly (``tuned_from:
"measured"``); miss → fall back to the analytic ``choose_algorithm``
choice (``tuned_from: "model"``) *without* measuring, so jitted paths
never block on a search.  Only an explicit :func:`autotune` call times
anything.

DB location: ``$REPRO_TUNING_DB`` if set, else
``~/.cache/repro/tuning.json`` (``$XDG_CACHE_HOME`` honored).  Corrupt,
truncated, or unreadable DB files are ignored with a warning — plan
construction must never crash on tuning state.
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import statistics
import time
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from . import telemetry
from .cache import cart_create, device_fingerprint
from .dims import max_dims
from .factorized import _as_tuple
from .tuning import LinkModel, candidate_factorizations

DB_VERSION = 1

# Backends a DB record is allowed to request at plan-build time.  The
# measured search records direct or factorized; overlap winners come from
# records of earlier searches, which timed chunked compute-free rounds.
MEASURED_BACKENDS = ("direct", "factorized", "overlap")

# Backends the ragged-family measured search (``autotune_ragged``) may
# record as a winner: the dense-bucketed ragged executor vs the
# sparse-neighborhood one.  Sparse must *win on measured time* to be
# recorded — there is no analytic shortcut into a measured record.
RAGGED_MEASURED_BACKENDS = ("ragged", "sparse")


# ---------------------------------------------------------------------------
# The persistent tuning database
# ---------------------------------------------------------------------------

def default_db_path() -> Path:
    """``$REPRO_TUNING_DB`` override, else ``~/.cache/repro/tuning.json``."""
    env = os.environ.get("REPRO_TUNING_DB")
    if env:
        return Path(env).expanduser()
    cache_home = os.environ.get("XDG_CACHE_HOME")
    base = Path(cache_home).expanduser() if cache_home \
        else Path.home() / ".cache"
    return base / "repro" / "tuning.json"


# Per-DB-path write counters, bumped on every successful write/clear so
# the plan registry (which caches resolved "autotune" plans) can key on
# DB state and re-resolve after a new measurement lands.  Per path, not
# global: writing a scratch DB must not invalidate cached plans resolved
# against the default one.
_GENERATIONS: dict[str, int] = {}


def db_generation(path=None) -> int:
    p = Path(path).expanduser() if path is not None else default_db_path()
    return _GENERATIONS.get(str(p), 0)


def _bump_generation(path: Path) -> None:
    _GENERATIONS[str(path)] = _GENERATIONS.get(str(path), 0) + 1


class TuningDB:
    """Persistent ``key -> measured record`` store (one JSON file).

    Robustness contract: a missing, corrupt, truncated, or unreadable
    file loads as empty with a single warning; a failed write warns and
    leaves the in-memory state usable.  Writes are atomic (temp file +
    ``os.replace``) so a crashed process never truncates the DB.

    Lock contention contract: the advisory flock serializing
    read-merge-writes is acquired with a bounded timeout
    (``lock_timeout`` seconds, exponential backoff between attempts;
    default from ``$REPRO_TUNING_LOCK_TIMEOUT`` or 5s).  A wedged
    lock-holder therefore degrades this process to *in-memory tuning* —
    the record lands in a per-handle overlay that ``get``/``load`` still
    see — instead of hanging the trainer on a file lock.
    """

    def __init__(self, path: str | os.PathLike | None = None,
                 lock_timeout: float | None = None):
        self.path = Path(path).expanduser() if path is not None \
            else default_db_path()
        # precomputed string form: the plan registry embeds it in every
        # autotune cache key, on the steady-state fetch path
        self.path_key = str(self.path)
        if lock_timeout is None:
            lock_timeout = float(os.environ.get(
                "REPRO_TUNING_LOCK_TIMEOUT", 5.0))
        self.lock_timeout = lock_timeout
        # records that could not be persisted (lock timeout): visible to
        # this handle's reads, overwritten by any later successful put
        self._overlay: dict[str, dict] = {}

    def generation(self) -> int:
        return _GENERATIONS.get(self.path_key, 0)

    def load(self) -> dict:
        """The ``{key: record}`` entry map (empty on any load problem)."""
        try:
            raw = self.path.read_text()
        except FileNotFoundError:
            return self._with_overlay({})
        except (OSError, UnicodeDecodeError) as e:
            # UnicodeDecodeError: corrupted-to-garbage bytes (not UTF-8)
            warnings.warn(f"unreadable tuning DB {self.path}: {e}; "
                          "treating as empty", stacklevel=2)
            return self._with_overlay({})
        try:
            doc = json.loads(raw)
            if not isinstance(doc, dict) or \
                    not isinstance(doc.get("entries"), dict):
                raise ValueError("not a tuning-DB document")
        except (ValueError, TypeError) as e:
            warnings.warn(f"corrupt tuning DB {self.path} ({e}); "
                          "treating as empty", stacklevel=2)
            return self._with_overlay({})
        if doc.get("version") != DB_VERSION:
            # A future format: don't guess, don't crash, don't clobber
            # until someone actually stores a new measurement.
            warnings.warn(f"tuning DB {self.path} has version "
                          f"{doc.get('version')!r} != {DB_VERSION}; "
                          "ignoring its entries", stacklevel=2)
            return self._with_overlay({})
        return self._with_overlay(doc["entries"])

    def _with_overlay(self, entries: dict) -> dict:
        """Merge unpersisted (lock-timeout) records over the file state."""
        if self._overlay:
            entries = {**entries, **self._overlay}
        return entries

    def get(self, key: str) -> dict | None:
        return self.load().get(key)

    def put(self, key: str, record: dict) -> bool:
        """Merge one record and persist; True if the write landed.

        The read-merge-write runs under an advisory file lock (POSIX
        ``flock`` on ``<db>.lock``) so two processes autotuning different
        keys against the shared default DB don't drop each other's
        records; where locking is unavailable the atomic replace still
        prevents corruption (last writer wins per whole file).

        If the lock cannot be acquired within ``lock_timeout`` seconds
        (a wedged holder), the record is kept in this handle's in-memory
        overlay — reads still see it, a later successful ``put`` flushes
        it — and False is returned after a warning, never a hang.
        """
        try:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            with self._locked():
                entries = self.load()   # merges any pending overlay
                entries[key] = record
                doc = {"version": DB_VERSION, "entries": entries}
                tmp = self.path.with_name(self.path.name + ".tmp")
                tmp.write_text(json.dumps(doc, indent=1))
                os.replace(tmp, self.path)
        except TimeoutError as e:
            self._overlay[key] = record
            warnings.warn(
                f"{e}; degrading to in-memory tuning (record kept in this "
                "process, not persisted)", stacklevel=2)
            _bump_generation(self.path)   # readers of this handle see it
            return False
        except OSError as e:
            warnings.warn(f"could not write tuning DB {self.path}: {e}",
                          stacklevel=2)
            return False
        self._overlay.clear()             # flushed with this write
        _bump_generation(self.path)
        return True

    def _locked(self):
        import contextlib
        try:
            import fcntl
        except ImportError:                   # non-POSIX: best effort
            return contextlib.nullcontext()
        timeout = self.lock_timeout

        @contextlib.contextmanager
        def lock():
            lockfile = self.path.with_name(self.path.name + ".lock")
            with open(lockfile, "w") as fh:
                # Non-blocking acquisition with exponential backoff: a
                # wedged holder must surface as a TimeoutError the caller
                # degrades on, never as an indefinite flock wait.
                deadline = time.perf_counter() + max(0.0, timeout)
                delay = 0.005
                while True:
                    try:
                        fcntl.flock(fh, fcntl.LOCK_EX | fcntl.LOCK_NB)
                        break
                    except OSError:
                        if time.perf_counter() >= deadline:
                            raise TimeoutError(
                                f"tuning-DB lock {lockfile} not acquired "
                                f"within {timeout}s")
                        time.sleep(delay)
                        delay = min(delay * 2, 0.1)
                try:
                    yield
                finally:
                    fcntl.flock(fh, fcntl.LOCK_UN)
        return lock()

    def clear(self) -> None:
        """Delete the DB file (missing file is fine).  Takes the same
        advisory lock as ``put`` so a concurrent read-merge-write can't
        resurrect the cleared entries."""
        self._overlay.clear()
        try:
            with self._locked():
                self.path.unlink()
        except FileNotFoundError:
            pass
        except TimeoutError as e:
            warnings.warn(f"{e}; cleared in-memory state only",
                          stacklevel=2)
            _bump_generation(self.path)
            return
        except OSError as e:
            warnings.warn(f"could not delete tuning DB {self.path}: {e}",
                          stacklevel=2)
            return
        _bump_generation(self.path)

    def __len__(self) -> int:
        return len(self.load())

    def __repr__(self):
        return f"TuningDB({str(self.path)!r})"


# Default handle, memoized per *resolved* path — the same resolution
# autotune()'s default TuningDB() performs — so the two default-DB code
# paths can never diverge, and env changes (tests monkeypatching
# REPRO_TUNING_DB / XDG_CACHE_HOME) take effect immediately.
_DEFAULT_DBS: dict[str, TuningDB] = {}


def get_default_db() -> TuningDB:
    path = str(default_db_path())
    db = _DEFAULT_DBS.get(path)
    if db is None:
        db = _DEFAULT_DBS[path] = TuningDB(path)
    return db


# ---------------------------------------------------------------------------
# Keys, stats, lookup
# ---------------------------------------------------------------------------

_STATS = {"searches": 0, "timing_executions": 0,
          "db_hits": 0, "db_misses": 0}


def autotune_stats() -> dict[str, int]:
    """Counters: measured searches run, timed executions performed, and
    plan-construction DB hits/misses (``backend="autotune"`` lookups)."""
    return dict(_STATS)


# The autotuner slice of the unified telemetry snapshot
# (core.telemetry.metrics_snapshot -> "autotune.*").
telemetry.register_stats_provider("autotune", autotune_stats)


def reset_autotune_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


def fingerprint_digest(dev_key) -> str:
    """Short stable digest of a ``core.cache.device_fingerprint`` tuple —
    512-device fingerprints stay out of the JSON keys ("none" for
    device-agnostic dims-tuple plans, which therefore never hit records
    stored from real measurements)."""
    if dev_key is None:
        return "none"
    return hashlib.sha1(repr(dev_key).encode()).hexdigest()[:16]


def plan_db_key(dev_key, dims, axis_names, block_shape, dtype,
                variant: str) -> str:
    """Stable DB key: device-fingerprint digest + the plan identity."""
    fp = fingerprint_digest(dev_key)
    block = "x".join(str(int(s)) for s in block_shape)
    return (f"fp:{fp}|dims:{','.join(str(int(s)) for s in dims)}"
            f"|axes:{','.join(axis_names)}|block:{block}"
            f"|dtype:{jnp.dtype(dtype).name}|variant:{variant}")


def ragged_db_key(dev_key, dims, axis_names, row_shape, dtype,
                  max_count: int, variant: str, density: float) -> str:
    """Stable DB key for the ragged-vs-sparse measured choice.

    Extends :func:`plan_db_key`'s identity with the ragged bucket bound
    and a coarse density bucket (one decade per bucket: 1.0, 0.1, 0.01,
    ...) — the dense<->sparse crossover moves with orders of magnitude
    of occupancy, not percents, and a finer key would fragment the DB.
    """
    fp = fingerprint_digest(dev_key)
    row = "x".join(str(int(s)) for s in row_shape) or "scalar"
    if not 0.0 < density <= 1.0:
        raise ValueError(f"density must be in (0, 1], got {density}")
    decade = min(6, max(0, -int(math.floor(math.log10(density)))))
    return (f"ragged|fp:{fp}|dims:{','.join(str(int(s)) for s in dims)}"
            f"|axes:{','.join(axis_names)}|row:{row}"
            f"|dtype:{jnp.dtype(dtype).name}|max:{int(max_count)}"
            f"|variant:{variant}|rho:1e-{decade}")


def _valid_record(rec) -> bool:
    if not isinstance(rec, dict):
        return False
    w = rec.get("winner")
    return (isinstance(w, dict)
            and w.get("backend") in MEASURED_BACKENDS
            and isinstance(w.get("n_chunks", 1), int))


def lookup_measured(dev_key, dims, axis_names, block_shape, dtype,
                    variant: str, db: TuningDB | None = None) -> dict | None:
    """The plan-construction side of the DB: a validated record or None.

    Counts a hit/miss in ``autotune_stats``; malformed records (a
    hand-edited DB, a newer writer) are treated as misses so
    ``plan_all_to_all`` can always fall back to the analytic model.
    """
    db = db if db is not None else get_default_db()
    rec = db.get(plan_db_key(dev_key, dims, axis_names, block_shape,
                             dtype, variant))
    if rec is not None and not _valid_record(rec):
        warnings.warn(f"ignoring malformed tuning record in {db.path}",
                      stacklevel=2)
        rec = None
    if rec is None:
        _STATS["db_misses"] += 1
    else:
        _STATS["db_hits"] += 1
    return rec


def _valid_ragged_record(rec) -> bool:
    if not isinstance(rec, dict):
        return False
    w = rec.get("winner")
    return (isinstance(w, dict)
            and w.get("backend") in RAGGED_MEASURED_BACKENDS)


def lookup_ragged_measured(dev_key, dims, axis_names, row_shape, dtype,
                           max_count: int, variant: str, density: float,
                           db: TuningDB | None = None) -> dict | None:
    """The consumption side of :func:`autotune_ragged`: a validated
    ragged-vs-sparse record or None.  Same hit/miss accounting and
    malformed-record tolerance as :func:`lookup_measured` — a miss means
    the caller falls back to the analytic density-aware policy
    (``tuning.choose_ragged_algorithm``), never a blocking measurement.
    """
    db = db if db is not None else get_default_db()
    rec = db.get(ragged_db_key(dev_key, dims, axis_names, row_shape, dtype,
                               max_count, variant, density))
    if rec is not None and not _valid_ragged_record(rec):
        warnings.warn(f"ignoring malformed ragged tuning record in "
                      f"{db.path}", stacklevel=2)
        rec = None
    if rec is None:
        _STATS["db_misses"] += 1
    else:
        _STATS["db_hits"] += 1
    return rec


def demote_hit_to_miss() -> None:
    """Reclassify the last counted hit as a miss: called by the plan
    layer when a looked-up record proves unusable at build time, so
    ``db_hits`` stays equal to the number of plans actually built from
    measurements (what the dryrun telemetry documents)."""
    _STATS["db_hits"] -= 1
    _STATS["db_misses"] += 1


def migrate_records(db: "TuningDB", old_dev_key, new_dev_key, dims,
                    axis_names) -> int:
    """Re-key measured winners from a dead device set onto its rebuilt
    survivor torus (the ``TorusComm.rebuild`` tuning-migration step).

    Only records whose plan identity is still valid on the new torus
    migrate: every axis the record was measured over must exist in the
    new comm's ``axis_names`` with the *same extent* (the typical case is
    a sub-axes plan — e.g. a single-axis exchange whose dimension length
    survived the re-factorization).  Migrated records keep their measured
    winner and links but gain ``"migrated": True`` — they are a
    warm-start heuristic, since the surviving physical links may differ;
    a later explicit :func:`autotune` overwrites them with fresh
    measurements.  Returns the number of records migrated.
    """
    old_fp, new_fp = fingerprint_digest(old_dev_key), \
        fingerprint_digest(new_dev_key)
    if old_fp == new_fp or old_fp == "none" or new_fp == "none":
        return 0
    new_extent = {a: int(Dk) for a, Dk in zip(axis_names, dims)}
    prefix = f"fp:{old_fp}|"
    migrated = 0
    for key, rec in db.load().items():
        if not key.startswith(prefix) or not _valid_record(rec):
            continue
        rec_axes = rec.get("axis_names") or ()
        rec_dims = rec.get("dims") or ()
        if not rec_axes or len(rec_axes) != len(rec_dims):
            continue
        if any(new_extent.get(a) != int(Dk)
               for a, Dk in zip(rec_axes, rec_dims)):
            continue
        if db.put(f"fp:{new_fp}|" + key[len(prefix):],
                  {**rec, "migrated": True}):
            migrated += 1
    return migrated


def measured_links(record: dict) -> tuple[LinkModel, ...] | None:
    """Per-axis LinkModels recorded by the search, if the fit succeeded."""
    raw = record.get("measured_links")
    if not raw:
        return None
    try:
        return tuple(LinkModel(alpha=float(l["alpha"]),
                               bandwidth=float(l["bandwidth"]))
                     for l in raw)
    except (KeyError, TypeError, ValueError):
        return None


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def _timed(fn, x, *, warmup: int, repeats: int, **span_attrs) -> float:
    """Median wall seconds of ``fn(x)``; every execution (warmup included)
    is counted in the timing_executions stat.

    Emits one ``autotune.measure`` telemetry span per candidate (attrs
    from ``span_attrs`` plus the measured median).  The tracer is forced
    off *around the executions themselves* so a sweep run under tracing
    still measures the fused jit path — the stepped per-round traced
    path must never contaminate a tuning record, and measurement
    repetitions must not feed the drift detector they calibrate."""
    tr = telemetry.get_tracer()
    with tr.span("autotune.measure", cat="autotune", warmup=warmup,
                 repeats=repeats, **span_attrs) as sp:
        was_enabled = tr.enabled
        tr.enabled = False
        try:
            for _ in range(max(0, warmup)):
                jax.block_until_ready(fn(x))
                _STATS["timing_executions"] += 1
            ts = []
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x))
                ts.append(time.perf_counter() - t0)
                _STATS["timing_executions"] += 1
        finally:
            tr.enabled = was_enabled
        med = statistics.median(ts)
        sp.set(median_us=med * 1e6)
    return med


def _operand(p: int, block_shape, dtype):
    """Deterministic global (p, p, *block) host_fn operand."""
    n = p * p * math.prod(block_shape)
    return (jnp.arange(n) % 251).reshape((p, p) + tuple(block_shape)) \
        .astype(dtype)


def _fit_axis_links(mesh, axis_names, dims, dtype, *, warmup, repeats,
                    deadline) -> list[dict] | None:
    """Two-point alpha-beta fit per active axis from measured single-axis
    all-to-alls: t(b) = (D_k - 1) * (alpha_k + b / bw_k) at two payload
    sizes solves for (alpha_k, bw_k).  Returns JSON-ready dicts —
    trivial (size-1) axes, which no prediction ever prices, get a fixed
    placeholder marked ``fit: False`` to keep the list positional with
    the axes — or None when the fit is infeasible (noise-swamped
    timings, budget exhausted).
    """
    from .plan import plan_all_to_all

    e_small, e_big = 16, 4096
    itemsize = jnp.dtype(dtype).itemsize
    out = []
    for ax, Dk in zip(axis_names, dims):
        if Dk <= 1:
            # never on the critical path; keep a sane placeholder so the
            # list stays positional with the axes
            out.append({"alpha": 1e-6, "bandwidth": 1e9, "fit": False})
            continue
        if time.perf_counter() > deadline:
            return None
        ts = []
        for nelem in (e_small, e_big):
            plan = plan_all_to_all(mesh, (ax,), (nelem,), dtype,
                                   backend="factorized")
            x = _operand(Dk, (nelem,), dtype)
            ts.append(_timed(plan.host_fn(mesh), x, warmup=warmup,
                             repeats=repeats))
        b1, b2 = e_small * itemsize, e_big * itemsize
        t1, t2 = ts
        if t2 <= t1:          # noise swamped the size difference
            return None
        bw = (Dk - 1) * (b2 - b1) / (t2 - t1)
        alpha = t1 / (Dk - 1) - b1 / bw
        out.append({"alpha": max(alpha, 1e-9),
                    "bandwidth": max(bw, 1e3), "fit": True})
    return out


def _subgroup_devices(mesh: Mesh, axes) -> list:
    """Devices of one communication subgroup: the tuned axes swept, every
    other mesh axis pinned at index 0.  The factorization sweep rebuilds
    its auxiliary Cartesian meshes over exactly these devices — for axes
    spanning the whole mesh this is all of them, for a subset (MoE EP
    axes on a mesh that also has "model") it is one representative
    group, which is what a single all-to-all actually runs over.

    Returned in this package's linearization: most-significant requested
    axis outermost (row-major flat list, fastest digit contiguous) — the
    order ``cart_create`` expects.
    """
    import numpy as np
    idx = tuple(slice(None) if n in axes else 0 for n in mesh.axis_names)
    sub = mesh.devices[idx]
    sel = [n for n in mesh.axis_names if n in axes]
    sub = np.transpose(sub, [sel.index(a) for a in reversed(axes)])
    return list(sub.flat)


def _round_orders(d_active: int, round_orders):
    if round_orders is not None:
        return [tuple(o) for o in round_orders]
    if d_active <= 1:
        return [tuple(range(d_active))]
    if d_active <= 3:
        import itertools
        return list(itertools.permutations(range(d_active)))
    ident = tuple(range(d_active))
    return [ident, tuple(reversed(ident))]


def autotune(mesh: Mesh, axis_names, block_shape, dtype, **kwargs):
    """Measure candidate configurations, persist the winner, return its plan.

    The returned :class:`~repro.core.plan.A2APlan` is exactly what any
    later ``plan_all_to_all(mesh, axes, block_shape, dtype,
    backend="autotune")`` call will reconstruct from the DB (``describe()
    ["tuned_from"] == "measured"``).

    ``budget_seconds`` bounds the whole search: once exceeded, remaining
    candidates are recorded as skipped (never silently dropped) — the
    direct and factorized baselines are always measured.

    The whole sweep runs under one ``autotune.search`` telemetry span
    (child ``autotune.measure`` spans per candidate) — see
    ``core.telemetry``.
    """
    axes = _as_tuple(axis_names)
    with telemetry.get_tracer().span(
            "autotune.search", cat="autotune", kind="dense",
            axes=",".join(axes),
            dims="x".join(str(int(mesh.shape[a])) for a in axes)):
        return _autotune_impl(mesh, axes, block_shape, dtype, **kwargs)


def _autotune_impl(mesh: Mesh, axis_names, block_shape, dtype, *,
                   variant: str = "natural", round_orders=None,
                   include_factorizations: bool = True,
                   warmup: int = 2, repeats: int = 5,
                   budget_seconds: float = 20.0, fit_links: bool = True,
                   db: TuningDB | None = None, verbose: bool = False):
    from .plan import plan_all_to_all

    axes = _as_tuple(axis_names)
    dims = tuple(int(mesh.shape[a]) for a in axes)
    p = math.prod(dims)
    dev_key = device_fingerprint(mesh)
    db = db if db is not None else TuningDB()
    deadline = time.perf_counter() + budget_seconds
    _STATS["searches"] += 1

    block_shape = tuple(int(s) for s in block_shape)
    x = _operand(p, block_shape, dtype)

    links_fitted = None
    if fit_links:
        links_fitted = _fit_axis_links(mesh, axes, dims, dtype,
                                       warmup=warmup, repeats=repeats,
                                       deadline=deadline)

    # ---- candidate list on the caller's axes (winner-eligible) ----
    d_active = len([D for D in dims if D > 1])
    cands = [("direct", tuple(range(d_active)))]
    for order in _round_orders(d_active, round_orders):
        cands.append(("factorized", order))

    table, skipped = [], []
    for i, (backend, order) in enumerate(cands):
        if i >= 2 and time.perf_counter() > deadline:
            skipped.append({"backend": backend, "round_order": list(order),
                            "n_chunks": 1})
            continue
        plan = plan_all_to_all(mesh, axes, block_shape, dtype,
                               backend=backend, variant=variant,
                               round_order=order)
        med = _timed(plan.host_fn(mesh), x, warmup=warmup, repeats=repeats,
                     backend=backend,
                     round_order=",".join(str(o) for o in order))
        table.append({"backend": backend, "dims": list(dims),
                      "round_order": list(order), "n_chunks": 1,
                      "median_us": med * 1e6, "eligible": True})
        if verbose:
            print(f"[autotune] {backend} order={order}: "
                  f"{med * 1e6:.1f}us")

    # ---- alternative factorizations of p (informational rows: they need
    # a different Cartesian mesh, so they can't be applied behind the
    # caller's axes — recorded to steer mesh construction) ----
    if include_factorizations and p > 1:
        group_devices = _subgroup_devices(mesh, axes)
        for dims_msf in candidate_factorizations(p, max_d=min(4,
                                                              max_dims(p))):
            dims_ff = tuple(reversed(dims_msf))   # fastest digit first
            if dims_ff == dims or len(dims_ff) == 1:
                continue
            if time.perf_counter() > deadline:
                skipped.append({"backend": "factorized",
                                "dims": list(dims_ff), "n_chunks": 1})
                continue
            aux_names = tuple(f"at{i}" for i in range(len(dims_ff)))
            aux_mesh = cart_create(group_devices, dims_ff, aux_names)
            plan = plan_all_to_all(aux_mesh, aux_names, block_shape, dtype,
                                   backend="factorized", variant=variant)
            med = _timed(plan.host_fn(aux_mesh), x, warmup=warmup,
                         repeats=repeats, backend="factorized",
                         dims="x".join(str(s) for s in dims_ff))
            table.append({"backend": "factorized", "dims": list(dims_ff),
                          "round_order": list(range(len(dims_ff))),
                          "n_chunks": 1, "median_us": med * 1e6,
                          "eligible": False})
            if verbose:
                print(f"[autotune] factorized dims={dims_ff}: "
                      f"{med * 1e6:.1f}us")
    if skipped and verbose:
        print(f"[autotune] budget exhausted; skipped {len(skipped)} "
              f"candidates: {skipped}")

    eligible = [r for r in table if r["eligible"]]
    win = min(eligible, key=lambda r: r["median_us"])
    best_row = min(table, key=lambda r: r["median_us"])
    record = {
        "version": DB_VERSION,
        "winner": {"backend": win["backend"],
                   "round_order": win["round_order"],
                   "n_chunks": int(win["n_chunks"]),
                   "median_us": win["median_us"]},
        "p": p, "dims": list(dims), "axis_names": list(axes),
        "block_shape": list(block_shape),
        "dtype": jnp.dtype(dtype).name, "variant": variant,
        "best_factorization": {"dims": best_row["dims"],
                               "backend": best_row["backend"],
                               "median_us": best_row["median_us"]},
        "measured_links": links_fitted,
        "table": table, "skipped": skipped,
        "warmup": warmup, "repeats": repeats,
        "created": time.time(),
    }
    db.put(plan_db_key(dev_key, dims, axes, block_shape, dtype, variant),
           record)
    # Reconstruct through the DB path so the returned plan is the exact
    # object later backend="autotune" callers fetch (tuned_from="measured").
    return plan_all_to_all(mesh, axes, block_shape, dtype,
                           backend="autotune", variant=variant, db=db)


def _sparse_counts_operand(p: int, max_count: int, density: float,
                           seed: int = 0):
    """Deterministic global (p, p) int32 count matrix at roughly the
    requested non-zero density (at least one non-zero pair, so the
    operand always exercises the data rounds)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    counts = (rng.random((p, p)) < density) \
        * rng.integers(1, max_count + 1, (p, p))
    counts = counts.astype(np.int32)
    if not counts.any():
        counts[0, p - 1] = max_count
    return jnp.asarray(counts)


def autotune_ragged(mesh: Mesh, axis_names, row_shape, dtype, *,
                    max_count: int, density: float, **kwargs):
    """Measure dense-bucketed ragged vs sparse-neighborhood Alltoallv on
    a representative sparse operand and persist the winner.

    The two candidates run their jitted ``host_fn`` over the same
    deterministic ``(p, p, bucket, *row)`` payload and a count matrix
    drawn at the requested ``density`` — so the sparse backend's skip
    predicates see realistic emptiness, and it is recorded as the winner
    **only when it wins on measured time** (the same discipline as the
    dense autotuner: no analytic shortcut into a measured record).
    Returns the winning plan; the record is consumed by
    :func:`lookup_ragged_measured` (e.g. the dropless-MoE plan chooser
    under ``a2a_backend="autotune"``).  The sweep runs under one
    ``autotune.search`` telemetry span like the dense search.
    """
    axes = _as_tuple(axis_names)
    with telemetry.get_tracer().span(
            "autotune.search", cat="autotune", kind="ragged",
            axes=",".join(axes), density=float(density),
            dims="x".join(str(int(mesh.shape[a])) for a in axes)):
        return _autotune_ragged_impl(mesh, axes, row_shape, dtype,
                                     max_count=max_count, density=density,
                                     **kwargs)


def _autotune_ragged_impl(mesh: Mesh, axis_names, row_shape, dtype, *,
                          max_count: int, density: float,
                          avg_count: float | None = None,
                          variant: str = "natural", warmup: int = 2,
                          repeats: int = 5, seed: int = 0,
                          db: TuningDB | None = None,
                          verbose: bool = False):
    from .comm import torus_comm
    from .ragged import next_pow2

    axes = _as_tuple(axis_names)
    dims = tuple(int(mesh.shape[a]) for a in axes)
    p = math.prod(dims)
    dev_key = device_fingerprint(mesh)
    db = db if db is not None else TuningDB()
    _STATS["searches"] += 1

    row_shape = tuple(int(s) for s in row_shape)
    max_count = int(max_count)
    bucket = next_pow2(max_count)
    counts = _sparse_counts_operand(p, max_count, density, seed)
    x = _operand(p, (bucket,) + row_shape, dtype)

    comm = torus_comm(mesh, axes, variant=variant, db=db)
    ragged_plan = comm.ragged_all_to_all(row_shape, dtype,
                                         max_count=max_count,
                                         avg_count=avg_count)
    sparse_plan = comm.sparse_all_to_all(row_shape, dtype,
                                         max_count=max_count,
                                         avg_count=avg_count,
                                         density=density)
    table = []
    for backend, plan in (("ragged", ragged_plan), ("sparse", sparse_plan)):
        fn = plan.host_fn(mesh)
        med = _timed(lambda _: fn(x, counts), None, warmup=warmup,
                     repeats=repeats, backend=backend)
        table.append({"backend": backend, "median_us": med * 1e6})
        if verbose:
            print(f"[autotune_ragged] {backend}: {med * 1e6:.1f}us")

    win = min(table, key=lambda r: r["median_us"])
    record = {
        "version": DB_VERSION,
        "winner": {"backend": win["backend"],
                   "median_us": win["median_us"]},
        "p": p, "dims": list(dims), "axis_names": list(axes),
        "row_shape": list(row_shape), "dtype": jnp.dtype(dtype).name,
        "max_count": max_count, "bucket": bucket, "variant": variant,
        "density": float(density), "table": table,
        "warmup": warmup, "repeats": repeats, "seed": seed,
        "created": time.time(),
    }
    db.put(ragged_db_key(dev_key, dims, axes, row_shape, dtype, max_count,
                         variant, density), record)
    return sparse_plan if win["backend"] == "sparse" else ragged_plan
