"""Pencil-decomposition multidimensional FFT on the torus transpose.

The classic distributed-memory FFT (Dalcin et al., "Fast parallel
multidimensional FFT using advanced MPI", arXiv 1804.09536) keeps each
array axis *either* fully local *or* sharded: local axes are transformed
with the on-device FFT, then a **global transpose** re-shards the array
so the next axis becomes local.  Every transpose is an all-to-all of one
contiguous pencil chunk per peer — exactly the paper's factorized
zero-copy collective — so here each transpose is a cached
:class:`~repro.core.plan.TransposePlan` resolved through any dense
backend (``direct`` / ``factorized`` / ``overlap`` / ``tuned`` /
``autotune``).

Decomposition model
-------------------

A rank-``m`` global array on a rank-``d`` torus.  The torus axes are
partitioned into ``g`` *groups* (``grid``); group ``k`` (size ``q_k``,
the product of its axis dims) shards array axis ``k`` of the input.
``g = d`` with singleton groups is the pencil decomposition;
``g = 1`` with every torus axis in one group is the slab decomposition
(the only option for 2-D arrays, where a single axis must absorb the
whole torus).  Array axes ``g..m-1`` start local.

Forward: transform the local axes, then for ``k = g-1 .. 0`` transpose
over group ``k`` (axis ``k+1`` becomes sharded, axis ``k`` becomes
local) and transform axis ``k``.  The output is sharded on axes
``1..g``; axis 0 is local.  Inverse mirrors the chain exactly, and each
inverse transpose is the *same* plan's drain direction
(``inverse_apply``), so a forward/inverse pair resolves one plan per
stage.

The whole data path is one ``jax.jit(jax.shard_map(...))`` per
direction — zero host round-trips between stages.  With the telemetry
tracer enabled the pipeline switches to a stepped per-stage path so
every transpose round gets a measured span and a drift observation
(same contract as ``A2APlan.host_fn``).

Correctness oracle: ``core.simulator.simulate_pencil_transpose``; the
full pipeline is validated against ``numpy.fft`` at 12 devices in
``tests/device_scripts/check_fft.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core import telemetry

__all__ = ["PencilFFT", "pencil_fft"]

_COMPLEX = {"float32": "complex64", "float64": "complex128",
            "complex64": "complex64", "complex128": "complex128"}


def _normalize_axes(axes, m: int) -> tuple[int, ...]:
    if axes is None:
        return tuple(range(m))
    out = []
    for ax in axes:
        ax = int(ax)
        if ax < 0:
            ax += m
        if not 0 <= ax < m:
            raise ValueError(f"fft axis {ax} outside array rank {m}")
        out.append(ax)
    if len(set(out)) != len(out):
        raise ValueError(f"duplicate fft axes {axes}")
    return tuple(sorted(out))


class PencilFFT:
    """A resolved pencil/slab-decomposed FFT over a :class:`TorusComm`.

    Parameters
    ----------
    comm:
        Torus communicator; its mesh hosts the data path.
    global_shape:
        Global (unsharded) array shape, rank ``m >= 2``.
    axes:
        Array axes to transform (default: all).  The transpose chain is
        fixed by the decomposition — axes outside ``axes`` still ride
        the re-shard, they just skip the local transform.
    grid:
        Tuple of tuples of torus axis names — group ``k`` shards array
        axis ``k``.  Default: one singleton group per torus axis when
        ``m - 1 >= d`` (pencil), else one group of all axes (slab).
    real:
        Real-input transform: ``rfft`` along the last array axis (which
        must be in ``axes``), complex transforms elsewhere; the inverse
        ends in ``irfft`` and returns a real array.
    dtype:
        Input dtype (default ``float32`` when ``real`` else
        ``complex64``); transposes run in the matching complex dtype.
    backend, links, db, **plan_kw:
        Forwarded to :meth:`TorusComm.transpose` for every stage plan.
    """

    def __init__(self, comm, global_shape, *, axes=None, grid=None,
                 real: bool = False, dtype=None, backend: str = "tuned",
                 links=None, db=None, **plan_kw):
        self.comm = comm
        self.global_shape = tuple(int(n) for n in global_shape)
        m = len(self.global_shape)
        if m < 2:
            raise ValueError("pencil FFT needs a rank >= 2 array")
        self.fft_axes = _normalize_axes(axes, m)
        if grid is None:
            grid = tuple((name,) for name in comm.axis_names) \
                if m - 1 >= comm.d else (tuple(comm.axis_names),)
        self.grid = tuple(tuple(group) for group in grid)
        g = len(self.grid)
        if not 1 <= g <= m - 1:
            raise ValueError(f"{g} torus groups need an array of rank "
                             f">= {g + 1}, got {m}")
        flat = [name for group in self.grid for name in group]
        if sorted(flat) != sorted(comm.axis_names):
            raise ValueError(f"grid {self.grid} must partition the comm "
                             f"axes {comm.axis_names}")
        self.real = bool(real)
        if self.real and m - 1 not in self.fft_axes:
            raise ValueError("real transform requires the last array "
                             "axis in `axes` (the rfft axis)")
        self.dtype = str(dtype) if dtype is not None else \
            ("float32" if self.real else "complex64")
        if self.dtype not in _COMPLEX:
            raise ValueError(f"unsupported dtype {self.dtype!r}")
        if self.real and self.dtype.startswith("complex"):
            raise ValueError("real transform takes a float input dtype")
        self.cdtype = _COMPLEX[self.dtype]
        self.backend = backend

        dim_of = dict(zip(comm.axis_names, comm.dims))
        self.group_sizes = tuple(
            math.prod(dim_of[name] for name in group)
            for group in self.grid)
        for k, q in enumerate(self.group_sizes):
            if self.global_shape[k] % q:
                raise ValueError(
                    f"array axis {k} (size {self.global_shape[k]}) not "
                    f"divisible by group {self.grid[k]} size {q}")
        self._gspecs = tuple(tuple(reversed(group)) for group in self.grid)

        # Shape the transposes see: rfft halves the last axis up front.
        work = list(self.global_shape)
        if self.real:
            work[m - 1] = work[m - 1] // 2 + 1
        cur = [work[k] // self.group_sizes[k] if k < g else work[k]
               for k in range(m)]
        self._comms = tuple(
            comm if group == tuple(comm.axis_names) else comm.sub(group)
            for group in self.grid)
        plans = [None] * g
        for k in range(g - 1, -1, -1):
            plans[k] = self._comms[k].transpose(
                tuple(cur), self.cdtype, split_axis=k + 1, concat_axis=k,
                backend=backend, links=links, db=db, **plan_kw)
            cur[k + 1] //= self.group_sizes[k]
            cur[k] *= self.group_sizes[k]
        self.plans = tuple(plans)
        self.out_local_shape = tuple(cur)

        self.in_spec = P(*[self._gspecs[k] if k < g else None
                           for k in range(m)])
        out = [None] * m
        for k in range(g):
            out[k + 1] = self._gspecs[k]
        self.out_spec = P(*out)
        self._fns: dict = {}
        self._stage_fns: dict = {}

    # -- geometry ----------------------------------------------------------

    @property
    def m(self) -> int:
        return len(self.global_shape)

    @property
    def g(self) -> int:
        return len(self.grid)

    @property
    def mesh(self) -> Mesh | None:
        return self.comm.mesh

    def _local_fft_axes(self) -> tuple[int, ...]:
        """The transformed axes that never need a transpose (local from
        the start), rfft axis excluded."""
        hi = self.m - 1 if self.real else self.m
        return tuple(ax for ax in self.fft_axes if self.g <= ax < hi)

    # -- per-shard pipeline (inside shard_map over the full mesh) ----------

    def forward_local(self, x):
        """Forward transform of this device's input pencil — local FFTs
        interleaved with :meth:`TransposePlan.apply` collectives.  Runs
        inside ``jax.shard_map`` over the comm's torus axes."""
        if self.real:
            x = jnp.fft.rfft(x, axis=self.m - 1)
        else:
            x = x.astype(self.cdtype)
        for ax in self._local_fft_axes():
            x = jnp.fft.fft(x, axis=ax)
        for k in range(self.g - 1, -1, -1):
            x = self.plans[k].apply(x)
            if k in self.fft_axes:
                x = jnp.fft.fft(x, axis=k)
        return x

    def inverse_local(self, y):
        """Exact inverse of :meth:`forward_local`: each re-shard is the
        same stage plan's drain direction, so the transpose round-trip
        is bit-identical and only the FFT pair introduces float error."""
        for k in range(self.g):
            if k in self.fft_axes:
                y = jnp.fft.ifft(y, axis=k)
            y = self.plans[k].inverse_apply(y)
        for ax in reversed(self._local_fft_axes()):
            y = jnp.fft.ifft(y, axis=ax)
        if self.real:
            y = jnp.fft.irfft(y, n=self.global_shape[self.m - 1],
                              axis=self.m - 1)
            y = y.astype(self.dtype)
        return y

    # -- host-level entry points -------------------------------------------

    def _host_fn(self, direction: str, mesh: Mesh | None):
        mesh = self.mesh if mesh is None else mesh
        if mesh is None:
            raise ValueError("comm carries no Mesh; pass one")
        local = self.forward_local if direction == "forward" \
            else self.inverse_local
        in_spec = self.in_spec if direction == "forward" else self.out_spec
        out_spec = self.out_spec if direction == "forward" else self.in_spec
        fkey = (direction, mesh)
        if fkey not in self._fns:
            self._fns[fkey] = jax.jit(jax.shard_map(
                local, mesh=mesh, in_specs=in_spec, out_specs=out_spec))
        fast = self._fns[fkey]
        tr = telemetry.get_tracer()

        def run(x):
            if not tr.enabled:
                return fast(x)
            return self._traced(tr, direction, mesh, x)

        run.jitted = fast
        return run

    def forward_fn(self, mesh: Mesh | None = None):
        """Jitted forward FFT over the global array (sharded per
        ``in_spec``; result sharded per ``out_spec``).  One fused jit
        when tracing is off — the zero-host-round-trip data path
        (exposed as ``fn.jitted`` for HLO inspection); stepped per-stage
        spans when the tracer is on."""
        return self._host_fn("forward", mesh)

    def inverse_fn(self, mesh: Mesh | None = None):
        """Jitted inverse FFT — see :meth:`forward_fn`."""
        return self._host_fn("inverse", mesh)

    # -- telemetry-traced stepped path -------------------------------------

    def _spec_of(self, dist: dict) -> P:
        return P(*[self._gspecs[dist[a]] if a in dist else None
                   for a in range(self.m)])

    def _stages(self, direction: str, mesh: Mesh):
        """``(kind, label, host_fn)`` per pipeline stage; transpose
        stages delegate to :meth:`TransposePlan.host_fn` (their own
        stepped/fused round spans), FFT stages get one jitted fn each."""
        skey = (direction, mesh)
        if skey in self._stage_fns:
            return self._stage_fns[skey]

        def fft_stage(axes_, spec, ifft=False, rfft=False, irfft=False):
            def local(x, _axes=tuple(axes_)):
                if rfft:
                    x = jnp.fft.rfft(x, axis=self.m - 1)
                if not rfft and not irfft and not ifft:
                    x = x.astype(self.cdtype)
                for ax in _axes:
                    x = (jnp.fft.ifft if ifft else jnp.fft.fft)(x, axis=ax)
                if irfft:
                    x = jnp.fft.irfft(x, n=self.global_shape[self.m - 1],
                                      axis=self.m - 1).astype(self.dtype)
                return x
            return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=spec,
                                         out_specs=spec))

        stages = []
        if direction == "forward":
            dist = {k: k for k in range(self.g)}
            spec = self._spec_of(dist)
            stages.append(("fft", "fft[local]", fft_stage(
                self._local_fft_axes(), spec, rfft=self.real)))
            for k in range(self.g - 1, -1, -1):
                s_in = self._spec_of(dist)
                del dist[k]
                dist[k + 1] = k
                s_out = self._spec_of(dist)
                stages.append(("transpose", f"transpose[{k}]",
                               self.plans[k].host_fn(
                                   mesh, in_spec=s_in, out_spec=s_out)))
                if k in self.fft_axes:
                    stages.append(("fft", f"fft[axis={k}]",
                                   fft_stage((k,), s_out)))
        else:
            dist = {k + 1: k for k in range(self.g)}
            for k in range(self.g):
                s_in = self._spec_of(dist)
                if k in self.fft_axes:
                    stages.append(("fft", f"ifft[axis={k}]",
                                   fft_stage((k,), s_in, ifft=True)))
                del dist[k + 1]
                dist[k] = k
                s_out = self._spec_of(dist)
                stages.append(("transpose", f"transpose[{k}]",
                               self.plans[k].host_fn(
                                   mesh, in_spec=s_in, out_spec=s_out)))
            stages.append(("fft", "ifft[local]", fft_stage(
                tuple(reversed(self._local_fft_axes())), self._spec_of(dist),
                ifft=True, irfft=self.real)))
        self._stage_fns[skey] = stages
        return stages

    def _traced(self, tr, direction: str, mesh: Mesh, x):
        import time
        with tr.span(f"fft.{direction}", cat="workload",
                     shape="x".join(str(n) for n in self.global_shape),
                     grid="|".join(",".join(g) for g in self.grid),
                     axes=",".join(str(a) for a in self.fft_axes),
                     real=self.real, backend=self.backend) as sp:
            t0 = time.perf_counter()
            for kind, label, fn in self._stages(direction, mesh):
                if kind == "transpose":
                    x = fn(x)      # TransposePlan.host_fn emits its spans
                else:
                    with tr.span("fft.stage", cat="workload", stage=label):
                        x = jax.block_until_ready(fn(x))
            sp.set(measured_seconds=time.perf_counter() - t0)
        return x

    # -- introspection -----------------------------------------------------

    def describe(self) -> dict:
        """Stable, JSON-serializable summary: decomposition geometry +
        every stage plan's describe."""
        preds = [p.describe()["predicted_seconds"] for p in self.plans]
        return {
            "kind": "pencil_fft",
            "global_shape": list(self.global_shape),
            "fft_axes": list(self.fft_axes),
            "grid": [list(g) for g in self.grid],
            "group_sizes": list(self.group_sizes),
            "decomposition": "slab" if self.g == 1 else "pencil",
            "real": self.real,
            "dtype": self.dtype,
            "cdtype": self.cdtype,
            "backend": self.backend,
            "out_local_shape": list(self.out_local_shape),
            "transposes": [p.describe() for p in self.plans],
            "predicted_transpose_seconds":
                None if any(t is None for t in preds) else sum(preds),
        }

    def __repr__(self):
        return (f"PencilFFT(shape={self.global_shape}, grid={self.grid}, "
                f"real={self.real}, backend={self.backend!r})")


def pencil_fft(comm, global_shape, axes=None, **kw) -> PencilFFT:
    """Build (or re-resolve — every transpose plan is registry-cached) a
    :class:`PencilFFT` over ``comm``; see the class for the knobs."""
    return PencilFFT(comm, global_shape, axes=axes, **kw)
