"""Mixture-of-Experts with expert parallelism over the *factorized torus
all-to-all* — the primary consumer of the paper's collective.

Dispatch layout (capacity-based, GShard-style):

* The EP group spans the mesh axes ``ep_axes(mesh)`` — ``("data",)`` on a
  single pod, ``("data", "pod")`` across pods.  Virtual expert rank
  ``v = data + |data| * pod``: experts are *owned* along "data" and
  *replicated* across "pod" (storage stays exact ``(E, ...)``; the virtual
  ``(G, ...)`` view is a ``reshape`` when ``E >= G`` and a ``tile`` when
  ``E < G`` — tiling makes replica gradients sum automatically).
* Each device scatters its top-k routed tokens into ``(G, E_loc, C, D)``
  composite blocks — *exactly* the paper's ``p``-block send buffer — and
  one ``A2APlan`` collective per direction moves them: on the multi-pod
  mesh this is the d=2 schedule (ICI "data" round, then DCN "pod" round),
  the paper's hierarchical decomposition.
* Expert FFN runs as a grouped matmul (``kernels.expert_matmul``) with the
  hidden dim tensor-parallel over "model" (one psum per layer).
* ``capacity_factor=None`` switches to **dropless** dispatch: the
  collective becomes the ragged Alltoallv (``core.plan
  .plan_ragged_all_to_all``) with the per-rank window sized to the worst
  case, per-rank send counts from the router, and padding waste reported
  as the plan's bucket occupancy — no token is ever dropped.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.core.autotune import lookup_ragged_measured
from repro.core.comm import torus_comm
from repro.core.ragged import next_pow2
from repro.core.tuning import choose_ragged_algorithm, default_links, \
    mesh_links
from repro.kernels import ops as kops
from repro.models.common import ParamSpec, silu, gelu
from repro.parallel.sharding import ShardingRules, constrain, ep_axes, \
    resolve_spec
from .config import ModelConfig


def moe_specs(cfg: ModelConfig) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamSpec((D, E), (None, None), dtype=jnp.float32),
        "w1": ParamSpec((E, D, F), ("expert", "embed_fsdp", "mlp")),
        "w3": ParamSpec((E, D, F), ("expert", "embed_fsdp", "mlp")),
        "w2": ParamSpec((E, F, D), ("expert", "mlp", "embed_fsdp")),
    }


def _group_geometry(cfg: ModelConfig, mesh):
    """(axes, G, E_loc, R): EP axes, group size, experts/rank, replicas."""
    if mesh is None:
        return (), 1, cfg.n_experts, 1
    axes = ep_axes(mesh)
    G = math.prod(mesh.shape[a] for a in axes)
    E = cfg.n_experts
    if E >= G:
        if E % G:
            raise ValueError(f"n_experts={E} not divisible by EP group {G}")
        return axes, G, E // G, 1
    if G % E:
        raise ValueError(f"EP group {G} not divisible by n_experts={E}")
    return axes, G, 1, G // E


def _virtual_weights(w, G: int):
    """(E, ...) -> (G, E_loc, ...) virtual-expert view (reshape or tile)."""
    E = w.shape[0]
    if E >= G:
        return w.reshape(G, E // G, *w.shape[1:])
    R = G // E
    return jnp.tile(w, (R,) + (1,) * (w.ndim - 1)) \
        .reshape(G, 1, *w.shape[1:])


def _capacity(cfg: ModelConfig, n_tokens: int, n_slots: int) -> int:
    # A single expert can receive at most n_tokens rows from one device
    # (the top_k experts of a token are distinct), so the capacity is
    # clamped there: tiny batches must not pad past the routed tokens.
    hard = max(1, n_tokens)
    if cfg.capacity_factor is None:    # dropless: worst case, no slack
        return hard
    c = math.ceil(cfg.capacity_factor * cfg.top_k * n_tokens / n_slots)
    return min(max(8, -(-c // 8) * 8), hard)  # 8-aligned, then clamped


def moe_ep_comm(cfg: ModelConfig, mesh, axes):
    """The cached Cartesian communicator of the EP group — the API root
    every MoE collective is constructed through (``core.comm``).  Fetched
    from the comm registry on every later layer/step, so the torus
    factorization and device fingerprint resolve once per (devices, EP
    axes, variant)."""
    if not axes or mesh is None:
        return None
    return torus_comm(mesh, axes, variant=cfg.a2a_variant)


def _ep_links(cfg: ModelConfig, mesh, axes):
    """Per-axis links observed from the mesh's devices for the EP plans.
    Under ``"autotune"`` none are given, so that a tuning-DB record's
    measured links take precedence."""
    return None if cfg.a2a_backend == "autotune" else mesh_links(mesh, axes)


def moe_a2a_plan(cfg: ModelConfig, mesh, axes, E_loc: int, C: int):
    """The one A2APlan shared by dispatch and combine for this MoE layer.

    Resolved once per (mesh devices, EP axes, block shape, dtype, config
    knobs) through the EP group's :class:`~repro.core.comm.TorusComm` and
    fetched from the plan registry on every later layer/step — the
    paper's cached-communicator amortization.  ``cfg.a2a_backend``
    parameterizes plan construction here and nowhere else; with
    ``"autotune"`` the dispatch/combine collective replays the measured
    winner recorded in the tuning DB for exactly this (devices, EP axes,
    block, dtype) key, falling back to the analytic model on a miss — an
    explicit ``core.autotune.autotune(...)`` run warms the DB offline.
    """
    comm = moe_ep_comm(cfg, mesh, axes)
    if comm is None:
        return None
    return comm.all_to_all(
        block_shape=(E_loc, C, cfg.d_model), dtype=cfg.cdtype,
        backend=cfg.a2a_backend, n_chunks=cfg.a2a_chunks,
        max_chunks=cfg.a2a_chunks or 4, links=_ep_links(cfg, mesh, axes))


def moe_ragged_a2a_plan(cfg: ModelConfig, mesh, axes, E_loc: int, C: int,
                        n_loc: int):
    """The RaggedA2APlan for dropless dispatch/combine
    (``capacity_factor=None``).

    One ragged row is one token embedding; each destination rank's bucket
    window holds its ``(E_loc, C)`` expert-strided slots, so ``max_count``
    is the per-rank window ``E_loc * C`` while the *expected* per-rank
    payload is ``top_k * n_loc / p`` rows — the ratio is the plan's
    occupancy estimate, the quantity dropless mode trades for never
    dropping a token.  Same registry/caching semantics as
    :func:`moe_a2a_plan` (both construct through :func:`moe_ep_comm`);
    ``cfg.a2a_backend`` resolves the padded data plan identically.
    """
    comm = moe_ep_comm(cfg, mesh, axes)
    if comm is None:
        return None
    window = E_loc * C
    avg = min(float(window), max(1.0, cfg.top_k * n_loc / comm.p))
    return comm.ragged_all_to_all(
        row_shape=(cfg.d_model,), dtype=cfg.cdtype,
        max_count=window, avg_count=avg, backend=cfg.a2a_backend,
        n_chunks=cfg.a2a_chunks, max_chunks=cfg.a2a_chunks or 4,
        links=_ep_links(cfg, mesh, axes))


def moe_dropless_a2a_plan(cfg: ModelConfig, mesh, axes, E_loc: int, C: int,
                          n_loc: int):
    """Dropless plan chooser: ragged (dense-bucketed) vs sparse
    (neighborhood) Alltoallv, decided by the router's expected density.

    The expected nonzero fraction of the p x p count matrix follows the
    Poisson occupancy of ``top_k * n_loc / p`` tokens per (source, dest)
    pair: ``rho ~= 1 - exp(-top_k * n_loc / p)``.  With
    ``cfg.a2a_backend == "autotune"`` the measured ragged-vs-sparse
    winner recorded by :func:`core.autotune.autotune_ragged` is replayed
    for exactly this (devices, EP axes, row, dtype, window, density
    decade) key; on a miss — and for every analytic backend — the
    density-aware :func:`core.tuning.choose_ragged_algorithm` prices
    both and the sparse plan is used only when it wins.  Either way the
    returned plan exposes the same ``forward``/``reverse`` bucketed
    contract, so :func:`_moe_inner` is backend-agnostic.
    """
    comm = moe_ep_comm(cfg, mesh, axes)
    if comm is None:
        return None
    window = E_loc * C
    lam = cfg.top_k * n_loc / comm.p
    density = min(1.0, max(1e-6, 1.0 - math.exp(-lam)))
    backend = None
    if cfg.a2a_backend == "autotune":
        rec = lookup_ragged_measured(
            comm.dev_key, comm.dims, comm.axis_names, (cfg.d_model,),
            cfg.cdtype, window, cfg.a2a_variant, density)
        if rec is not None:
            backend = rec["winner"]["backend"]
    if backend is None:
        row_bytes = cfg.d_model * jnp.dtype(cfg.cdtype).itemsize
        links = mesh_links(mesh, axes) or default_links(comm.axis_names)
        sched = choose_ragged_algorithm(
            comm.dims, links, row_bytes,
            next_pow2(window), max_chunks=cfg.a2a_chunks or 4,
            density=density)
        backend = sched.kind
    if backend == "sparse":
        avg = min(float(window), max(1.0, cfg.top_k * n_loc / comm.p))
        return comm.sparse_all_to_all(
            row_shape=(cfg.d_model,), dtype=cfg.cdtype, max_count=window,
            avg_count=avg, density=density,
            links=_ep_links(cfg, mesh, axes))
    return moe_ragged_a2a_plan(cfg, mesh, axes, E_loc, C, n_loc)


def _moe_inner(x, router_w, w1, w3, w2, *, cfg: ModelConfig, axes, G, E_loc,
               R, C, tp_axis, reduce_axes, plan=None, ragged_plan=None):
    """Per-device MoE computation (runs inside shard_map, or standalone when
    there is no mesh).  x: (B_loc, S, D); w*: (1, E_loc, ...) local slices
    of the virtual-expert arrays; ``plan`` is the resolved A2APlan (None
    when there is no EP group); ``ragged_plan`` the RaggedA2APlan — or the
    duck-typed SparseA2APlan, same bucketed forward/reverse contract —
    dropless mode routes through instead (``capacity_factor=None``)."""
    B, S, D = x.shape
    N = B * S
    E = cfg.n_experts
    cd = cfg.cdtype
    xt = x.reshape(N, D)
    w1, w3, w2 = w1[0], w3[0], w2[0]

    # ---- routing (f32) ----
    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, cfg.top_k)     # (N, k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # ---- per-expert positions (order: token-major, k-minor) ----
    flat_e = expert_idx.reshape(-1)                              # (N*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos_e = jnp.cumsum(onehot, axis=0) - 1                       # inclusive-1
    pos_e = jnp.take_along_axis(pos_e, flat_e[:, None], 1)[:, 0]

    if E >= G:   # experts partitioned over ranks
        v_idx = flat_e // E_loc
        sub_idx = flat_e % E_loc
        slot_pos = pos_e
    else:        # experts replicated R times: round-robin across replicas
        spread = pos_e % R
        v_idx = flat_e + E * spread       # tile layout: replica r at r*E+e
        sub_idx = jnp.zeros_like(flat_e)
        slot_pos = pos_e // R
    keep = slot_pos < C
    c_idx = jnp.where(keep, slot_pos, C)  # C = out-of-bounds -> dropped

    # ---- dispatch scatter: (G, E_loc, C, D) composite blocks ----
    tok_idx = jnp.repeat(jnp.arange(N), cfg.top_k)
    disp = jnp.zeros((G, E_loc, C, D), cd)
    disp = disp.at[v_idx, sub_idx, c_idx].set(
        xt[tok_idx].astype(cd), mode="drop")

    # ---- expert FFN (grouped matmul; TP over `tp_axis` on the hidden dim).
    # Takes any capacity slice (G, E_loc, Cc, D): tokens are independent
    # rows of the grouped matmul, so this doubles as the overlap engine's
    # per-chunk compute stage. ----
    def expert_ffn(recv, _chunk=0):
        Cc = recv.shape[2]
        xe = recv.transpose(1, 0, 2, 3).reshape(E_loc, G * Cc, D)
        h = silu(kops.expert_matmul(xe, w1.astype(cd))) \
            * kops.expert_matmul(xe, w3.astype(cd)) \
            if cfg.act == "swiglu" else \
            gelu(kops.expert_matmul(xe, w1.astype(cd)))
        ye = kops.expert_matmul(h, w2.astype(cd))      # partial over F shard
        if tp_axis is not None:
            ye = jax.lax.psum(ye, tp_axis)
        return ye.reshape(E_loc, G, Cc, D).transpose(1, 0, 2, 3)

    # ---- the paper's collective, through its resolved A2APlan: backend,
    # chunk count, and round orders were all fixed once at plan time
    # (tuning.choose_algorithm prices tuned|direct|factorized|overlap with
    # per-axis ICI/DCN links); here we only replay the chosen kernel. ----
    def a2a(blocks, reverse=False):
        if plan is None:
            return blocks
        flat = blocks.reshape(G, -1)
        out = plan.reverse(flat) if reverse else plan.forward(flat)
        return out.reshape(blocks.shape)

    if ragged_plan is not None:
        # Dropless (capacity_factor=None): the ragged Alltoallv moves the
        # (E_loc, C) expert-strided window of each destination rank as one
        # bucket of token rows; per-rank send counts (the real routed
        # assignments) drive the counts phase and the occupancy stat, and
        # the combine direction reuses the dispatch's recv counts.  C is
        # the worst case, so `keep` is identically true — no token drops.
        # Combine re-derives slot validity from this device's own routing
        # indices, so recv_counts feeds nothing the output depends on and
        # XLA dead-code-eliminates both counts exchanges here — the
        # counts phase costs nothing in this path; it exists for callers
        # that do consume recv counts (see RaggedA2APlan.forward).
        counts = jnp.zeros((G,), jnp.int32).at[v_idx].add(
            keep.astype(jnp.int32), mode="drop")
        rows = disp.reshape(G, E_loc * C, D)
        recv_rows, recv_counts = ragged_plan.forward(rows, counts)
        recv = recv_rows[:, :E_loc * C].reshape(G, E_loc, C, D)
        recv = checkpoint_name(recv, "moe_recv")
        ye = expert_ffn(recv)
        back_rows, _ = ragged_plan.reverse(
            ye.reshape(G, E_loc * C, D), recv_counts)
        back = back_rows[:, :E_loc * C].reshape(G, E_loc, C, D)
        back = checkpoint_name(back, "moe_back")
    elif plan is not None and plan.backend == "overlap":
        # dispatch-round / expert-FFN / combine-round pipelined per
        # capacity chunk: chunk c+1's rounds hide behind chunk c's FFN.
        # Each chunk's post-dispatch state keeps the "moe_recv" name so the
        # remat_policy="collectives" save list works unchanged.
        back = plan.overlap(
            disp,
            compute_fn=lambda chunk, c: expert_ffn(
                checkpoint_name(chunk, "moe_recv"), c),
            reverse=True, chunk_axis=2)
        back = checkpoint_name(back, "moe_back")
    else:
        recv = checkpoint_name(a2a(disp), "moe_recv")  # (G, E_loc, C, D)
        ye = expert_ffn(recv)
        # ---- reverse collective + combine ----
        back = checkpoint_name(a2a(ye, reverse=True), "moe_back")
    pad = jnp.zeros((G, E_loc, 1, D), cd)
    backp = jnp.concatenate([back, pad], axis=2)       # dropped -> zeros
    yk = backp[v_idx, sub_idx, c_idx]                  # (N*k, D)
    yk = yk.reshape(N, cfg.top_k, D)
    gates = (gate_vals * keep.reshape(N, cfg.top_k)).astype(jnp.float32)
    y = jnp.einsum("nkd,nk->nd", yk.astype(jnp.float32), gates)

    # ---- load-balance aux loss (GShard): E * sum_e f_e * P_e; = 1 when
    # perfectly balanced ----
    f_e = jnp.mean(onehot.astype(jnp.float32), axis=0)   # sums to 1
    p_e = jnp.mean(probs, axis=0)
    if reduce_axes:
        f_e = jax.lax.pmean(f_e, reduce_axes)
        p_e = jax.lax.pmean(p_e, reduce_axes)
    aux = E * jnp.sum(f_e * p_e)
    return y.reshape(B, S, D).astype(x.dtype), aux


def moe_block(p, x, cfg: ModelConfig, mesh=None,
              rules: ShardingRules | None = None):
    """x: (B, S, D) -> (y, aux_loss)."""
    axes, G, E_loc, R = _group_geometry(cfg, mesh)
    B, S, D = x.shape

    w1 = _virtual_weights(p["w1"], G)
    w3 = _virtual_weights(p["w3"], G)
    w2 = _virtual_weights(p["w2"], G)

    if mesh is None:
        C = _capacity(cfg, B * S, max(cfg.n_experts, G))
        return _moe_inner(x, p["router"], w1, w3, w2, cfg=cfg, axes=(),
                          G=G, E_loc=E_loc, R=R, C=C, tp_axis=None,
                          reduce_axes=())

    rules = rules or ShardingRules()
    w1 = constrain(w1, ("expert_virtual", None, None, "mlp"), mesh, rules)
    w3 = constrain(w3, ("expert_virtual", None, None, "mlp"), mesh, rules)
    w2 = constrain(w2, ("expert_virtual", None, "mlp", None), mesh, rules)

    x_spec = resolve_spec(x.shape, ("batch", None, None), mesh, rules)
    part = x_spec[0]
    batch_axes = () if part is None else \
        ((part,) if isinstance(part, str) else tuple(part))
    n_batch_shards = math.prod([mesh.shape[a] for a in batch_axes]) \
        if batch_axes else 1
    n_loc = (B // n_batch_shards) * S
    C = _capacity(cfg, n_loc, max(cfg.n_experts, G))
    tp_axis = "model" if "model" in mesh.shape and mesh.shape["model"] > 1 \
        else None
    reduce_axes = batch_axes

    wv_spec = resolve_spec(w1.shape, ("expert_virtual", None, None, "mlp"),
                           mesh, rules)
    w2_spec = resolve_spec(w2.shape, ("expert_virtual", None, "mlp", None),
                           mesh, rules)
    router_spec = P(None, None)

    # Dropless mode replaces the capacity-padded dense collective with the
    # ragged or sparse-neighborhood plan (density-chosen); otherwise the
    # dense A2APlan path is unchanged.
    if cfg.dropless:
        plan, ragged = None, moe_dropless_a2a_plan(cfg, mesh, axes, E_loc, C,
                                                   n_loc)
    else:
        plan, ragged = moe_a2a_plan(cfg, mesh, axes, E_loc, C), None
    inner = functools.partial(
        _moe_inner, cfg=cfg, axes=axes, G=G, E_loc=E_loc, R=R, C=C,
        tp_axis=tp_axis, reduce_axes=reduce_axes, plan=plan,
        ragged_plan=ragged)

    y, aux = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(x_spec, router_spec, wv_spec, wv_spec, w2_spec),
        out_specs=(x_spec, P()),
        check_vma=False,   # aux is value-replicated after pmean; see note
    )(x, p["router"], w1, w3, w2)
    return y, aux
