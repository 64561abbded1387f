"""Flash attention backward Pallas kernels + custom_vjp wrapper.

Identified in EXPERIMENTS §Perf (grok train) as the next memory lever: the
XLA attention path materializes the (B, H, S, S) probability matrix in the
residuals; the flash backward recomputes tiles from (q, k, v, lse, delta)
and never touches an S x S buffer in HBM.

Standard FlashAttention-2 backward:

    p    = exp(q k^T * scale - lse)            (recomputed per tile)
    dv  += p^T dO
    dp   = dO v^T
    ds   = p * (dp - delta) * scale            (delta = rowsum(dO * O))
    dq  += ds k
    dk  += ds^T q

Two kernels: dq (grid over q blocks, kv innermost, accumulate in VMEM) and
dkv (grid over kv blocks, q innermost).  GQA: dk/dv are computed per
*query* head and group-summed outside (an (B, Hq, Skv, hd) -> (B, Hkv, ..)
reduction the compiler fuses), keeping the kernels race-free.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .flash_attention import NEG_INF, _pick_block, flash_attention


# ---------------------------------------------------------------------------
# forward returning residuals (lse)
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, window, block_q, block_k, kv_len,
                kv_offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + kv_offset
    cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = cols < kv_len
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    s = jnp.where(mask, s, NEG_INF)

    # running max / sum are (bq, 1) columns, the layout of the lse output
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha \
        + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finish():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, ...] = (acc_ref[...] / safe).astype(o_ref.dtype)
        lse_ref[0, 0, ...] = m_ref[...] + jnp.log(safe)


def flash_attention_fwd(q, k, v, *, causal=True, window=None, scale=None,
                        block_q=128, block_k=128, kv_offset=0,
                        interpret=False):
    """Returns (out, lse); lse: (B, Hq, Sq, 1) f32.

    The trailing size-1 axis keeps the last two dimensions of every lse
    block (``(bq, 1)``) legal for the TPU's (8, 128) tiling rule."""
    B, Hq, Sq, Dh = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Skv, block_k)
    grid = (B, Hq, Sq // bq, Skv // bk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, kv_len=Skv, kv_offset=kv_offset)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, iq, ik, g=group: (b, h // g, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, Dh), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, Dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_tile(q, k, v, do, lse, delta, rows, cols, *, scale, causal, window,
              kv_len):
    """Recompute p and ds for one (bq, bk) tile; returns (p, ds) f32.

    ``lse`` and ``delta`` are (bq, 1) columns."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    mask = cols < kv_len
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, window, block_q, block_k, kv_len,
               kv_offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + kv_offset
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    _, ds = _bwd_tile(q_ref[0, 0].astype(jnp.float32),
                      k_ref[0, 0].astype(jnp.float32),
                      v_ref[0, 0].astype(jnp.float32),
                      do_ref[0, 0].astype(jnp.float32),
                      lse_ref[0, 0], delta_ref[0, 0], rows, cols,
                      scale=scale, causal=causal, window=window,
                      kv_len=kv_len)
    acc_ref[...] += jnp.dot(ds, k_ref[0, 0].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0, 0, ...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, window,
                block_q, block_k, kv_len, kv_offset):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    rows = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + kv_offset
    cols = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    p, ds = _bwd_tile(q, k_ref[0, 0].astype(jnp.float32),
                      v_ref[0, 0].astype(jnp.float32), do,
                      lse_ref[0, 0], delta_ref[0, 0], rows, cols,
                      scale=scale, causal=causal, window=window,
                      kv_len=kv_len)
    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(iq == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[0, 0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0, ...] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, window=None,
                        scale=None, block_q=128, block_k=128, kv_offset=0,
                        interpret=False):
    B, Hq, Sq, Dh = q.shape
    _, Hkv, Skv, _ = k.shape
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(Dh)
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Skv, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                # (B, Hq, Sq, 1)

    common = dict(scale=scale, causal=causal, window=window, block_q=bq,
                  block_k=bk, kv_len=Skv, kv_offset=kv_offset)
    q_spec = pl.BlockSpec((1, 1, bq, Dh), lambda b, h, i, j: (b, h, i, 0))
    qrow_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, Dh),
                           lambda b, h, i, j, g=group: (b, h // g, j, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(B, Hq, Sq // bq, Skv // bk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, qrow_spec, qrow_spec],
        out_specs=pl.BlockSpec((1, 1, bq, Dh),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, Dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, Dh), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv per *query* head (race-free); group-sum to KV heads after.
    q_spec2 = pl.BlockSpec((1, 1, bq, Dh), lambda b, h, j, i: (b, h, i, 0))
    qrow2 = pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bk, Dh),
                            lambda b, h, j, i, g=group: (b, h // g, j, 0))
    okv_spec = pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j, i: (b, h, j, 0))
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(B, Hq, Skv // bk, Sq // bq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, qrow2, qrow2],
        out_specs=[okv_spec, okv_spec],
        out_shape=[jax.ShapeDtypeStruct((B, Hq, Skv, Dh), k.dtype),
                   jax.ShapeDtypeStruct((B, Hq, Skv, Dh), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, Dh), jnp.float32),
                        pltpu.VMEM((bk, Dh), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    dk = dk_h.reshape(B, Hkv, group, Skv, Dh).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, group, Skv, Dh).sum(axis=2).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper — the trainable flash attention
# ---------------------------------------------------------------------------

@functools.partial(
    jax.custom_vjp,
    nondiff_argnames=("causal", "window", "scale", "block_q", "block_k",
                      "kv_offset", "interpret"))
def flash_attention_trainable(q, k, v, causal=True, window=None, scale=None,
                              block_q=128, block_k=128, kv_offset=0,
                              interpret=False):
    return flash_attention(q, k, v, causal=causal, window=window,
                           scale=scale, block_q=block_q, block_k=block_k,
                           kv_offset=kv_offset, interpret=interpret)


def _fa_fwd(q, k, v, causal, window, scale, block_q, block_k, kv_offset,
            interpret):
    out, lse = flash_attention_fwd(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, kv_offset=kv_offset,
        interpret=interpret)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, scale, block_q, block_k, kv_offset, interpret,
            res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(
        q, k, v, out, lse, do, causal=causal, window=window, scale=scale,
        block_q=block_q, block_k=block_k, kv_offset=kv_offset,
        interpret=interpret)
    return dq, dk, dv


flash_attention_trainable.defvjp(_fa_fwd, _fa_bwd)
