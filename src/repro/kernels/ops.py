"""Public jit'd kernel wrappers and implementation dispatch.

The model stack calls these entry points; each selects between the Pallas
kernel compiled for the TPU (``impl="pallas"``), the same kernel run by
the Pallas interpreter (``impl="pallas_interpret"``, for CPU tests only)
and the XLA reference path (``impl="xla"``).  No default selects the
interpreter.  The Pallas kernels compile for a TPU v5e
(``tests/test_tpu_compile.py``) and run there against ``ref.py``
(``chip_smoke.py``).

``attention(impl="pallas")`` is trainable: a ``jax.custom_vjp`` whose
forward and backward are both Pallas kernels
(``flash_attention_bwd.flash_attention_trainable``).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from . import ref as _ref
from .block_reorder import datatype_pack, datatype_unpack
from .flash_attention import flash_attention
from .moe_gmm import grouped_matmul

AttentionImpl = Literal["xla", "pallas", "pallas_interpret"]


def attention(q, k, v, *, causal=True, window=None, kv_offset=0,
              impl: AttentionImpl = "xla", block_q=128, block_k=128):
    """Multi-head attention with GQA/causal/sliding-window support.

    ``impl="pallas"`` uses the trainable flash kernel (custom_vjp with the
    Pallas backward — no (S, S) residuals in HBM)."""
    if impl == "xla":
        return _ref.ref_attention(q, k, v, causal=causal, window=window,
                                  kv_offset=kv_offset)
    from .flash_attention_bwd import flash_attention_trainable
    interpret = impl == "pallas_interpret"
    return flash_attention_trainable(q, k, v, causal=causal, window=window,
                                     block_q=block_q, block_k=block_k,
                                     kv_offset=kv_offset,
                                     interpret=interpret)


def expert_matmul(lhs, rhs, *, impl: AttentionImpl = "xla",
                  block_c=128, block_n=128, block_k=128):
    """(E, C, K) @ (E, K, N) grouped matmul."""
    if impl == "xla":
        return _ref.ref_gmm(lhs, rhs)
    return grouped_matmul(lhs, rhs, block_c=block_c, block_n=block_n,
                          block_k=block_k,
                          interpret=(impl == "pallas_interpret"))


def pack_round(x, dims, k, *, impl: AttentionImpl = "pallas"):
    """Round-k datatype pack (explicit-copy baseline path)."""
    if impl == "xla":
        from repro.core.simulator import round_datatype
        pos, extent = round_datatype(tuple(dims), k)
        return _ref.ref_block_reorder(x, pos, extent, dims[k])
    return datatype_pack(x, dims=tuple(dims), k=k,
                         interpret=(impl == "pallas_interpret"))


def unpack_round(y, dims, k, *, impl: AttentionImpl = "pallas"):
    if impl == "xla":
        from repro.core.simulator import round_datatype
        pos, extent = round_datatype(tuple(dims), k)
        return _ref.ref_block_unreorder(y, pos, extent, dims[k])
    return datatype_unpack(y, dims=tuple(dims), k=k,
                           interpret=(impl == "pallas_interpret"))
