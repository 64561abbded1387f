"""Published peaks of each chip, keyed by JAX's ``device_kind``, and the
work (operations and bytes) that each measured step needs, computed from
shapes alone.

Source of the TPU v5e numbers: Google Cloud documentation, "TPU v5e"
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 16 GB of HBM at
819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (ICI) per chip.

A device kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

import math
from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    flops_bf16: float        # FLOP/s
    hbm_bytes_per_s: float   # B/s
    hbm_bytes: float         # B
    ici_bytes_per_s: float   # B/s per chip
    source: str


_V5E = Peaks(flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
             ici_bytes_per_s=1600e9 / 8,
             source="Google Cloud documentation, 'TPU v5e'")

PEAKS = {
    "TPU v5 lite": _V5E,     # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


# ---------------------------------------------------------------------------
# the all-to-all exchange
# ---------------------------------------------------------------------------

def exchange_bytes_per_chip(p: int, block_bytes: int) -> int:
    """Bytes one chip sends (and receives) in a plain all-to-all of ``p``
    blocks of ``block_bytes``: every block but its own.  The same count
    for every backend: a schedule that forwards blocks through an
    intermediate chip does more than this, and that counts against it."""
    return (p - 1) * block_bytes


def exchange_least_s(p: int, block_bytes: int, peaks: Peaks) -> float:
    return exchange_bytes_per_chip(p, block_bytes) / peaks.ici_bytes_per_s


# ---------------------------------------------------------------------------
# a dense decoder (llama / mistral layout: GQA attention, SwiGLU MLP)
# ---------------------------------------------------------------------------

def decoder_weight_params(c: dict) -> int:
    """Parameters of the decoder: embedding, layers, final norm, head."""
    D, F, V, L = c["hidden_size"], c["intermediate_size"], \
        c["vocab_size"], c["num_hidden_layers"]
    hd = D // c["num_attention_heads"]
    attn = D * hd * (2 * c["num_attention_heads"]
                     + 2 * c["num_key_value_heads"])
    layer = attn + 3 * D * F + 2 * D
    head = 0 if c.get("tie_word_embeddings") else V * D
    return V * D + L * layer + D + head


def decoder_matmul_flops_per_token(c: dict) -> float:
    """Forward FLOPs of the weight matmuls for one token (2 per MAC); the
    embedding lookup is a gather and does no FLOPs."""
    D, F, V, L = c["hidden_size"], c["intermediate_size"], \
        c["vocab_size"], c["num_hidden_layers"]
    hd = D // c["num_attention_heads"]
    attn = D * hd * (2 * c["num_attention_heads"]
                     + 2 * c["num_key_value_heads"])
    return 2.0 * (L * (attn + 3 * D * F) + V * D)


def decoder_attn_flops(c: dict, context: int) -> float:
    """Forward FLOPs of one query token's attention over ``context``
    cached keys (scores and the weighted sum), summed over layers."""
    window = c.get("sliding_window") or context
    ctx = min(context, window)
    return 2.0 * 2 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * (c["hidden_size"] // c["num_attention_heads"]) * ctx


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """Bytes of keys and values one token leaves in the cache."""
    hd = c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * hd \
        * itemsize


def decode_tick_least_s(c: dict, contexts, peaks: Peaks,
                        itemsize: int = 2) -> float:
    """The least time one decode tick could take on this chip: each slot in
    ``contexts`` (the number of cached tokens it attends to, itself
    included) feeds one token.  The larger of its FLOPs over the peak and
    of the bytes it must read (every weight once, and the live KV rows)
    over the HBM bandwidth."""
    contexts = list(contexts)
    if not contexts:
        return 0.0
    flops = sum(decoder_matmul_flops_per_token(c)
                + decoder_attn_flops(c, n) for n in contexts)
    window = c.get("sliding_window") or max(contexts)
    kv = kv_bytes_per_token(c, itemsize) * sum(min(n, window)
                                               for n in contexts)
    weights = decoder_weight_params(c) * itemsize
    return max(flops / peaks.flops_bf16,
               (weights + kv) / peaks.hbm_bytes_per_s)


def train_flops_per_token(c: dict, seq: int) -> float:
    """Model FLOPs of one training token, forward and backward (3x the
    forward), without recomputation: weight matmuls plus causal attention
    over on average ``seq / 2`` keys."""
    window = c.get("sliding_window") or seq
    mean_ctx = sum(min(i + 1, window) for i in range(seq)) / seq
    fwd = decoder_matmul_flops_per_token(c) + 2.0 * 2 \
        * c["num_hidden_layers"] * c["num_attention_heads"] \
        * (c["hidden_size"] // c["num_attention_heads"]) * mean_ctx
    return 3.0 * fwd


def mfu(tokens_per_s: float, flops_per_token: float, chips: int,
        peaks: Peaks) -> float:
    """Model FLOP/s utilization, in %."""
    return 100.0 * tokens_per_s * flops_per_token \
        / (chips * peaks.flops_bf16)


def ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def moe_capacity(capacity_factor: float, top_k: int, tokens: int,
                 experts: int) -> int:
    """GShard capacity per expert and source chip, 8-aligned, clamped to
    the tokens present (as the MoE layer computes it)."""
    c = math.ceil(capacity_factor * top_k * tokens / experts)
    return min(max(8, ceil_div(c, 8) * 8), max(1, tokens))
