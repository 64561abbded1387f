"""Plain float32 reference of a dense decoder in the Mistral layout
(h2o-danube-1.8b, arXiv:2401.16818): token embedding, then per layer
RMSNorm, grouped-query attention with rotary positions and a sliding
window, RMSNorm and a SwiGLU MLP, each added to the residual; a final
RMSNorm and an untied LM head.

It is written from the published description and imports nothing of the
program.  Departures, each of which the program shares:

* rotary positions rotate interleaved pairs ``(0, 1), (2, 3), ...`` of a
  head, where the published code rotates the two halves; with weights
  drawn at random the two are the same function up to a fixed permutation
  of the query and key columns;
* the weights are drawn at random from the seed, ``normal(0,
  initializer_range)`` for every matrix and ones for the norms, as the
  published ``initializer_range`` says.

The weights are made here, in the tree layout the program takes, so that
the benchmark gives the program its inputs and the reference never reads
anything the program made.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes(c: dict):
    d, h, kv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    return d, h, kv, d // h, c["intermediate_size"], c["vocab_size"], \
        c["num_hidden_layers"]


def layout(c: dict) -> dict:
    """Shapes of the weight tree (the program's parameter layout)."""
    d, h, kv, hd, f, v, n = sizes(c)
    tree = {
        "embed": (v, d),
        "final_norm": {"g": (d,)},
        "blocks": {"pos0": {
            "norm1": {"g": (n, d)},
            "mixer": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                      "wv": (n, d, kv, hd), "wo": (n, h, hd, d)},
            "norm2": {"g": (n, d)},
            "ffn": {"w1": (n, d, f), "w3": (n, d, f), "w2": (n, f, d)},
        }},
    }
    if not c["tie_word_embeddings"]:
        tree["lm_head"] = (v, d)
    return tree


def make_weights(c: dict, key, dtype=jnp.bfloat16):
    """Every weight from ``key``; meant to run as one jitted call."""
    shapes = layout(c)
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))[0]
    out = []
    for i, (path, shape) in enumerate(paths):
        if path[-1].key == "g":
            out.append(jnp.ones(shape, dtype))
        else:
            out.append((jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32)
                        * c["initializer_range"]).astype(dtype))
    treedef = jax.tree.structure(shapes,
                                 is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _fp8(a):
    """Round to fp8 e4m3 with one scale per tensor (the control)."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     -1).reshape(x.shape)


def logits(c: dict, w, tokens, *, fp8: bool = False):
    """``tokens`` (n, S) -> logits (n, S, vocab), float32 at the highest
    matmul precision; with ``fp8`` every matmul operand is first rounded
    to fp8 e4m3 (the control)."""
    d, h, kv, hd, f, v, n_layers = sizes(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    window = c.get("sliding_window")
    q8 = _fp8 if fp8 else (lambda a: a)
    f32 = jnp.float32
    n, s = tokens.shape
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    x = w["embed"].astype(f32)[tokens]

    def layer(x, lw):
        a = _rms(x, lw["norm1"]["g"].astype(f32), eps)
        m = lw["mixer"]
        q = jnp.einsum("nsd,dhk->nhsk", q8(a), q8(m["wq"].astype(f32)))
        k = jnp.einsum("nsd,dhk->nhsk", q8(a), q8(m["wk"].astype(f32)))
        vv = jnp.einsum("nsd,dhk->nhsk", q8(a), q8(m["wv"].astype(f32)))
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(n, kv, h // kv, s, hd)
        sc = jnp.einsum("nkgqd,nksd->nkgqs", q, k) / math.sqrt(hd)
        sc = jnp.where(mask, sc, -jnp.inf)
        o = jnp.einsum("nkgqs,nksd->nkgqd", jax.nn.softmax(sc, -1), vv)
        o = o.reshape(n, h, s, hd)
        x = x + jnp.einsum("nhsk,hkd->nsd", q8(o), q8(m["wo"].astype(f32)))
        b = _rms(x, lw["norm2"]["g"].astype(f32), eps)
        p = lw["ffn"]
        g = jax.nn.silu(q8(b) @ q8(p["w1"].astype(f32))) \
            * (q8(b) @ q8(p["w3"].astype(f32)))
        return x + q8(g) @ q8(p["w2"].astype(f32)), None

    x, _ = jax.lax.scan(layer, x, w["blocks"]["pos0"])
    x = _rms(x, w["final_norm"]["g"].astype(f32), eps)
    head = w["lm_head"] if "lm_head" in w else w["embed"]
    return jnp.einsum("nsd,vd->nsv", q8(x), q8(head.astype(f32)))


def served_gaps(c: dict, w, fed, first, count, *, control: bool = False):
    """For each request ``i``, the tokens ``fed[i]`` (prompt then served
    tokens, zero-padded), the position ``first[i]`` whose logits chose the
    first served token, and ``count[i]`` served tokens: how far below the
    reference's best logit each served token's logit lies, (n, S), zero
    outside the served positions.  With ``control`` the token compared is
    the one that the fp8 reference puts first instead."""
    with jax.default_matmul_precision("highest"):
        ref = logits(c, w, fed)
        if control:
            pick = jnp.argmax(logits(c, w, fed, fp8=True), -1)
        else:
            pick = jnp.concatenate([fed[:, 1:], fed[:, :1]], 1)
    got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    gap = jnp.max(ref, -1) - got
    t = jnp.arange(fed.shape[1])[None, :]
    served = (t >= first[:, None]) & (t < (first + count)[:, None])
    return jnp.where(served, gap, 0.0)
