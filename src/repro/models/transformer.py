"""Transformer stack: superblock scan, unified Model API.

The repeating (mixer, ffn) *superblock* (``cfg.superblock``) is scanned
over ``cfg.n_superblocks`` with stacked parameters — HLO stays O(1) in
depth, remat wraps each superblock.  Heterogeneous stacks (jamba's
mamba/attn interleave with MoE-every-2, xLSTM's 7:1 mLSTM/sLSTM) are one
superblock of several positions; homogeneous stacks are a superblock of
length 1.

Modes:
  * ``forward``     — full-sequence (train / prefill), returns logits.
  * ``decode_step`` — one token with per-layer caches (KV / SSM states).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ffn as ffn_mod
from repro.models import mamba as mamba_mod
from repro.models import moe as moe_mod
from repro.models import spectral as spectral_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import (ParamSpec, init_params, rms_norm,
                                 layer_norm, softmax_cross_entropy,
                                 stack_specs)
from repro.parallel.sharding import ShardingRules, constrain
from .config import ModelConfig

ACT_SPEC = ("batch", None, "act_embed")


def remat_policy_of(cfg: ModelConfig):
    """Map cfg.remat_policy to a jax checkpoint policy."""
    if cfg.remat_policy == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "collectives":
        # save every checkpoint_name'd value; collectives are wrapped with
        # checkpoint_name at their call sites (sharding boundaries).
        return jax.checkpoint_policies.save_only_these_names(
            "act_gather", "moe_recv", "moe_back")
    raise ValueError(cfg.remat_policy)


def _norm_specs(cfg):
    if cfg.norm == "layernorm":
        return {"g": ParamSpec((cfg.d_model,), (None,), init="ones"),
                "b": ParamSpec((cfg.d_model,), (None,), init="zeros")}
    return {"g": ParamSpec((cfg.d_model,), (None,), init="ones")}


def _apply_norm(p, x, cfg):
    with jax.named_scope("norm"):
        if cfg.norm == "layernorm":
            return layer_norm(x, p["g"], p["b"])
        return rms_norm(x, p["g"])


def _mixer_specs(cfg, kind):
    return {"attn": attn.attn_specs, "mamba": mamba_mod.mamba_specs,
            "mlstm": xlstm_mod.mlstm_specs,
            "slstm": xlstm_mod.slstm_specs,
            "spectral": spectral_mod.spectral_specs}[kind](cfg)


def _ffn_specs(cfg, kind):
    if kind == "dense":
        return ffn_mod.ffn_specs(cfg)
    if kind == "moe":
        return moe_mod.moe_specs(cfg)
    return {}


def position_specs(cfg, mixer, ffn):
    out = {"norm1": _norm_specs(cfg), "mixer": _mixer_specs(cfg, mixer)}
    if ffn != "none":
        out["norm2"] = _norm_specs(cfg)
        out["ffn"] = _ffn_specs(cfg, ffn)
    return out


def superblock_specs(cfg: ModelConfig):
    return {f"pos{i}": position_specs(cfg, mixer, ffn)
            for i, (mixer, ffn) in enumerate(cfg.superblock)}


# ---------------------------------------------------------------------------
# Cache/state initialization (decode)
# ---------------------------------------------------------------------------

def _position_state(cfg: ModelConfig, mixer, batch, max_seq):
    if mixer == "attn":
        # Sliding-window attention needs only `window` KV slots (ring
        # buffer) — this is what makes long_500k decode O(window) for SWA.
        slots = min(max_seq, cfg.window) if cfg.window else max_seq
        cs = attn.CacheSpec(batch, cfg.n_kv_heads, slots, cfg.hd,
                            cfg.cdtype)
        return attn.init_cache(cs)
    D = cfg.d_model
    if mixer == "mamba":
        Ein = cfg.ssm_expand * D
        return {"ssm": jnp.zeros((batch, Ein, cfg.ssm_state), jnp.float32),
                "conv": jnp.zeros((batch, cfg.ssm_conv - 1, Ein),
                                  cfg.cdtype)}
    if mixer == "spectral":
        Ein = cfg.ssm_expand * D
        return {"ssm": jnp.zeros((batch, Ein, cfg.ssm_state), jnp.float32)}
    if mixer == "mlstm":
        Din = 2 * D
        H = cfg.n_heads
        hd = Din // H
        return {"C": jnp.zeros((batch, H, hd, hd), jnp.float32),
                "n": jnp.zeros((batch, H, hd), jnp.float32),
                "m": jnp.full((batch, H), -1e30, jnp.float32)}
    if mixer == "slstm":
        z = jnp.zeros((batch, D), jnp.float32)
        return {"c": z, "n": z + 1e-6, "m": jnp.full((batch, D), -1e30,
                                                     jnp.float32), "h": z}
    raise ValueError(mixer)


def init_layer_states(cfg: ModelConfig, batch: int, max_seq: int):
    """Stacked (n_superblocks, ...) state tree for decode."""
    per_sb = {f"pos{i}": _position_state(cfg, mixer, batch, max_seq)
              for i, (mixer, _) in enumerate(cfg.superblock)}
    n = cfg.n_superblocks
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), per_sb)


def _position_state_logical(cfg: ModelConfig, mixer):
    """Logical sharding axes mirroring ``_position_state`` (for dry-run
    abstract caches: sharded ShapeDtypeStructs, no allocation)."""
    if mixer == "attn":
        kv = ("batch", "kv_heads", "seq_sp", None)
        return {"k": kv, "v": kv, "slot_pos": ("batch", "seq_sp")}
    if mixer == "mamba":
        return {"ssm": ("batch", "mlp", None),
                "conv": ("batch", None, "mlp")}
    if mixer == "spectral":
        return {"ssm": ("batch", "mlp", None)}
    if mixer == "mlstm":
        return {"C": ("batch", "heads", None, None),
                "n": ("batch", "heads", None), "m": ("batch", "heads")}
    if mixer == "slstm":
        v = ("batch", None)
        return {"c": v, "n": v, "m": v, "h": v}
    raise ValueError(mixer)


def cache_logical_axes(cfg: ModelConfig):
    """Logical axes tree matching ``Model.init_caches`` output (layer
    states get a leading stacked superblock dim)."""
    per_sb = {f"pos{i}": _position_state_logical(cfg, mixer)
              for i, (mixer, _) in enumerate(cfg.superblock)}
    states = jax.tree.map(lambda ax: (None,) + tuple(ax), per_sb,
                          is_leaf=lambda x: isinstance(x, tuple))
    return {"states": states, "pos": ("batch",)}


# ---------------------------------------------------------------------------
# Superblock application
# ---------------------------------------------------------------------------

def _apply_position(pp, x, cfg, mixer, ffn, mesh, rules, positions,
                    state=None, decode=False):
    """One (mixer, ffn) position.  Returns (x, aux, new_state).  Each
    part runs under a ``jax.named_scope`` (``norm``, ``mixer.<kind>``,
    ``ffn`` or ``moe``) that names its ops in the compiled HLO."""
    h = _apply_norm(pp["norm1"], x, cfg)
    new_state = state
    with jax.named_scope(f"mixer.{mixer}"):
        if mixer == "attn":
            if decode:
                y, new_state = attn.decode_attention(pp["mixer"], h, state,
                                                     positions, cfg)
            else:
                y = attn.attention_block(
                    pp["mixer"], h, cfg, causal=True, positions=positions,
                    mesh=mesh, rules=rules)
        elif mixer == "mamba":
            y, new_state = mamba_mod.mamba_block(pp["mixer"], h, cfg,
                                                 state=state)
        elif mixer == "spectral":
            y, new_state = spectral_mod.spectral_block(pp["mixer"], h, cfg,
                                                       state=state)
        elif mixer == "mlstm":
            y, new_state = xlstm_mod.mlstm_block(pp["mixer"], h, cfg,
                                                 state=state)
        elif mixer == "slstm":
            y, new_state = xlstm_mod.slstm_block(pp["mixer"], h, cfg,
                                                 state=state)
        else:
            raise ValueError(mixer)
    x = x + y.astype(x.dtype)

    aux = jnp.zeros((), jnp.float32)
    if ffn != "none":
        h = _apply_norm(pp["norm2"], x, cfg)
        with jax.named_scope("moe" if ffn == "moe" else "ffn"):
            if ffn == "moe":
                y, aux = moe_mod.moe_block(pp["ffn"], h, cfg, mesh=mesh,
                                           rules=rules)
            else:
                y = ffn_mod.ffn_block(pp["ffn"], h, cfg)
        x = x + y.astype(x.dtype)
    x = constrain(x, ACT_SPEC, mesh, rules)
    return x, aux, new_state


def _apply_superblock(params_sb, x, cfg, mesh, rules, positions,
                      states_sb=None, decode=False):
    aux_total = jnp.zeros((), jnp.float32)
    new_states = {}
    for i, (mixer, ffn) in enumerate(cfg.superblock):
        st = states_sb[f"pos{i}"] if states_sb is not None else None
        x, aux, st2 = _apply_position(
            params_sb[f"pos{i}"], x, cfg, mixer, ffn, mesh, rules,
            positions, state=st, decode=decode)
        aux_total = aux_total + aux
        if st2 is not None:
            new_states[f"pos{i}"] = st2
    return x, aux_total, new_states


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclass
class Model:
    cfg: ModelConfig

    # ---- parameter specs ----
    def specs(self):
        cfg = self.cfg
        out = {
            "embed": ParamSpec((cfg.vocab, cfg.d_model),
                               ("vocab", "embed_fsdp"), init="embed",
                               scale=1.0),
            "blocks": stack_specs(superblock_specs(cfg), cfg.n_superblocks,
                                  None),
            "final_norm": _norm_specs(cfg),
        }
        if not cfg.tie_embeddings:
            out["lm_head"] = ParamSpec((cfg.vocab, cfg.d_model),
                                       ("vocab", "embed_fsdp"))
        if cfg.frontend is not None:
            out["frontend_proj"] = ParamSpec(
                (cfg.d_model, cfg.d_model), ("embed_fsdp", None))
        return out

    def init(self, key):
        return init_params(self.specs(), key, self.cfg.pdtype)

    # ---- embedding / head ----
    def embed(self, params, tokens):
        with jax.named_scope("embed"):
            e = jnp.take(params["embed"], tokens, axis=0)
            return e.astype(self.cfg.cdtype)

    def logits(self, params, x):
        w = params.get("lm_head", params["embed"])
        with jax.named_scope("lm_head"):
            return jnp.einsum("bsd,vd->bsv", x.astype(self.cfg.cdtype),
                              w.astype(self.cfg.cdtype),
                              preferred_element_type=jnp.float32)  # f32

    # ---- full-sequence forward (train / prefill) ----
    def forward(self, params, tokens, *, mesh=None, rules=None,
                frontend_embeds=None):
        cfg = self.cfg
        x = self.embed(params, tokens)
        if frontend_embeds is not None:
            fe = frontend_embeds.astype(cfg.cdtype)
            fe = fe @ params["frontend_proj"].astype(cfg.cdtype)
            x = jnp.concatenate([fe, x], axis=1)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = constrain(x, ACT_SPEC, mesh, rules)

        def body(carry, params_sb):
            x, aux = carry
            x, aux_sb, _ = _apply_superblock(params_sb, x, cfg, mesh, rules,
                                             positions)
            return (x, aux + aux_sb), None

        if cfg.remat:
            body = jax.checkpoint(body, policy=remat_policy_of(cfg))
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
        x = _apply_norm(params["final_norm"], x, cfg)
        if frontend_embeds is not None:
            x = x[:, frontend_embeds.shape[1]:]
        return self.logits(params, x), aux

    # ---- loss ----
    def loss(self, params, batch, *, mesh=None, rules=None):
        cfg = self.cfg
        logits, aux = self.forward(
            params, batch["tokens"], mesh=mesh, rules=rules,
            frontend_embeds=batch.get("frontend_embeds"))
        ce = softmax_cross_entropy(logits, batch["labels"], cfg.z_loss)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(batch["labels"], jnp.float32)
        loss = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        total = loss + cfg.router_aux_weight * aux   # aux == 0 if no MoE
        metrics = {"ce_loss": loss, "aux_loss": aux, "total_loss": total}
        return total, metrics

    # ---- decode ----
    def init_caches(self, batch: int, max_seq: int):
        return {"states": init_layer_states(self.cfg, batch, max_seq),
                "pos": jnp.zeros((batch,), jnp.int32)}

    def prefill(self, params, tokens, caches, *, mesh=None, rules=None,
                frontend_embeds=None):
        """Sequential prefill through decode_step (correct though not the
        fast path; full-seq prefill uses ``forward``)."""
        def step(carry, t):
            caches, _ = carry
            logits, caches = self.decode_step(params, tokens[:, t:t + 1],
                                              caches, mesh=mesh, rules=rules)
            return (caches, logits), None
        (caches, logits), _ = jax.lax.scan(
            step, (caches, jnp.zeros((tokens.shape[0], 1, self.cfg.vocab),
                                     jnp.float32)),
            jnp.arange(tokens.shape[1]))
        return logits, caches

    def decode_step(self, params, tokens_t, caches, *, mesh=None,
                    rules=None):
        """tokens_t: (B, 1). Returns (logits (B,1,V), caches)."""
        cfg = self.cfg
        x = self.embed(params, tokens_t)
        x = constrain(x, ("batch", None, None), mesh, rules)
        pos = caches["pos"]

        def body(carry, xs):
            x = carry
            params_sb, states_sb = xs
            x, _, new_states = _apply_superblock(
                params_sb, x, cfg, mesh, rules, pos, states_sb=states_sb,
                decode=True)
            return x, new_states

        x, new_states = jax.lax.scan(
            body, x, (params["blocks"], caches["states"]))
        x = _apply_norm(params["final_norm"], x, cfg)
        logits = self.logits(params, x)
        return logits, {"states": new_states, "pos": pos + 1}
