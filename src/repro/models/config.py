"""Unified model configuration covering all assigned architecture families."""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field

import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                       # dense|moe|hybrid|ssm|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int                         # 0 => no separate FFN (xLSTM)
    vocab: int
    head_dim: int | None = None       # default d_model // n_heads

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 2
    # Expert capacity factor (GShard-style), or None for *dropless* MoE:
    # dispatch/combine route through the ragged Alltoallv plan
    # (core.plan.plan_ragged_all_to_all) with the per-expert buffer sized
    # to the worst case, so no token is ever dropped and the padding
    # waste is reported as the plan's bucket occupancy instead of being
    # silently shipped as capacity slack.
    capacity_factor: float | None = 1.25
    router_aux_weight: float = 0.01
    moe_every: int = 1                # MoE FFN every k-th layer (jamba: 2)

    # --- attention ---
    window: int | None = None         # sliding-window size (SWA)
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    attention_impl: str = "xla"       # xla | pallas | pallas_interpret

    # --- layer mixer pattern (repeating):
    #     attn | mamba | mlstm | slstm | spectral ---
    block_pattern: tuple[str, ...] = ("attn",)

    # --- ssm (mamba) ---
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    # Substitute every recurrent mixer (mamba/mlstm/slstm) in
    # block_pattern with the spectral long-convolution layer
    # (models.spectral): same diagonal state-space family, but the
    # full-sequence pass is an FFT causal conv (O(S log S), and
    # sequence-parallel via workloads.fft.PencilFFT) instead of a
    # sequential scan; decode keeps the O(1)-per-token recurrence.
    spectral_long_conv: bool = False

    # --- xlstm: chunkwise-parallel mLSTM chunk length; 0 = per-step
    # recurrence (paper-faithful baseline).  L>0 cuts matrix-memory HBM
    # traffic by ~L (EXPERIMENTS §Perf hillclimb) ---
    xlstm_chunk: int = 0
    # remat each recurrent timestep/chunk body: the bwd pass recomputes
    # step internals from the carried state instead of saving ~17 stacked
    # per-step residual buffers (EXPERIMENTS §Perf hillclimb)
    recurrent_step_remat: bool = False

    # --- frontends / enc-dec ---
    frontend: str | None = None       # vit_stub | audio_stub
    n_frontend_tokens: int = 0
    encoder_layers: int = 0           # >0 => encoder-decoder (whisper)

    # --- numerics ---
    norm: str = "rmsnorm"             # rmsnorm | layernorm
    act: str = "swiglu"               # swiglu | gelu
    tie_embeddings: bool = True
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    remat: bool = True
    # remat policy: what survives the forward pass of each superblock.
    #   "nothing"  — recompute everything in bwd (min memory, max flops
    #                AND re-runs fwd collectives — the paper-baseline)
    #   "dots"     — save dot outputs w/o batch dims (skips most
    #                recompute of matmuls; moderate memory)
    #   "collectives" — save collective results by name (avoids re-running
    #                all-gathers in bwd; the collective-term optimization)
    remat_policy: str = "nothing"
    z_loss: float = 1e-4

    # --- parallelism hints ---
    use_ulysses: bool = False         # Ulysses SP for attention
    expert_axes: tuple[str, ...] = ("data",)   # EP mesh axes (fastest first)
    a2a_variant: str = "natural"      # factorized A2A variant for EP/SP
    # tuned | autotune | factorized | direct | overlap (pipelined: a
    # deprecated alias of factorized)
    # "overlap" pipelines dispatch-round / expert-FFN / combine-round per
    # payload chunk (core.overlap); "tuned" picks backend AND chunk count
    # from the alpha-beta model (tuning.choose_algorithm); "autotune"
    # replays the measured winner from the persistent tuning DB
    # (core.autotune) and falls back to "tuned" semantics on a DB miss —
    # it never measures inside a model step.  These three knobs
    # parameterize A2APlan construction (core.plan.plan_all_to_all)
    # in one place per consumer — moe.moe_a2a_plan and ulysses — and are
    # resolved once per (devices, axes, shape, dtype) plan key; nothing
    # dispatches on these strings at call time.
    a2a_backend: str = "tuned"
    a2a_chunks: int = 0               # payload chunks; 0 = cost-model auto

    def __post_init__(self):
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be divisible by n_kv_heads")
        if self.n_layers % len(self.block_pattern):
            raise ValueError("n_layers must divide into block_pattern")
        # validate against the plan layer's own backend list so the two
        # can never drift (lazy import: keep config importable without
        # pulling the collective stack in until it's needed)
        from repro.core.plan import BACKENDS
        if self.a2a_backend not in BACKENDS:
            raise ValueError(f"unknown a2a_backend {self.a2a_backend!r}; "
                             f"expected one of {BACKENDS}")

    @property
    def dropless(self) -> bool:
        """Dropless MoE: no capacity factor, ragged dispatch/combine."""
        return self.capacity_factor is None

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def pdtype(self):
        return DTYPES[self.param_dtype]

    @property
    def cdtype(self):
        return DTYPES[self.compute_dtype]

    @property
    def superblock(self) -> tuple[tuple[str, str], ...]:
        """Repeating (mixer, ffn) plan; scan iterates over superblocks."""
        period = len(self.block_pattern)
        if self.moe_every > 1:
            period = math.lcm(period, self.moe_every)
        plan = []
        for i in range(period):
            mixer = self.block_pattern[i % len(self.block_pattern)]
            if self.spectral_long_conv and mixer in ("mamba", "mlstm",
                                                     "slstm"):
                mixer = "spectral"
            if self.d_ff == 0:
                ffn = "none"
            elif self.n_experts and (self.moe_every <= 1
                                     or i % self.moe_every == 1):
                ffn = "moe"
            else:
                ffn = "dense"
            plan.append((mixer, ffn))
        return tuple(plan)

    @property
    def n_superblocks(self) -> int:
        n = len(self.superblock)
        assert self.n_layers % n == 0
        return self.n_layers // n

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    # ---- parameter count (for MODEL_FLOPS = 6*N*D) ----
    def param_count_estimate(self, active_only: bool = False) -> int:
        D, F, V = self.d_model, self.d_ff, self.vocab
        hd, Hq, Hkv = self.hd, self.n_heads, self.n_kv_heads
        n_attn = 0
        n_mixer_other = 0
        n_ffn_dense = 0
        n_ffn_moe = 0
        attn_p = D * hd * Hq * 2 + D * hd * Hkv * 2     # q,o + k,v
        Ein = self.ssm_expand * D
        mamba_p = D * Ein * 2 + Ein * self.ssm_conv + \
            Ein * (self.ssm_state * 2 + 1) + Ein * D + Ein * self.ssm_state
        mlstm_p = D * (2 * D) * 2 + (2 * D) * 3 * (2 * D) // 4 + 2 * D * D
        slstm_p = D * D * 4 + D * 4 * D // 4
        spectral_p = D * 2 * Ein + Ein * (3 * self.ssm_state + 2) + Ein * D
        ffn_dense = 3 * D * F if self.act == "swiglu" else 2 * D * F
        per_expert = 3 * D * F if self.act == "swiglu" else 2 * D * F
        for i in range(self.n_layers):
            mixer, ffn = self.superblock[i % len(self.superblock)]
            if mixer == "attn":
                n_attn += 1
            elif mixer == "mamba":
                n_mixer_other += mamba_p
            elif mixer == "mlstm":
                n_mixer_other += mlstm_p
            elif mixer == "slstm":
                n_mixer_other += slstm_p
            elif mixer == "spectral":
                n_mixer_other += spectral_p
            if ffn == "dense":
                n_ffn_dense += 1
            elif ffn == "moe":
                n_ffn_moe += 1
        total = n_attn * attn_p + n_mixer_other
        total += n_ffn_dense * ffn_dense
        k_active = min(self.top_k, max(1, self.n_experts))
        experts_counted = k_active if active_only else self.n_experts
        total += n_ffn_moe * (per_expert * experts_counted + D * self.n_experts)
        total += V * D * (1 if self.tie_embeddings else 2)
        if self.encoder_layers:
            total += self.encoder_layers * (attn_p + ffn_dense)
        return total
