"""Model-level invariants: forward == decode path, recurrent scan ==
incremental state, MoE routing properties, ring-buffer windowed cache."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _property import given, settings, st

from repro.models import ModelConfig, build_model
from repro.models.common import init_params
from repro.models import mamba as mamba_mod
from repro.models import xlstm as xlstm_mod
from repro.models.moe import moe_block, moe_specs

KEY = jax.random.PRNGKey(0)
BASE = dict(n_kv_heads=2, vocab=97, param_dtype="float32",
            compute_dtype="float32")


def _cfg(name, **kw):
    return ModelConfig(name=name, family="x", n_layers=kw.pop("n_layers", 2),
                       d_model=32, n_heads=4,
                       d_ff=kw.pop("d_ff", 64), **BASE, **kw)


class TestForwardDecodeConsistency:
    """The KV-cache/state decode path must reproduce full-seq forward."""

    @pytest.mark.parametrize("name,kw", [
        ("dense", {}),
        ("swa", {"window": 5}),
        ("moe", {"n_experts": 4, "capacity_factor": 8.0}),
        ("hybrid", {"n_experts": 4, "capacity_factor": 8.0,
                    "moe_every": 2, "block_pattern": ("mamba", "attn")}),
        ("xlstm", {"d_ff": 0, "block_pattern": ("mlstm", "slstm")}),
    ])
    def test_forward_equals_decode(self, name, kw):
        cfg = _cfg(name, **kw)
        m = build_model(cfg)
        p = init_params(m.specs(), KEY, cfg.pdtype)
        B, S = 2, 10
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab)
        logits_full, _ = m.forward(p, toks)
        caches = m.init_caches(B, 16)
        outs = []
        for t in range(S):
            lg, caches = m.decode_step(p, toks[:, t:t + 1], caches)
            outs.append(lg)
        logits_dec = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.array(logits_full),
                                   np.array(logits_dec),
                                   rtol=5e-3, atol=5e-3)

    def test_ring_buffer_smaller_than_context(self):
        # window=5 cache has only 5 slots; decoding 10 tokens must still
        # match the full forward (ring overwrite correctness).
        cfg = _cfg("swa", window=5)
        m = build_model(cfg)
        p = init_params(m.specs(), KEY, cfg.pdtype)
        caches = m.init_caches(2, 16)
        W = caches["states"]["pos0"]["k"].shape[3]
        assert W == 5  # min(max_seq, window)


class TestRecurrentBlocks:
    @pytest.mark.parametrize("mod,specs,block", [
        (mamba_mod, mamba_mod.mamba_specs, mamba_mod.mamba_block),
        (xlstm_mod, xlstm_mod.mlstm_specs, xlstm_mod.mlstm_block),
        (xlstm_mod, xlstm_mod.slstm_specs, xlstm_mod.slstm_block),
    ])
    def test_scan_equals_incremental(self, mod, specs, block):
        cfg = _cfg("r", ssm_state=8)
        p = init_params(specs(cfg), KEY, jnp.float32)
        x = jax.random.normal(KEY, (2, 12, 32))
        y_full, _ = block(p, x, cfg)
        state, outs = None, []
        for t in range(12):
            yt, state = block(p, x[:, t:t + 1], cfg, state=state)
            outs.append(yt)
        np.testing.assert_allclose(np.array(y_full),
                                   np.array(jnp.concatenate(outs, 1)),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("B,S,L", [(2, 32, 8), (1, 64, 16),
                                       (2, 48, 12)])
    def test_chunked_mlstm_equals_per_step(self, B, S, L):
        cfg0 = _cfg("m", d_ff=0, block_pattern=("mlstm",))
        cfgc = cfg0.replace(xlstm_chunk=L)
        p = init_params(xlstm_mod.mlstm_specs(cfg0), KEY, jnp.float32)
        x = jax.random.normal(KEY, (B, S, 32))
        y0, s0 = xlstm_mod.mlstm_block(p, x, cfg0)
        y1, s1 = xlstm_mod.mlstm_block(p, x, cfgc)
        np.testing.assert_allclose(np.array(y0), np.array(y1),
                                   rtol=3e-4, atol=3e-4)
        for k in ("C", "n", "m"):
            np.testing.assert_allclose(np.array(s0[k]), np.array(s1[k]),
                                       rtol=3e-4, atol=3e-4)

    def test_chunked_mlstm_with_carried_state(self):
        cfg0 = _cfg("m", d_ff=0, block_pattern=("mlstm",))
        cfgc = cfg0.replace(xlstm_chunk=8)
        p = init_params(xlstm_mod.mlstm_specs(cfg0), KEY, jnp.float32)
        x = jax.random.normal(KEY, (2, 48, 32))
        _, st = xlstm_mod.mlstm_block(p, x[:, :16], cfg0)
        y0, _ = xlstm_mod.mlstm_block(p, x[:, 16:], cfg0, state=st)
        y1, _ = xlstm_mod.mlstm_block(p, x[:, 16:], cfgc, state=st)
        np.testing.assert_allclose(np.array(y0), np.array(y1),
                                   rtol=3e-4, atol=3e-4)

    def test_state_sizes_constant_in_seq(self):
        # sub-quadratic property: state size independent of context length
        cfg = _cfg("r", ssm_state=8)
        p = init_params(mamba_mod.mamba_specs(cfg), KEY, jnp.float32)
        _, s1 = mamba_mod.mamba_block(p, jnp.zeros((2, 4, 32)), cfg)
        _, s2 = mamba_mod.mamba_block(p, jnp.zeros((2, 64, 32)), cfg)
        assert jax.tree.map(jnp.shape, s1) == jax.tree.map(jnp.shape, s2)


class TestMoE:
    def test_capacity_drops_are_masked(self):
        # absurdly low capacity: output must stay finite (dropped tokens
        # contribute zero, not garbage)
        cfg = _cfg("moe", n_experts=4, capacity_factor=0.05)
        p = init_params(moe_specs(cfg), KEY, jnp.float32)
        x = jax.random.normal(KEY, (2, 16, 32))
        y, aux = moe_block(p, x, cfg, mesh=None)
        assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(aux))

    def test_aux_loss_balanced_near_one(self):
        # uniform router (zero weights) => perfectly balanced aux ~= 1
        cfg = _cfg("moe", n_experts=4, capacity_factor=4.0)
        p = init_params(moe_specs(cfg), KEY, jnp.float32)
        p["router"] = jnp.zeros_like(p["router"])
        x = jax.random.normal(KEY, (2, 64, 32))
        _, aux = moe_block(p, x, cfg, mesh=None)
        assert abs(float(aux) - 1.0) < 0.05

    @given(st.integers(2, 8), st.sampled_from([1, 2]))
    @settings(max_examples=8, deadline=None)
    def test_gates_route_topk(self, E, k):
        cfg = _cfg("moe", n_experts=E, top_k=k, capacity_factor=8.0)
        p = init_params(moe_specs(cfg), KEY, jnp.float32)
        x = jax.random.normal(KEY, (1, 8, 32))
        y, _ = moe_block(p, x, cfg, mesh=None)
        assert y.shape == x.shape and bool(jnp.isfinite(y).all())

    def test_capacity_clamped_to_routed_tokens(self):
        # the boundary: a tiny batch must never pad the capacity past the
        # routed-token count, whatever the capacity factor says
        from repro.models.moe import _capacity
        cfg = _cfg("moe", n_experts=4, top_k=1, capacity_factor=8.0)
        assert _capacity(cfg, 2, 4) == 2          # was 8 (8-aligned floor)
        assert _capacity(cfg, 1, 4) == 1
        cfg2 = _cfg("moe", n_experts=4, top_k=2, capacity_factor=8.0)
        # per-expert worst case is n_tokens (top_k experts are distinct)
        assert _capacity(cfg2, 3, 4) == 3
        assert _capacity(cfg2, 100, 4) == 100     # clamp binds: 8.0*2*100/4
        cfg3 = _cfg("moe", n_experts=4, top_k=2, capacity_factor=0.25)
        assert _capacity(cfg3, 100, 4) == 16      # unclamped regime: 8-align

    def test_dropless_capacity_is_worst_case(self):
        from repro.models.moe import _capacity
        cfg = _cfg("moe", n_experts=4, top_k=2, capacity_factor=None)
        assert cfg.dropless
        assert _capacity(cfg, 16, 4) == 16
        assert _capacity(cfg, 2, 4) == 2

    def test_dropless_equals_high_capacity_locally(self):
        # capacity_factor=None (dropless) must reproduce the capacity path
        # whenever the capacity path would not have dropped
        cfg_cap = _cfg("moe", n_experts=4, capacity_factor=8.0)
        cfg_drop = _cfg("moe", n_experts=4, capacity_factor=None)
        p = init_params(moe_specs(cfg_cap), KEY, jnp.float32)
        x = jax.random.normal(KEY, (2, 16, 32))
        y_cap, aux_cap = moe_block(p, x, cfg_cap, mesh=None)
        y_drop, aux_drop = moe_block(p, x, cfg_drop, mesh=None)
        np.testing.assert_allclose(np.array(y_cap), np.array(y_drop),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(float(aux_cap), float(aux_drop),
                                   rtol=1e-6)

    def test_dropless_keeps_tokens_the_capacity_path_drops(self):
        # skew the router so one expert overflows a tight capacity: the
        # capacity path drops (some gate mass lost), dropless must not
        cfg_tight = _cfg("moe", n_experts=4, top_k=1, capacity_factor=0.3)
        cfg_drop = _cfg("moe", n_experts=4, top_k=1, capacity_factor=None)
        p = init_params(moe_specs(cfg_tight), KEY, jnp.float32)
        p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(10.0)
        x = jax.random.normal(KEY, (2, 32, 32))
        y_tight, _ = moe_block(p, x, cfg_tight, mesh=None)
        y_drop, _ = moe_block(p, x, cfg_drop, mesh=None)
        # dropped tokens contribute zero output rows in the tight path
        zero_rows_tight = int(jnp.sum(jnp.all(y_tight == 0, axis=-1)))
        zero_rows_drop = int(jnp.sum(jnp.all(y_drop == 0, axis=-1)))
        assert zero_rows_tight > 0 and zero_rows_drop == 0


class TestRematPolicies:
    @pytest.mark.parametrize("policy", ["nothing", "dots", "collectives"])
    def test_policies_same_loss(self, policy):
        cfg = _cfg("dense", remat=True).replace(remat_policy=policy)
        m = build_model(cfg)
        p = init_params(m.specs(), KEY, cfg.pdtype)
        toks = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
        batch = {"tokens": toks, "labels": toks}
        loss, _ = m.loss(p, batch)
        g = jax.grad(lambda p: m.loss(p, batch)[0])(p)
        assert bool(jnp.isfinite(loss))
        assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(g))


class TestSpectral:
    """The spectral long-conv mixer (models.spectral): the FFT conv path
    must equal the LTI recurrence exactly (same discretized SSM), the
    prefill state must hand off into decode, and the mixer must slot into
    the model via spectral_long_conv."""

    def _params(self, cfg):
        from repro.models import spectral as spectral_mod
        return init_params(spectral_mod.spectral_specs(cfg), KEY,
                           jnp.float32)

    def test_conv_equals_recurrence(self):
        from repro.models import spectral as spectral_mod
        cfg = _cfg("spec", ssm_state=8)
        p = self._params(cfg)
        x = jax.random.normal(KEY, (2, 12, 32))
        y_conv, st_conv = spectral_mod.spectral_block(p, x, cfg)
        Ein = cfg.ssm_expand * cfg.d_model
        zero = {"ssm": jnp.zeros((2, Ein, cfg.ssm_state), jnp.float32)}
        y_rec, st_rec = spectral_mod.spectral_block(p, x, cfg, state=zero)
        np.testing.assert_allclose(np.array(y_conv), np.array(y_rec),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.array(st_conv["ssm"]),
                                   np.array(st_rec["ssm"]),
                                   rtol=2e-4, atol=2e-4)

    def test_prefill_state_hands_off_to_decode(self):
        from repro.models import spectral as spectral_mod
        cfg = _cfg("spec", ssm_state=8)
        p = self._params(cfg)
        x = jax.random.normal(KEY, (2, 16, 32))
        y_full, _ = spectral_mod.spectral_block(p, x, cfg)
        _, st = spectral_mod.spectral_block(p, x[:, :10], cfg)
        outs = []
        for t in range(10, 16):
            yt, st = spectral_mod.spectral_block(p, x[:, t:t + 1], cfg,
                                                 state=st)
            outs.append(yt)
        np.testing.assert_allclose(np.array(y_full[:, 10:]),
                                   np.array(jnp.concatenate(outs, 1)),
                                   rtol=2e-4, atol=2e-4)

    def test_gradients_flow(self):
        from repro.models import spectral as spectral_mod
        cfg = _cfg("spec", ssm_state=8)
        p = self._params(cfg)
        x = jax.random.normal(KEY, (2, 8, 32))

        def loss(p):
            y, _ = spectral_mod.spectral_block(p, x, cfg)
            return jnp.sum(y ** 2)

        g = jax.grad(loss)(p)
        for name, gv in g.items():
            assert bool(jnp.any(gv != 0)), f"zero grad for {name}"
            assert bool(jnp.isfinite(gv).all()), f"nonfinite grad {name}"

    def test_model_forward_equals_decode(self):
        # spectral_long_conv substitutes the mamba mixer; full-seq
        # forward must match the incremental decode path end to end.
        cfg = _cfg("spec", ssm_state=8, d_ff=0,
                   block_pattern=("mamba",), spectral_long_conv=True)
        assert cfg.superblock == (("spectral", "none"),)
        m = build_model(cfg)
        p = init_params(m.specs(), KEY, cfg.pdtype)
        B, S = 2, 10
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab)
        logits_full, _ = m.forward(p, toks)
        caches = m.init_caches(B, 16)
        outs = []
        for t in range(S):
            lg, caches = m.decode_step(p, toks[:, t:t + 1], caches)
            outs.append(lg)
        np.testing.assert_allclose(np.array(logits_full),
                                   np.array(jnp.concatenate(outs, 1)),
                                   rtol=5e-3, atol=5e-3)

    def test_param_count_estimate_covers_spectral(self):
        cfg = _cfg("spec", ssm_state=8, d_ff=0,
                   block_pattern=("mamba",), spectral_long_conv=True)
        n = cfg.param_count_estimate()
        D, Ein = cfg.d_model, cfg.ssm_expand * cfg.d_model
        per_layer = D * 2 * Ein + Ein * (3 * cfg.ssm_state + 2) + Ein * D
        assert n >= cfg.n_layers * per_layer


class TestLayerScopes:
    """Each model layer runs under a ``jax.named_scope`` that names its
    ops in the compiled HLO, and the scopes change nothing else."""

    @staticmethod
    def _decode_hlo(cfg):
        m = build_model(cfg)
        p = jax.eval_shape(m.init, KEY)
        caches = jax.eval_shape(lambda: m.init_caches(2, 16))
        toks = jax.ShapeDtypeStruct((2, 1), jnp.int32)
        step = jax.jit(lambda p, t, c: m.decode_step(p, t, c))
        return step.lower(p, toks, caches).compile().as_text()

    def test_decode_step_hlo_carries_layer_scopes(self):
        import re
        text = self._decode_hlo(_cfg("dense"))
        paths = set(re.findall(r'op_name="([^"]*)"', text))
        for scope in ("embed", "norm", "mixer.attn", "ffn", "lm_head"):
            assert any(f"/{scope}/" in p for p in paths), scope

    def test_scopes_leave_the_program_unchanged(self, monkeypatch):
        import contextlib
        from repro.core.hlo_inspect import program_structure
        cfg = _cfg("dense")
        scoped = self._decode_hlo(cfg)
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = self._decode_hlo(cfg)
        assert "mixer.attn" in scoped and "mixer.attn" not in bare
        assert program_structure(scoped) == program_structure(bare)
