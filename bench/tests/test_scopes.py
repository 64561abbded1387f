"""Device time by name scope: the join of traced ops with the compiled
HLO's ``op_name`` metadata, and the two readers built on it, on hand-made
HLO text and events; and the scopes in the serving cell's compiled decode
step, at a size the CPU holds."""

import importlib.util
import os
import subprocess
import sys

import pytest

from bench import harness, scopes
from bench.tests import small

HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "model.py"

%fused_computation.1 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %multiply.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/while/body/mixer.attn/mul"}
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_type="mul" op_name="jit(step)/while/body/mixer.attn/mul" source_file="model.py" source_line=3}
  %dynamic-slice.4 = f32[4]{0} dynamic-slice(%fusion.3), metadata={op_name="jit(step)/while/body/dynamic_slice"}
  %copy.5 = f32[4]{0} copy(%dynamic-slice.4)
  %convolution.6 = f32[4]{0} convolution(%copy.5), metadata={op_name="jit(step)/while/body/transpose(jvp(ffn))/dot_general"}
  %copy-start.8 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%p)
  %copy-done.9 = f32[4]{0} copy-done(%copy-start.8)
  ROOT %all-to-all.7 = f32[4]{0} all-to-all(%convolution.6), metadata={op_name="jit(step)/a2a[overlap]/all_to_all"}
}
"""

# (name, text, seconds) as bench.reduce.Reduced.events holds them
EVENTS = [("fusion.3", "", 4.0), ("dynamic-slice.4", "", 2.0),
          ("copy.5", "", 1.0), ("convolution.6", "", 3.0),
          ("all_to_all.7", "", 6.0), ("fusion.99", "", 50.0),
          ("copy-start.8", "", 40.0)]   # an async copy in flight


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name.replace('.', '_')}", harness.metric_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Reduced:
    def __init__(self, events):
        self.events = events


def _ctx(events):
    return harness.Context(cell=None, counters={},
                           reduced=None if events is None
                           else _Reduced(events), peaks=None)


def test_join_attributes_traced_ops_to_their_scopes():
    table = scopes.ScopeTable(HLO)
    assert table.scope("fusion.3") == "jit(step)/while/body/mixer.attn/mul"
    assert table.scope("copy.5") == scopes.UNSCOPED       # no metadata
    # a TPU trace spells the collective with underscores
    assert table.scope("all_to_all.7") == "jit(step)/a2a[overlap]/all_to_all"
    assert table.scope("fusion.99") is None               # another module
    assert table.scope("copy-start.8") == scopes.UNSCOPED
    assert table.scope("multiply.1").endswith("mixer.attn/mul")
    joined = scopes.joined(EVENTS, table)          # no async start
    assert [n for n, _, _ in joined] == [
        "fusion.3", "dynamic-slice.4", "copy.5", "convolution.6",
        "all_to_all.7"]


def test_scope_patterns_match_whole_components():
    assert scopes.LAYER_SCOPE.search("jit(f)/while/body/mixer.attn/add")
    assert scopes.LAYER_SCOPE.search("jit(f)/transpose(jvp(ffn))/dot")
    assert scopes.LAYER_SCOPE.search("jit(f)/norm")
    assert not scopes.LAYER_SCOPE.search("jit(f)/normalize/add")
    assert not scopes.LAYER_SCOPE.search("params['ffn']['w1']/x")
    assert scopes.EXCHANGE_SCOPE.search("jit(f)/shard_map/a2a[direct]/x")
    assert not scopes.EXCHANGE_SCOPE.search("jit(f)/a2a_round[data]/x")


def test_layer_scan_share_reader(monkeypatch):
    read = _reader("layer_scan_share.serve").read
    monkeypatch.setattr(scopes, "serve_step_text", lambda cell: HLO)
    # of 16 s joined, outside the layers: dynamic-slice 2 + copy 1 +
    # the collective 6 (no layer scope) = 9
    assert read(_ctx(EVENTS)) == pytest.approx(100.0 * 9 / 16)
    assert read(_ctx(None)) is None
    assert read(_ctx([("fusion.99", "", 1.0)])) is None    # nothing joined
    # a program without layer scopes gives no reading
    monkeypatch.setattr(scopes, "serve_step_text", lambda cell: HLO.replace(
        "mixer.attn", "m").replace("ffn", "f"))
    assert read(_ctx(EVENTS)) is None


def test_exchange_pack_share_reader(monkeypatch):
    read = _reader("exchange_pack_share").read
    text = HLO.replace('op_name="jit(step)/while/body/dynamic_slice"',
                       'op_name="jit(step)/a2a[overlap]/dynamic_slice"')
    monkeypatch.setattr(scopes, "exchange_text", lambda cell: text)
    # under a2a[*] and no collective: dynamic-slice 2; collectives 6
    assert read(_ctx(EVENTS)) == pytest.approx(100.0 * 2 / 8)
    assert read(_ctx(None)) is None
    assert read(_ctx([("copy.5", "", 1.0)])) is None
    monkeypatch.setattr(scopes, "exchange_text",
                        lambda cell: HLO.replace("a2a[overlap]", "x"))
    assert read(_ctx(EVENTS)) is None


def test_compiled_decode_step_of_the_serving_cell_carries_layer_scopes():
    cell = small.cell("danube.chat.1c", small.SMALL_DECODER,
                      small.SMALL_CHAT)
    table = scopes.ScopeTable(scopes.serve_step_text(cell))
    paths = set(table.paths.values())
    for scope in ("embed", "norm", "mixer.attn", "ffn", "lm_head"):
        assert any(f"/{scope}/" in p for p in paths), scope
    assert any(p == scopes.UNSCOPED or not scopes.LAYER_SCOPE.search(p)
               for p in paths)


def test_compiled_exchange_program_carries_its_backend_scope():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    code = ("from bench import scopes\n"
            "from bench.tests import small\n"
            "cell = small.cell('phi35moe.a2a_dispatch.2x2', small.SMALL_MOE,"
            " small.SMALL_DISPATCH)\n"
            "t = scopes.ScopeTable(scopes.exchange_text(cell))\n"
            "print(sorted({p for p in t.paths.values()"
            " if scopes.EXCHANGE_SCOPE.search(p)}))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=harness.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "/a2a[" in proc.stdout and "all_to_all" in proc.stdout
