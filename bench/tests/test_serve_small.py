"""The serving cell's run at a size the CPU holds, driven past the
harness's look for a chip: a sound program comes out correct; the control
(the reference in fp8) and each fault planted under the timed path come
out not correct."""

import pytest

from bench.drivers import serve
from bench.tests import small

SEED = 2 ** 31 + 99


@pytest.fixture(scope="module")
def cell():
    return small.cell("danube.chat.1c", small.SMALL_DECODER,
                      small.SMALL_CHAT)


def _run(cell, **kw):
    return serve.run(cell, seed=SEED, seconds=1.0, trace=False, peaks=None,
                     hooks=small.no_hooks(), **kw)


def test_sound_program_is_correct_and_its_control_is_not(cell):
    out = _run(cell, control=True)
    assert out.failed == 0 and out.attempted == 20
    assert small.correct(cell, out)
    limits = {k.name.rsplit("_", 1)[1]: k.limit for k in out.checks}
    assert set(limits) == {"max", "mean"}
    control = out.counters["control_gap"]
    assert any(control[k] > limits[k] for k in limits), (control, limits)
    assert out.e2e["ttft_p95_ms"] > 0 and out.e2e["tpot_p95_ms"] > 0
    assert 0 < out.counters["prefill_slot_ticks"] \
        < out.counters["occupied_slot_ticks"]


def _altered_tokens(step):
    def bad(params, toks, caches):
        logits, caches = step(params, toks, caches)
        return logits[..., ::-1], caches           # another token is served
    return bad


def _state_unchanged(step):
    def bad(params, toks, caches):
        logits, _ = step(params, toks, caches)
        return logits, caches                      # the cache never moves
    return bad


@pytest.mark.parametrize("fault", [_altered_tokens, _state_unchanged])
def test_fault_under_the_timed_path_is_not_correct(cell, fault,
                                                   monkeypatch):
    import repro.launch.serve as launch_serve
    good = launch_serve.batcher_step
    monkeypatch.setattr(launch_serve, "batcher_step",
                        lambda serve_fn: fault(good(serve_fn)))
    out = _run(cell)
    assert not small.correct(cell, out)
