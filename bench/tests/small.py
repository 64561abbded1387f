"""Cells at a size the CPU runs in seconds, with the widths of the chip's
cells cut, for the tests of the harness.  Everything else (drivers,
references, comparison) is what a chip run uses."""

from __future__ import annotations

import dataclasses
import json

from bench import harness

SMALL_DECODER = {
    "program": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                "n_kv_heads": 2, "d_ff": 128, "vocab": 256, "window": 16,
                "tie_embeddings": False},
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "sliding_window": 16,
    "serve": {"max_batch": 4, "max_seq": 32,
              "gap_limits": {"max": 6e-4, "mean": 6e-5}},
}
SMALL_CHAT = {
    "rate_per_s": 20.0,
    "prompt_len": {"dist": "lognormal", "mean": 8, "sigma": 0.5, "min": 2,
                   "max": 16},
    "output_len": {"dist": "lognormal", "mean": 8, "sigma": 0.5, "min": 2,
                   "max": 16},
    "check_requests": 3, "drain_limit_s": 60.0,
}
SMALL_MOE = {"program": {"d_model": 64, "n_experts": 4},
             "hidden_size": 64, "num_local_experts": 4}
SMALL_DISPATCH = {"tokens_per_chip": 64, "round_trips_per_call": 2,
                  "sample_from_calls": 4}


def cell(workload: str, config: dict, mix: dict) -> harness.Cell:
    """The benchmark's cell, its configuration and mix overlaid with the
    small sizes."""
    c = harness.resolve(harness.load_benchmark(), workload)
    merged = json.loads(json.dumps(c.config))
    for k, v in config.items():
        if isinstance(v, dict) and isinstance(merged.get(k), dict):
            merged[k] = {**merged[k], **v}
        else:
            merged[k] = v
    return dataclasses.replace(c, config=merged, mix={**c.mix, **mix})


def no_hooks() -> harness.Hooks:
    return harness.Hooks(lambda: None, lambda: None)


def correct(c: harness.Cell, out: harness.Outcome) -> bool:
    line = harness.result_line(c, out, {"platform": "cpu"}, 0.0, 0.0,
                               False, None)
    return line["correct"]
