"""The one generator of the benchmark's inputs.  Every traffic mix is a JSON
file beside this one; this module reads its parameters and makes the
inputs from ``--seed``.  The program under test gets only what is made
here.

Work is fixed per mix and run length: a seed draws the order of a fixed
set of sizes and gaps (quantiles of the stated distributions) and the
token ids, so two seeds do the same amount of work, in another order.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


def load_mix(name: str) -> dict:
    path = HERE / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} at {path}")
    return json.loads(path.read_text())


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream): any whole number is a
    seed, negative or beyond 64 bits included."""
    words = [seed % 2 ** 64, (seed // 2 ** 64) % 2 ** 64,
             int(seed < 0)] + [ord(ch) for ch in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def jax_seed(seed: int, stream: str) -> int:
    """A 31-bit seed for ``jax.random.key`` drawn from (seed, stream)."""
    return int(rng(seed, stream).integers(0, 2 ** 31 - 1))


# ---------------------------------------------------------------------------
# distributions as fixed quantile sets
# ---------------------------------------------------------------------------

def lognormal_quantiles(n: int, mean: float, sigma: float,
                        lo: float = 0.0, hi: float = math.inf) -> np.ndarray:
    """``n`` quantiles, at (i + 1/2) / n, of the log-normal with the given
    mean and log-space standard deviation, truncated to [lo, hi]: drawn
    as the log-normal conditioned on lying there."""
    mu = math.log(mean) - sigma * sigma / 2
    nd = NormalDist()
    f_lo = nd.cdf((math.log(lo) - mu) / sigma) if lo > 0 else 0.0
    f_hi = nd.cdf((math.log(hi) - mu) / sigma) if hi < math.inf else 1.0
    u = f_lo + (f_hi - f_lo) * (np.arange(n) + 0.5) / n
    z = np.asarray([nd.inv_cdf(float(x)) for x in u])
    return np.exp(mu + sigma * z)


def exponential_quantiles(n: int, mean: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-u)


def lengths(spec: dict, n: int, r: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths: quantiles of the log-normal (``mean``,
    ``sigma``) truncated to [``min``, ``max``], rounded, in an order drawn
    by ``r``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    q = lognormal_quantiles(n, spec["mean"], spec["sigma"], spec["min"],
                            spec["max"])
    out = np.clip(np.rint(q), spec["min"], spec["max"]).astype(np.int64)
    return r.permutation(out)


def arrival_times(rate_per_s: float, n: int,
                  r: np.random.Generator) -> np.ndarray:
    """Open-loop Poisson arrivals: ``n`` exponential gaps of mean
    ``1 / rate_per_s`` in an order drawn by ``r``; the first request is
    due at time 0."""
    gaps = r.permutation(exponential_quantiles(n, 1.0 / rate_per_s))
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def zipf_tokens(shape, vocab: int, exponent: float,
                r: np.random.Generator) -> np.ndarray:
    """Token ids from a Zipf unigram law over the vocabulary: id ``k`` is
    drawn with probability proportional to ``1 / (k + 1) ** exponent``."""
    w = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    u = r.random(int(np.prod(shape)))
    ids = np.searchsorted(cdf, u, side="right")
    return np.minimum(ids, vocab - 1).astype(np.int32).reshape(shape)


# ---------------------------------------------------------------------------
# serving requests
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RequestSpec:
    rid: int
    due_s: float           # scheduled arrival, from the window's start
    prompt: np.ndarray     # int32 token ids
    max_new: int


def requests(mix: dict, seconds: float, seed: int, vocab: int,
             max_seq: int) -> list[RequestSpec]:
    """The requests of one run: ``rate_per_s * seconds`` of them (at
    least one), each prompt plus output within ``max_seq``."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    due = arrival_times(mix["rate_per_s"], n, rng(seed, "arrivals"))
    plen = lengths(mix["prompt_len"], n, rng(seed, "prompt_len"))
    olen = lengths(mix["output_len"], n, rng(seed, "output_len"))
    if plen.max() + olen.max() > max_seq:
        raise ValueError(f"mix allows {plen.max()} + {olen.max()} tokens, "
                         f"the server holds {max_seq}")
    toks = zipf_tokens((int(plen.sum()),), vocab, mix["token_zipf"],
                       rng(seed, "tokens"))
    out, at = [], 0
    for i in range(n):
        out.append(RequestSpec(i, float(due[i]), toks[at:at + plen[i]],
                               int(olen[i])))
        at += int(plen[i])
    return out
