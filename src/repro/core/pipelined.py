"""Software-pipelined factorized all-to-all — compatibility facade.

The chunk-interleaved scheduler that used to live here has been absorbed
into the general overlap engine (``core.overlap``), which adds arbitrary
``round_order``, per-chunk compute stages and reverse (combine) rounds.
``pipelined_all_to_all`` remains the no-compute-stage form, which runs the
whole payload unchunked (chunks exist to interleave rounds with a compute
stage), and is re-exported here for existing callers; like every legacy
free function it is now a ``DeprecationWarning`` shim over
``core.plan.plan_all_to_all(..., backend="pipelined").forward``.

``choose_chunks`` now delegates to the tuning model's
``predict_overlapped``, which prices the factorized bandwidth term
``(D_k - 1) * (p / D_k) * block_bytes`` per round — consistent with
``tuning.predict_factorized`` — instead of the direct-algorithm
``(p - 1) * block_bytes`` the old local model used.
"""

from __future__ import annotations

from .dims import dims_create
from .overlap import overlapped_all_to_all, pipelined_all_to_all
from .tuning import LinkModel, resolve_links
from .tuning import choose_chunks as _choose_chunks

__all__ = ["choose_chunks", "overlapped_all_to_all", "pipelined_all_to_all"]


def choose_chunks(p: int, d: int, block_bytes: float,
                  link: LinkModel, max_chunks: int = 4, *,
                  links=None) -> int:
    """Pick n_chunks minimizing the overlapped alpha-beta estimate for a
    d-way factorization of ``p`` (legacy signature; see
    ``tuning.choose_chunks`` for the native per-axis form).

    ``link`` prices every axis uniformly; ``links=`` (a length-d
    sequence) overrides per axis — e.g. the measured fits recorded by
    ``core.autotune``.  Both spellings merge in ``tuning.resolve_links``,
    the single link-plumbing helper.
    """
    dims = dims_create(p, d)
    return _choose_chunks(dims,
                          resolve_links(link if links is None else links,
                                        dims),
                          block_bytes, max_chunks=max_chunks)
