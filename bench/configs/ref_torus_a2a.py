"""Plain reference of the expert-parallel dispatch exchange: a blockwise
all-to-all is a transpose of the (source, destination) block grid.

``x[s, d]`` is the block that chip ``s`` sends to chip ``d``; after the
exchange chip ``d`` holds, at position ``s``, the block that ``s`` sent it:
``y[d, s] = x[s, d]``.  The comparison is bit for bit.
"""

from __future__ import annotations

import numpy as np


def expected(x: np.ndarray) -> np.ndarray:
    """The received blocks, for a global send buffer ``x`` of shape
    ``(p, p, *block)``."""
    return np.swapaxes(x, 0, 1)


def mismatched_bytes(got: np.ndarray, want: np.ndarray) -> int:
    """Bytes that differ, comparing the raw bit patterns."""
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(max(got.nbytes, want.nbytes))
    a = np.ascontiguousarray(got).view(np.uint8)
    b = np.ascontiguousarray(want).view(np.uint8)
    return int(np.count_nonzero(a != b))


def lower_precision(x: np.ndarray) -> np.ndarray:
    """The control: the reference's exchange with its blocks carried in
    the next precision below the configuration's bfloat16 (fp8 e4m3) and
    read back.  A correct run must tell it apart from the exchange."""
    import ml_dtypes
    return np.swapaxes(x.astype(ml_dtypes.float8_e4m3fn).astype(x.dtype),
                       0, 1)
