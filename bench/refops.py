"""Integer operations the plain references share.  numpy and jax.numpy
only: nothing of repro is imported here or by any reference."""

from __future__ import annotations

import numpy as np


def quantize_weight(w_fp: np.ndarray, w_scale: float, bits: int = 8) -> np.ndarray:
    """(out, in) float weight -> (in, out) int8 weight.  ``bits`` below 8
    keeps only that many bits: a grid 2**(8 - bits) times coarser over the
    same range, which is the control's lower precision."""
    step = 2 ** (8 - bits)
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    q = np.clip(np.round(w_fp.T / np.float32(w_scale * step)), lo, hi)
    return (q * step).astype(np.int8)


def imatmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Exact int8 @ int8 with int32 accumulation (``jnp.matmul`` with an
    int32 result type: exact on every backend), batched over leading
    dims; returned as int64."""
    import jax.numpy as jnp

    out = jnp.matmul(jnp.asarray(a, jnp.int8), jnp.asarray(b, jnp.int8),
                     preferred_element_type=jnp.int32)
    return np.asarray(out).astype(np.int64)


def qdense(x: np.ndarray, w_q: np.ndarray, b: np.ndarray, rq_scale: float,
           clip_lo: int = -128) -> np.ndarray:
    """int8 x @ int8 w + int32 bias, requantized (round half to even) and
    clipped to [clip_lo, 127]."""
    acc = (imatmul(x, w_q) + b.astype(np.int64)).astype(np.float64)
    return np.clip(np.rint(acc * rq_scale), clip_lo, 127).astype(np.int8)
