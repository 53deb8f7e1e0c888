"""Plain-jnp spellings of the quantized-NN idioms the frontend recognizes.

These are ordinary ``jax.numpy`` compositions — nothing here is a custom
primitive — written in exactly the shape the jaxpr importer raises back into
single IR ops.  Model code is free to inline the same expressions by hand;
using the helpers just keeps the recognized form in one place:

    quantize(x, s)    = clip(round(x / s), -128, 127).astype(int8)   -> ir.quantize
    requantize(x, s)  = clip(round(x * s), iinfo range).astype(int8) -> ir.requantize
    dequantize(x, s)  = x.astype(float32) * s                        -> ir.dequantize
    max_pool2d(x, k)  = NHWC square reduce_window max                -> ir.max_pool2d
    dense(x, w)       = matmul with wide int accumulation            -> ir.dense
    conv2d(x, w)      = NHWC/HWIO conv with wide int accumulation    -> ir.conv2d

The two KV-cache helpers are the exception to "nothing here is special":
they are ``jax.jit``-wrapped so the traced jaxpr carries a *named* jit
call the importer can map 1:1 onto the stateful IR ops:

    kv_cache_read(c)         = c (identity; marks state consumption) -> ir.kv_cache_read
    kv_cache_append(c, u, p) = dynamic_update_slice at seq pos p     -> ir.kv_cache_append
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def quantize(x, scale: float, dtype=jnp.int8):
    """Symmetric quantization: round(x / scale), clipped to [-128, 127]."""
    return jnp.clip(jnp.round(x / scale), -128, 127).astype(dtype)


def requantize(x, scale: float, dtype=jnp.int8):
    """Requantization: round(x * scale) with a saturating cast to ``dtype``."""
    info = jnp.iinfo(dtype)
    return jnp.clip(jnp.round(x * scale), int(info.min), int(info.max)).astype(dtype)


def dequantize(x, scale: float):
    return x.astype(jnp.float32) * scale


def max_pool2d(x, size: int = 2, stride: int | None = None):
    """NHWC max pooling with a square window (no padding)."""
    stride = size if stride is None else stride
    if jnp.issubdtype(x.dtype, jnp.integer):
        init = np.asarray(jnp.iinfo(x.dtype).min, dtype=x.dtype)
    else:
        init = np.asarray(-np.inf, dtype=x.dtype)
    return lax.reduce_window(
        x,
        init,
        lax.max,
        window_dimensions=(1, size, size, 1),
        window_strides=(1, stride, stride, 1),
        padding="VALID",
    )


def dense(x, w):
    """x[..., C] @ w[C, K]; integer operands accumulate wide (int32),
    matching ``ir.dense`` / the systolic-array semantics.  A 3-D ``w`` is
    the batched activation-activation matmul ``x[B, M, C] @ w[B, C, K]``,
    spelled as an explicit batched ``dot_general`` because ``jnp.matmul``
    specializes a unit batch dim into a squeeze/transpose chain the
    importer does not recognize."""
    preferred = jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else None
    if x.ndim == 3 and w.ndim == 3:
        return lax.dot_general(
            x, w, (((2,), (1,)), ((0,), (0,))), preferred_element_type=preferred
        )
    return jnp.matmul(x, w, preferred_element_type=preferred)


@jax.jit
def kv_cache_read(cache):
    """Materialize the KV cache for attention -> ``ir.kv_cache_read``.

    Numerically the identity; the ``jax.jit`` wrapper makes the call appear
    in the jaxpr as a ``jit`` equation named ``kv_cache_read``, which the
    importer maps 1:1 to the stateful IR op (same mechanism as the named
    ``relu``/``clip`` idioms).  A bare ``return cache`` would NOT survive:
    jax forwards an identity jit's output var and leaves a dead jit
    equation with no outvars, so the body adds a scalar zero — bit-exact
    identity for every dtype, but a real equation the importer can see.
    """
    return cache + jnp.zeros((), cache.dtype)


@jax.jit
def kv_cache_append(cache, update, pos):
    """Write ``update``'s rows into ``cache`` at sequence position ``pos``
    (axis -2), returning the updated cache -> ``ir.kv_cache_append``.

    ``pos`` is a scalar, or ``[B]`` for per-request positions on a batched
    ``[B, L, D]`` cache.  Writes must stay in bounds — the IR executor
    raises where ``dynamic_update_slice`` would clamp.
    """
    if jnp.ndim(pos) == 0:
        starts = tuple(0 for _ in range(cache.ndim - 2)) + (pos, 0)
        return lax.dynamic_update_slice(cache, update, starts)
    return jax.vmap(
        lambda c, u, p: lax.dynamic_update_slice(
            c, u, tuple(0 for _ in range(c.ndim - 2)) + (p, 0)
        )
    )(cache, update, pos)


def conv2d(x, w, stride: int = 1, padding: int = 0):
    """NHWC conv with HWIO weights; integer operands accumulate to int32."""
    preferred = jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else None
    return lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=((padding, padding), (padding, padding)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=preferred,
    )
