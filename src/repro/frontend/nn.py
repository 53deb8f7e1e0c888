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

The blocks of a decoder with routed experts (Mixtral-style) are named the
same way; their float scalars are passed as ordinary arguments, so they
reach the importer as literals of the named call (``ir.*_ref`` define the
host semantics, in float64):

    rms_norm(x, s_in, s_out, eps)     = int8 RMSNorm, last axis       -> ir.rms_norm
    rope(x, cos, sin, s_in, s_out)    = rotate-half rotary positions  -> ir.rope
    silu_mul(x, s_in, s_out)          = silu(gate) * up on [gate|up]  -> ir.silu_mul
    moe_route(logits, top_k)          = top-k expert ids, ties low    -> ir.moe_route
    moe_group_sizes(idx, n_experts)   = routed rows per expert        -> ir.moe_group_sizes
    moe_dispatch(x, idx)              = rows sorted by expert         -> ir.moe_dispatch
    moe_combine(y, idx, logits, l, s) = gate-weighted sum per token   -> ir.moe_combine

and the expert GEMM itself is ``grouped_dense`` (``jax.lax.ragged_dot``
-> ir.grouped_dense).
"""

from __future__ import annotations

import functools

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


def _int8(y):
    return jnp.clip(jnp.round(y), -128, 127).astype(jnp.int8)


@jax.jit
def rms_norm(x, in_scale, out_scale, eps):
    """int8 RMSNorm over the last axis -> ``ir.rms_norm``."""
    xi = x.astype(jnp.int32)
    ms = jnp.sum(xi * xi, axis=-1, keepdims=True).astype(jnp.float32)
    ms = ms * (in_scale * in_scale) / x.shape[-1]
    return _int8(x.astype(jnp.float32) * in_scale * lax.rsqrt(ms + eps) / out_scale)


@jax.jit
def rope(x, cos, sin, in_scale, out_scale):
    """Rotate-half rotary positions on ``x[..., S, head_dim]`` with
    ``[S, head_dim]`` tables -> ``ir.rope``."""
    xf = x.astype(jnp.float32) * in_scale
    h = x.shape[-1] // 2
    rot = jnp.concatenate([-xf[..., h:], xf[..., :h]], axis=-1)
    return _int8((xf * cos + rot * sin) / out_scale)


@jax.jit
def silu_mul(x, in_scale, out_scale):
    """SwiGLU over ``[gate | up]`` on the last axis -> ``ir.silu_mul``."""
    f = x.shape[-1] // 2
    g = x[..., :f].astype(jnp.float32) * in_scale
    u = x[..., f:].astype(jnp.float32) * in_scale
    return _int8(jax.nn.silu(g) * u / out_scale)


@functools.partial(jax.jit, static_argnames=("top_k",))
def moe_route(logits, top_k: int):
    """The ``top_k`` experts of each token, best first; equal logits go to
    the lower expert index -> ``ir.moe_route``."""
    return lax.top_k(logits, top_k)[1].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n_experts",))
def moe_group_sizes(idx, n_experts: int):
    """Routed rows per expert -> ``ir.moe_group_sizes``."""
    return jnp.bincount(idx.reshape(-1), length=n_experts).astype(jnp.int32)


def _moe_order(idx):
    return jnp.argsort(idx.reshape(-1), stable=True)


@jax.jit
def moe_dispatch(x, idx):
    """Each token's row once per routed expert, sorted by expert (token,
    then slot, within an expert) -> ``ir.moe_dispatch``."""
    return x[_moe_order(idx) // idx.shape[-1]]


def grouped_dense(x, w, group_sizes):
    """``x[R, C]`` (rows sorted by group) times each group's ``w[g]``;
    integer operands accumulate in int32 -> ``ir.grouped_dense``."""
    preferred = jnp.int32 if jnp.issubdtype(x.dtype, jnp.integer) else None
    return lax.ragged_dot(x, w, group_sizes, preferred_element_type=preferred)


@jax.jit
def moe_combine(y, idx, logits, logit_scale, scale):
    """Each token's expert rows weighted by the softmax of its top-k router
    logits, summed in slot order -> ``ir.moe_combine``."""
    t, k = idx.shape
    pos = jnp.zeros(t * k, jnp.int32).at[_moe_order(idx)].set(jnp.arange(t * k))
    yk = y[pos].reshape(t, k, y.shape[-1]).astype(jnp.float32)
    g = jax.nn.softmax(
        jnp.take_along_axis(logits, idx, axis=-1).astype(jnp.float32) * logit_scale
    )
    return _int8(jnp.sum(g[..., None] * yk, axis=1) * scale)


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
