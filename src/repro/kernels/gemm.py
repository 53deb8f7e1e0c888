"""Scheduled GEMM Pallas kernel — the TPU lowering of the paper's mapping
generator output.

The extended-CoSA ``Schedule`` fixes the VMEM tile shape (block_m/k/n), the
dataflow (grid loop order: OS iterates m outer / n middle, WS iterates n
outer so the weight panel is revisited across m), and double buffering
(Mosaic pipelines block copies automatically; the scheduler already sized
tiles for half-VMEM shares when enabled).  The reduction dim is always the
innermost grid dim so partial sums accumulate in a VMEM f32/int32 scratch —
the TPU analogue of Gemmini's accumulator SRAM.

Kernel-naming convention: m, k, n are the GEMM dims (paper's N, C, K).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclass(frozen=True)
class GemmKernelConfig:
    """Everything the mapping generator derives from a Schedule."""

    block_m: int
    block_k: int
    block_n: int
    dataflow: str = "OS"  # OS: grid (m, n, k); WS: grid (n, m, k)
    acc_dtype: str = "float32"
    out_dtype: str = "float32"
    # epilogue (quantized generalized op): requantize+clip, or activation
    requant_scale: float | None = None
    clip_lo: float | None = None
    clip_hi: float | None = None
    activation: str | None = None
    has_bias: bool = False
    interpret: bool = False
    vmem_limit_bytes: int | None = None

    def grid_for(self, m: int, k: int, n: int) -> tuple[int, int, int]:
        gm, gk, gn = m // self.block_m, k // self.block_k, n // self.block_n
        if self.dataflow == "WS":
            return (gn, gm, gk)
        return (gm, gn, gk)


def _apply_epilogue(acc, cfg: GemmKernelConfig, bias=None):
    if bias is not None:
        acc = acc + bias.astype(acc.dtype)
    if cfg.requant_scale is not None:
        acc = jnp.round(acc.astype(jnp.float32) * cfg.requant_scale)
        acc = jnp.clip(acc, cfg.clip_lo, cfg.clip_hi)
    elif cfg.activation == "relu":
        acc = jnp.maximum(acc, 0)
    elif cfg.activation == "gelu":
        acc = jax.nn.gelu(acc)
    return acc


def _gemm_kernel(x_ref, w_ref, *rest, cfg: GemmKernelConfig, n_k: int):
    if cfg.has_bias:
        b_ref, o_ref, acc_ref = rest
    else:
        (o_ref, acc_ref) = rest
        b_ref = None
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_dtype = jnp.dtype(cfg.acc_dtype)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )

    @pl.when(k_step == n_k - 1)
    def _store():
        acc = acc_ref[...]
        acc = _apply_epilogue(acc, cfg, None if b_ref is None else b_ref[...])
        o_ref[...] = acc.astype(o_ref.dtype)


def scheduled_gemm(
    x: jax.Array,
    w: jax.Array,
    cfg: GemmKernelConfig,
    bias: jax.Array | None = None,
) -> jax.Array:
    """Out[m, n] = epilogue(x[m, k] @ w[k, n] (+ bias[n])).

    Shapes must already be padded to multiples of the block shape — the
    ops.py wrapper handles padding/unpadding (the scheduler padded dims to
    hardware alignment before factorization, so these agree).
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % cfg.block_m == 0 and k % cfg.block_k == 0 and n % cfg.block_n == 0, (
        (m, k, n),
        (cfg.block_m, cfg.block_k, cfg.block_n),
    )
    if cfg.has_bias != (bias is not None):
        raise ValueError("cfg.has_bias does not match bias argument")

    gm, gk, gn = m // cfg.block_m, k // cfg.block_k, n // cfg.block_n
    grid = cfg.grid_for(m, k, n)
    ws = cfg.dataflow == "WS"

    # index maps receive grid coords in grid order; normalize to (im, in, ik)
    if ws:
        x_map = lambda jn, im, ik: (im, ik)
        w_map = lambda jn, im, ik: (ik, jn)
        o_map = lambda jn, im, ik: (im, jn)
        b_map = lambda jn, im, ik: (0, jn)
    else:
        x_map = lambda im, jn, ik: (im, ik)
        w_map = lambda im, jn, ik: (ik, jn)
        o_map = lambda im, jn, ik: (im, jn)
        b_map = lambda im, jn, ik: (0, jn)

    in_specs = [
        pl.BlockSpec((cfg.block_m, cfg.block_k), x_map),
        pl.BlockSpec((cfg.block_k, cfg.block_n), w_map),
    ]
    operands = [x, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, cfg.block_n), b_map))
        operands.append(bias.reshape(1, n))

    kernel = functools.partial(_gemm_kernel, cfg=cfg, n_k=gk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((cfg.block_m, cfg.block_n), o_map),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(cfg.out_dtype)),
        scratch_shapes=[
            pltpu.VMEM((cfg.block_m, cfg.block_n), jnp.dtype(cfg.acc_dtype))
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=cfg.vmem_limit_bytes,
        ),
        interpret=cfg.interpret,
    )(*operands)
