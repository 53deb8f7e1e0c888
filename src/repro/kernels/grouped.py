"""Grouped int8 GEMM Pallas kernel: every expert GEMM of a routed-expert
(MoE) projection in one launch, with the fused requantize + clip epilogue.

The rows arrive sorted by expert, ``group_sizes[e]`` of them for expert
``e``, a count known only at run time.  ``grouped_qmatmul`` lays each
expert's rows out from a row-tile boundary (``block_m`` rows a tile; the
last tile of an expert is padded) and hands the kernel two scalar-prefetch
operands: the expert of every row tile, and how many tiles are in use.  The
grid is (column tiles, row tiles); a row tile's weight block is its
expert's ``[K, block_n]`` panel, so consecutive tiles of one expert share
the block and Mosaic fetches each expert's panel once per column tile: the
weights are read from HBM once per call, whatever the routing.  Each step
holds the whole reduction (``block_k`` = K), so no accumulator outlives a
step.  Tiles past the ones in use repeat the last tile's blocks (nothing is
fetched) and skip the compute.  An expert with no rows has no tiles and its
weights are never read.

``tile_layout`` is the same layout in numpy, for the host's counters.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gemm import GemmKernelConfig, _apply_epilogue


def max_row_tiles(rows: int, n_groups: int, block_m: int) -> int:
    """The most row tiles ``rows`` rows over ``n_groups`` groups can need:
    ``Σ ceil(n_e / block_m) <= floor((rows + n_groups·(block_m - 1)) / block_m)``."""
    return max((rows + n_groups * (block_m - 1)) // block_m, 1)


def tile_layout(group_sizes: np.ndarray, block_m: int) -> tuple[int, int, int]:
    """(row tiles in use, padded rows computed beyond the routed ones, the
    busiest group's rows) for one call."""
    gs = np.asarray(group_sizes, np.int64)
    tiles = -(-gs // block_m)
    used = int(tiles.sum())
    return used, used * block_m - int(gs.sum()), int(gs.max(initial=0))


def _grouped_kernel(tile_group_ref, n_used_ref, x_ref, w_ref, o_ref, *, cfg):
    @pl.when(pl.program_id(1) < n_used_ref[0])
    def _compute():
        acc = jax.lax.dot_general(
            x_ref[...],
            w_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        o_ref[...] = _apply_epilogue(acc, cfg).astype(o_ref.dtype)


def scheduled_grouped_gemm(
    x_pad: jax.Array,
    w: jax.Array,
    tile_group: jax.Array,
    n_used: jax.Array,
    cfg: GemmKernelConfig,
) -> jax.Array:
    """``out[tile i] = epilogue(x_pad[tile i] @ w[tile_group[i]])`` for the
    first ``n_used[0]`` row tiles; rows of the other tiles are undefined.
    ``x_pad``: int8 ``[tiles · block_m, K]``; ``w``: int8 ``[G, K, N]`` with
    N a multiple of ``block_n``."""
    m, k = x_pad.shape
    _, k2, n = w.shape
    assert k == k2 and m % cfg.block_m == 0 and n % cfg.block_n == 0, (
        x_pad.shape, w.shape, (cfg.block_m, cfg.block_n),
    )
    bm, bn = cfg.block_m, cfg.block_n

    def row_tile(i, n_used):
        return jnp.minimum(i, n_used[0] - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // bn, m // bm),
        in_specs=[
            pl.BlockSpec((bm, k), lambda j, i, tg, nu: (row_tile(i, nu), 0)),
            pl.BlockSpec((None, k, bn), lambda j, i, tg, nu: (tg[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, i, tg, nu: (row_tile(i, nu), j)),
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, cfg=cfg),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(cfg.out_dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=cfg.vmem_limit_bytes,
        ),
        interpret=cfg.interpret,
        name="grouped_qmatmul",
    )(tile_group, n_used, x_pad, w)


@functools.partial(jax.jit, static_argnames=("cfg",))
def grouped_qmatmul(
    x: jax.Array, w: jax.Array, group_sizes: jax.Array, cfg: GemmKernelConfig
) -> jax.Array:
    """Quantized grouped dense: ``x[R, K]`` int8 with rows sorted by group,
    ``w[G, K, N]`` int8, ``group_sizes[G]`` int32 summing to R ->
    ``requantize(clip)`` of each row times its group's panel, int8 ``[R, N]``."""
    if cfg.requant_scale is None:
        raise ValueError("quantized grouped GEMM requires cfg.requant_scale")
    cfg = dataclasses.replace(
        cfg,
        acc_dtype="int32",
        out_dtype=cfg.out_dtype or "int8",
        clip_lo=cfg.clip_lo if cfg.clip_lo is not None else -128.0,
        clip_hi=cfg.clip_hi if cfg.clip_hi is not None else 127.0,
        activation=None,
        has_bias=False,
    )
    rows = x.shape[0]
    n_groups = w.shape[0]
    bm = cfg.block_m
    n_tiles = max_row_tiles(rows, n_groups, bm)
    gs = group_sizes.astype(jnp.int32)
    tiles = (gs + bm - 1) // bm
    tile_end = jnp.cumsum(tiles)
    n_used = tile_end[-1]
    # the group of each row tile; tiles past the last in use repeat its group
    tile_ids = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32), n_used - 1)
    tile_group = jnp.searchsorted(tile_end, tile_ids, side="right").astype(jnp.int32)
    # where each sorted row lands: its group's first tile, plus its offset
    row_end = jnp.cumsum(gs)
    row_ids = jnp.arange(rows, dtype=jnp.int32)
    row_group = jnp.searchsorted(row_end, row_ids, side="right")
    dest = (tile_end - tiles)[row_group] * bm + row_ids - (row_end - gs)[row_group]
    x_pad = jnp.zeros((n_tiles * bm, x.shape[1]), x.dtype).at[dest].set(x)
    out = scheduled_grouped_gemm(x_pad, w, tile_group, n_used.reshape(1), cfg)
    return out[dest]
