"""TPU v5e accelerator description — the production target of this repo.

The TPU is itself a GEMM-based accelerator in the paper's sense: a 128x128
systolic MXU, a software-visible vector memory (VMEM) standing in for the
scratchpad, HBM behind block copies, and a GEMM "compute instruction"
(``jax.lax.dot_general`` inside a Pallas kernel body) whose tiles must be
hardware aligned.  This description drives the *same* extended-CoSA
scheduler as Gemmini; its schedules are lowered by the mapping generator to
``pl.pallas_call`` grids + BlockSpecs instead of RoCC instructions.

Hardware constants (per chip): 197 TFLOP/s bf16 from four 128x128 MXUs at
~1.5 GHz, 819 GB/s HBM, ~50 GB/s per ICI link.  VMEM is 128 MiB per core
(JAX's ``tpu_info`` table for "TPU v5 lite"), but Mosaic grants a kernel only
a 16 MiB scoped share unless the kernel asks for more.  Every kernel here
asks for ``VMEM_LIMIT_BYTES`` (64 MiB).  The scheduler budgets 48 MiB of it
and leaves ``MOSAIC_RESERVE_BYTES`` to Mosaic's internal scratch (up to
~9 MiB at 2048-wide tiles) and the bias blocks.  Within the budget it
counts what the kernel really holds: every block double-buffered (Mosaic's
pipeline always does so) plus the f32/int32 accumulator scratch beside the
output block.  Compiles for a described v5e chip hold both figures to
account (``tests/test_tpu_compile.py``).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.accel import AcceleratorDescription
from repro.core.arch_spec import (
    OUTPUT_STATIONARY,
    WEIGHT_STATIONARY,
    ArchSpec,
    HardwareConstraints,
    MemLevel,
)

MXU_DIM = 128
LANE = 128  # last-dim tiling granularity
SUBLANE = 8  # second-to-last-dim granularity (f32; bf16 is 16)
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
MOSAIC_RESERVE_BYTES = 16 * 1024 * 1024
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES - MOSAIC_RESERVE_BYTES
ACC_BYTES = 4  # f32 / int32 accumulator scratch per output element
HBM_GBPS = 819e9
PEAK_BF16_FLOPS = 197e12
ICI_LINK_GBPS = 50e9  # per link, ~4 links/chip on a 2D torus


def make_tpu_v5e_arch() -> ArchSpec:
    # 4 MXUs x 128x128 x 2 flops x 1.5 GHz ~= 197 TFLOP/s bf16.
    n_mxu = 4
    freq = PEAK_BF16_FLOPS / (2.0 * MXU_DIM * MXU_DIM * n_mxu)
    macs_per_cycle = MXU_DIM * MXU_DIM * n_mxu
    return ArchSpec(
        name="tpu_v5e",
        levels=(
            MemLevel("mxu", size_bytes=0, holds=(), bytes_per_cycle=0.0),
            MemLevel(
                "vmem",
                size_bytes=VMEM_BUDGET_BYTES,
                holds=("In", "W", "Out"),
                bytes_per_cycle=HBM_GBPS / freq,  # HBM->VMEM bytes per cycle
            ),
            MemLevel("hbm", size_bytes=0, bytes_per_cycle=HBM_GBPS / freq),
        ),
        constraints=HardwareConstraints(
            pe_dim=MXU_DIM,
            spatial_levels=(0,),
            # N is the sublane dim of In/Out; C and K sit on lanes somewhere.
            alignments={"N": SUBLANE, "C": LANE, "K": LANE},
            memory_share_candidates=(
                (1 / 3, 1 / 3, 1 / 3),
                (1 / 4, 1 / 2, 1 / 4),
                (1 / 2, 1 / 4, 1 / 4),
                (1 / 4, 1 / 4, 1 / 2),
                (1 / 8, 5 / 8, 1 / 4),
                (3 / 8, 1 / 8, 1 / 2),
            ),
            # Mosaic's pipeline double-buffers every block
            double_buffer_candidates=(True,),
            accumulator_bytes=ACC_BYTES,
        ),
        dataflows=(OUTPUT_STATIONARY, WEIGHT_STATIONARY),
        macs_per_cycle=macs_per_cycle,
        n_pe_units=n_mxu,
        freq_hz=freq,
        # XLA/host fallback for unfolded preprocessing is far cheaper than a
        # scalar RISC-V host but still wasteful vs folding:
        host_preproc_cycles_per_byte=1.0,
        # per-pallas_call launch + Mosaic prologue, amortized per grid step:
        instr_overhead_cycles=10.0,
        # ICI ring link: wide, low-latency inter-chip interconnect
        link_bytes_per_cycle=128.0,
        link_hop_cycles=32.0,
    )


def make_tpu_v5e_description() -> AcceleratorDescription:
    desc = AcceleratorDescription(
        name="tpu_v5e",
        arch=make_tpu_v5e_arch(),
        kernel_vmem_limit_bytes=VMEM_LIMIT_BYTES,
    )

    # -- preprocessing: layout + (optional) quantization, folded when const --
    @desc.register_preprocessing("dense", operand="W", constant=True)
    def to_bf16(w):
        return jnp.asarray(w, jnp.bfloat16)

    @desc.register_preprocessing("dense", operand="W", constant=True, name="quantize_w_int8")
    def quantize_w_int8(w, scale=None):
        import numpy as np

        w = np.asarray(w)
        if scale is None:
            scale = max(float(np.max(np.abs(w))) / 127.0, 1e-8)
        return np.clip(np.round(w / scale), -128, 127).astype(np.int8)

    @desc.register_preprocessing("conv2d", operand="In", constant=False)
    def im2col_tpu(x, kh=3, kw=3, stride=1):
        import jax.lax as lax

        n, h, w_, c = x.shape
        patches = lax.conv_general_dilated_patches(
            x.astype(jnp.float32),
            filter_shape=(kh, kw),
            window_strides=(stride, stride),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        oh, ow = patches.shape[1], patches.shape[2]
        return patches.reshape(n * oh * ow, kh * kw * c)

    # -- core computes -------------------------------------------------------
    @desc.register_core_compute("tpu_gemm_bf16", op="dense")
    def dense_bf16(x, w, bias=None):
        acc = jnp.dot(
            x.astype(jnp.bfloat16),
            w.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        if bias is not None:
            acc = acc + bias
        return acc

    @desc.register_core_compute("tpu_qgemm_int8", op="matmul", quantized=True)
    def qdense_int8(x_q, w_q, bias, scale_in, scale_w, scale_out):
        acc = jnp.dot(
            x_q.astype(jnp.int32), w_q.astype(jnp.int32),
        )
        acc = acc + bias.astype(jnp.int32)
        requant = acc.astype(jnp.float32) * (scale_in * scale_w / scale_out)
        return jnp.clip(jnp.round(requant), -128, 127).astype(jnp.int8)

    @desc.register_core_compute(
        "tpu_grouped_qgemm_int8", op="grouped_dense", quantized=True
    )
    def grouped_qdense_int8(x_q, w_q, group_sizes):
        # rows sorted by expert, one int8 weight panel per expert
        import jax.lax as lax

        return lax.ragged_dot(x_q, w_q, group_sizes, preferred_element_type=jnp.int32)

    @desc.register_core_compute("tpu_gemm_conv", op="conv2d")
    def conv_as_gemm(cols, w, bias=None):
        return dense_bf16(cols, w, bias)

    # -- hw intrinsics --------------------------------------------------------
    @desc.register_hw_intrinsic(
        "tpu.mxu_matmul",
        kind="compute",
        tag="tpu_gemm_bf16",
        tile_limits={"N": MXU_DIM, "C": MXU_DIM, "K": MXU_DIM},
        dataflow="OS",
    )
    def mxu_matmul(a_tile, b_tile, acc_tile):
        import jax.lax as lax

        return acc_tile + lax.dot_general(
            a_tile,
            b_tile,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @desc.register_hw_intrinsic(
        "tpu.mxu_matmul_int8",
        kind="compute",
        tag="tpu_qgemm_int8",
        tile_limits={"N": MXU_DIM, "C": MXU_DIM, "K": MXU_DIM},
        dataflow="OS",
    )
    def mxu_matmul_int8(a_tile, b_tile, acc_tile):
        import jax.lax as lax

        return acc_tile + lax.dot_general(
            a_tile, b_tile, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    # the grouped GEMM runs each expert's row tiles on the int8 MXU path
    @desc.register_hw_intrinsic(
        "tpu.mxu_matmul_grouped",
        kind="compute",
        tag="tpu_grouped_qgemm_int8",
        tile_limits={"N": MXU_DIM, "C": MXU_DIM, "K": MXU_DIM},
        dataflow="OS",
    )
    def mxu_matmul_grouped(a_tile, b_tile, acc_tile):
        return mxu_matmul_int8(a_tile, b_tile, acc_tile)

    # conv reuses the bf16 MXU intrinsic after im2col.
    @desc.register_hw_intrinsic(
        "tpu.mxu_matmul_conv",
        kind="compute",
        tag="tpu_gemm_conv",
        tile_limits={"N": MXU_DIM, "C": MXU_DIM, "K": MXU_DIM},
        dataflow="OS",
    )
    def mxu_matmul_conv(a_tile, b_tile, acc_tile):
        return mxu_matmul(a_tile, b_tile, acc_tile)

    # Memory "intrinsics": on TPU these are not explicit instructions — the
    # mapping generator lowers them to Pallas BlockSpec index maps, and the
    # Mosaic pipeliner emits the HBM<->VMEM copies (double-buffered).
    @desc.register_hw_intrinsic(
        "tpu.vmem_load_in", kind="memory", operand="In", lowering="blockspec"
    )
    def vmem_load_in(block_shape, index_map):
        return ("blockspec", "In", block_shape, index_map)

    @desc.register_hw_intrinsic(
        "tpu.vmem_load_w", kind="memory", operand="W", lowering="blockspec"
    )
    def vmem_load_w(block_shape, index_map):
        return ("blockspec", "W", block_shape, index_map)

    @desc.register_hw_intrinsic(
        "tpu.vmem_store_out", kind="memory", operand="Out", lowering="blockspec"
    )
    def vmem_store_out(block_shape, index_map):
        return ("blockspec", "Out", block_shape, index_map)

    @desc.register_hw_intrinsic("tpu.dimension_semantics", kind="config")
    def dimension_semantics(arbitrary_dims=("C",)):
        # reduction grid dims must be 'arbitrary' for Mosaic correctness
        return ("dimension_semantics", arbitrary_dims)

    errs = desc.validate()
    assert not errs, errs
    return desc
