"""Mapping Generator (paper §3.3): Schedule -> executable kernel mapping.

In the paper, CoSA's YAML output (tile factors + per-level loop order) is
applied as TIR schedule primitives, then TIR stages are rewritten with the
hardware intrinsics produced by the Hardware Intrinsic Generator
(tensorization).

On the TPU target the same information lowers to a ``pl.pallas_call``:

  * buffer-level tile sizes  ->  BlockSpec block shapes (VMEM tiles),
  * DRAM-level loop order    ->  grid iteration order (OS: m outer /
                                 WS: n outer so the weight panel is
                                 revisited across m),
  * PE-level factors         ->  the MXU ``dot_general`` "instruction"
                                 inside the kernel body (Eq. 1 guarantees
                                 they fit the 128x128 array),
  * double buffering         ->  Mosaic's automatic pipelining (the
                                 scheduler already halved usable VMEM),
  * epilogue attrs           ->  fused requantize/clip or activation.

For the Gemmini case study the same Schedule drives the cycle model
directly (there is no Pallas backend for a RISC-V RoCC accelerator); the
mapping generator emits a numpy executor that tensorizes with the
registered compute intrinsic, tile by tile — this is what the paper's
tests execute on the cycle-accurate simulator.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from repro.core.accel import AcceleratorDescription, IntrinsicDef
from repro.core.arch_spec import GEMM_DIMS
from repro.core.schedule import Schedule
from repro.kernels.gemm import GemmKernelConfig


@dataclass
class MappingGenerator:
    desc: AcceleratorDescription

    # -- TPU path: Schedule -> Pallas kernel config -------------------------
    def to_kernel_config(
        self,
        schedule: Schedule,
        *,
        acc_dtype: str = "float32",
        out_dtype: str = "float32",
        epilogue: dict[str, Any] | None = None,
        interpret: bool = False,
        has_bias: bool = False,
    ) -> GemmKernelConfig:
        buf = self.desc.arch.buffered_levels()
        level = buf[0] if buf else 0
        # paper dims N/C/K == kernel dims m/k/n
        block_m = schedule.tile(level, "N")
        block_k = schedule.tile(level, "C")
        block_n = schedule.tile(level, "K")
        if not interpret:
            # MXU alignment floor: never emit sub-lane blocks on real
            # Mosaic.  Interpret mode keeps the schedule's exact buffer
            # tiles (any block shape is legal in emulation), so the CPU CI
            # executes the same tiling the cycle model priced.
            block_m = max(block_m, 8)
            block_k = max(block_k, 128)
            block_n = max(block_n, 128)
        ep = epilogue or {}
        return GemmKernelConfig(
            block_m=block_m,
            block_k=block_k,
            block_n=block_n,
            dataflow=schedule.dataflow,
            acc_dtype=acc_dtype,
            out_dtype=out_dtype,
            requant_scale=ep.get("requant_scale"),
            clip_lo=ep.get("clip_lo"),
            clip_hi=ep.get("clip_hi"),
            activation=ep.get("activation"),
            has_bias=has_bias,
            interpret=interpret,
            vmem_limit_bytes=self.desc.kernel_vmem_limit_bytes,
        )

    # -- Gemmini path: Schedule -> tensorized tiled executor ----------------
    def to_tiled_executor(
        self, schedule: Schedule, intrinsic: IntrinsicDef
    ) -> Callable[[np.ndarray, np.ndarray], np.ndarray]:
        """Emit a loop-nest executor that applies the registered compute
        intrinsic per PE tile — the tensorization step, in numpy, faithful
        to the generated loop structure (used for functional validation of
        Gemmini schedules against the graph reference)."""
        pe = schedule.pe_tile()
        tm, tk, tn = pe["N"], pe["C"], pe["K"]
        pm = schedule.padded("N")
        pk = schedule.padded("C")
        pn = schedule.padded("K")
        intr_fn = intrinsic.fn

        def pad_w(w: np.ndarray) -> np.ndarray:
            k, n = w.shape
            wp = np.zeros((pk, pn), dtype=w.dtype)
            wp[:k, :n] = w
            return wp

        def run_prepadded(x: np.ndarray, wp: np.ndarray, n: int) -> np.ndarray:
            """Inner loop nest over an already-padded weight panel: the
            execution plan pre-pads constant weights once at plan-build time
            (stationary operands stay resident across calls)."""
            m, k = x.shape
            xp = np.zeros((pm, pk), dtype=x.dtype)
            xp[:m, :k] = x
            acc = np.zeros((pm, pn), dtype=np.int64)
            for i0 in range(0, pm, tm):
                for j0 in range(0, pn, tn):
                    tile_acc = np.zeros((tm, tn), dtype=np.int64)
                    for k0 in range(0, pk, tk):
                        tile_acc = intr_fn(
                            xp[i0 : i0 + tm, k0 : k0 + tk],
                            wp[k0 : k0 + tk, j0 : j0 + tn],
                            tile_acc,
                        )
                    acc[i0 : i0 + tm, j0 : j0 + tn] = tile_acc
            return acc[:m, :n]

        def run(x: np.ndarray, w: np.ndarray) -> np.ndarray:
            return run_prepadded(x, pad_w(w), w.shape[1])

        run.pad_w = pad_w
        run.prepadded = run_prepadded
        return run

    def describe(self, schedule: Schedule) -> str:
        """Human-readable mapping report (what CoSA's YAML + TIR transform
        sequence would contain)."""
        cfg_lines = [schedule.describe()]
        mem_intrs = [i.name for i in self.desc.memory_intrinsics()]
        cfg_lines.append(f"  memory intrinsics: {mem_intrs}")
        n_tiles = math.prod(
            schedule.trips(self.desc.arch.buffered_levels()[0] if self.desc.arch.buffered_levels() else 0, j)
            for j in GEMM_DIMS
        )
        cfg_lines.append(f"  outer tiles: {n_tiles}")
        return "\n".join(cfg_lines)
