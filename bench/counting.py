"""Operations and bytes of GEMM work, counted from a configuration's shapes.

Each configuration lists the GEMMs one call issues (``gemms(cfg, shape,
batch)`` in ``bench/configs/<name>.py``); these functions turn that list
into operations (2 per multiply-add), the bytes a GEMM must move at the
least (operands read once, result written once), and the least time the
chip could take for it.  The counts follow the model, not the program: a
padded tile or a GEMM replayed per head costs the program more time but
counts the same here, so a later change that does the same work another
way is read on the same scale.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Gemm:
    """``count`` independent GEMMs x[m, k] @ w[k, n] in int8."""

    name: str
    m: int
    k: int
    n: int
    count: int = 1
    out_bytes: int = 1  # int8 after a fused requantize, 4 for a raw int32
    bias: bool = False  # int32 bias row
    residual: bool = False  # int8 residual read in the epilogue

    def ops(self) -> int:
        return 2 * self.m * self.k * self.n * self.count

    def bytes(self) -> int:
        one = self.m * self.k + self.k * self.n + self.m * self.n * self.out_bytes
        one += 4 * self.n * self.bias + self.m * self.n * self.residual
        return one * self.count

    def ideal_s(self, peak_ops: float, peak_bytes: float) -> float:
        """The larger of compute time at peak and memory time at peak."""
        return max(self.ops() / peak_ops, self.bytes() / peak_bytes)


def total_ops(gemms: list[Gemm]) -> int:
    return sum(g.ops() for g in gemms)


def ideal_s(gemms: list[Gemm], peak_ops: float, peak_bytes: float) -> float:
    return sum(g.ideal_s(peak_ops, peak_bytes) for g in gemms)
