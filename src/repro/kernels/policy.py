"""Scheduled-kernel policy: routes model GEMMs through the paper's backend.

This is how the compiler-integration contribution becomes *first-class* in
the LM substrate: when a policy is active, every `repro.models.layers.dense`
call consults the extended-CoSA scheduler (via the generated backend) for
its (m, k, n, dtype) workload and executes through the scheduled Pallas
kernel; otherwise it falls back to plain XLA einsum — exactly the paper's
host-fallback semantics.

Schedules are resolved at trace time (shapes are static under jit) and
cached by workload key inside the scheduler.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

import jax.numpy as jnp

from repro.core.arch_spec import GemmWorkload
from repro.core.lowering import pallas_interpret_mode
from repro.core.mapping import MappingGenerator
from repro.kernels.gemm import GemmKernelConfig

_lock = threading.Lock()
_POLICY: "ScheduledKernelPolicy | None" = None


@dataclass
class ScheduledKernelPolicy:
    backend: object  # repro.core.pipeline.CompilerBackend
    # interpret-mode Pallas unless JAX's backend is a TPU
    interpret: bool = field(default_factory=pallas_interpret_mode)
    min_m: int = 8  # skip degenerate GEMMs (decode gemv handled by XLA)

    def config_for(
        self, m: int, k: int, n: int, dtype, *, has_bias: bool
    ) -> GemmKernelConfig | None:
        if m < self.min_m:
            return None
        elem = jnp.dtype(dtype).itemsize
        wl = GemmWorkload(
            N=m, C=k, K=n, in_bytes=elem, w_bytes=elem, out_bytes=4, name="lm_gemm"
        )
        try:
            result = self.backend.scheduler.schedule(wl)
        except RuntimeError:
            return None
        mg: MappingGenerator = self.backend.mapping_gen
        return mg.to_kernel_config(
            result.best,
            acc_dtype="float32",
            out_dtype=str(jnp.dtype(dtype)),
            interpret=self.interpret,
            has_bias=has_bias,
        )


def set_policy(policy: ScheduledKernelPolicy | None) -> None:
    global _POLICY
    with _lock:
        _POLICY = policy


def get_policy() -> ScheduledKernelPolicy | None:
    return _POLICY


class scheduled_kernels:
    """Context manager: `with scheduled_kernels(backend): model.apply(...)`."""

    def __init__(self, backend, interpret: bool | None = None):
        if interpret is None:
            interpret = pallas_interpret_mode()
        self._policy = ScheduledKernelPolicy(backend=backend, interpret=interpret)

    def __enter__(self):
        set_policy(self._policy)
        return self._policy

    def __exit__(self, *exc):
        set_policy(None)
        return False
