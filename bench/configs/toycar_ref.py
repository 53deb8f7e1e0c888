"""Plain reference of the ToyCar configuration in numpy.

Imports nothing of repro.  Per layer: quantize the float weight to int8
(round half to even, clip to [-128, 127]), exact integer GEMM with int32
accumulation (XLA's dot through jax.numpy, not repro's kernels), int32 bias,
requantize by rounding half to even, clip to int8, and at 0 (ReLU) after
every layer but the last.  ``weight_bits=4`` is
the control: the same model with its weights held in int4, the next
precision below the int8 the configuration states.
"""

from __future__ import annotations

import numpy as np

from bench.refops import qdense, quantize_weight


def reference(cfg: dict, params: dict, x: np.ndarray, weight_bits: int = 8) -> np.ndarray:
    """``x``: int8 [n, 1, 640] windows -> int8 [n, 1, 640] outputs."""
    a = cfg["assumed"]
    h = x.reshape(-1, x.shape[-1])
    n_layers = len(cfg["layer_widths"]) - 1
    for i in range(n_layers):
        w_q = quantize_weight(params[f"w{i}"], a["w_scale"], weight_bits)
        lo = 0 if i < n_layers - 1 else -128
        h = qdense(h, w_q, params[f"b{i}"], a["rq_scale"], clip_lo=lo)
    return h.reshape(x.shape[:-1] + (h.shape[-1],))
