"""Plain reference of the synthetic ``dense_f32`` configuration in numpy:
the hidden layers as ``bench.refops.qdense`` with ReLU, the last layer's
exact int32 sums dequantized in float32 and GELU (tanh form) in float32.
``weight_bits=4`` is the control."""

from __future__ import annotations

import numpy as np

from bench.refops import imatmul, qdense, quantize_weight


def _gelu_tanh(x: np.ndarray) -> np.ndarray:
    c = np.float32(np.sqrt(2.0 / np.pi))
    return np.float32(0.5) * x * (np.float32(1.0) + np.tanh(c * (x + np.float32(0.044715) * x**3)))


def reference(cfg: dict, params: dict, x: np.ndarray, weight_bits: int = 8) -> np.ndarray:
    """``x``: int8 [n, S, D] -> float32 [n, S, D_out]."""
    a = cfg["assumed"]
    h = x.reshape(-1, x.shape[-1])
    last = len(cfg["layer_widths"]) - 2
    for i in range(last):
        w_q = quantize_weight(params[f"w{i}"], a["w_scale"], weight_bits)
        h = qdense(h, w_q, params[f"b{i}"], a["rq_scale"], clip_lo=0)
    w_q = quantize_weight(params[f"w{last}"], a["w_scale"], weight_bits)
    acc = imatmul(h, w_q) + params[f"b{last}"].astype(np.int64)
    y = _gelu_tanh(acc.astype(np.float32) * np.float32(a["out_scale"]))
    return y.reshape(x.shape[:-1] + (y.shape[-1],))
