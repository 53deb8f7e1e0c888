"""Plain reference of the MusicGen-medium int8 layer stack in numpy.

Imports nothing of repro.  Integer GEMMs accumulate exactly in int32
(``bench.refops.imatmul``, XLA's dot, not repro's kernels); requantizing rounds
half to even and clips; the scores are dequantized in float32, the causal
mask is added in float32, and the softmax runs in float64 and is stored as
float32 before it is quantized, which is the precision the configuration
states for its host softmax.  The int8 residual adds wrap.  The batch is
computed one sequence at a time, so the reference fits beside nothing
else.  ``weight_bits=4`` is the control: the same model with its weights
held in int4.
"""

from __future__ import annotations

import numpy as np

from bench.refops import imatmul, qdense, quantize_weight

TAGS = ("q", "k", "v", "o", "f1", "f2")


def _softmax_f64(x: np.ndarray) -> np.ndarray:
    xf = x.astype(np.float64)
    e = np.exp(xf - np.max(xf, axis=-1, keepdims=True))
    return (e / np.sum(e, axis=-1, keepdims=True)).astype(np.float32)


def _layer(a, w, b, x, h, dh, mask):
    s, d = x.shape

    def heads(u):  # [S, D] -> [H, S, dh]
        return u.reshape(s, h, dh).transpose(1, 0, 2)

    q, k, v = (heads(qdense(x, w[t], b[t], a["rq_scale"])) for t in ("q", "k", "v"))
    scores = imatmul(q, k.transpose(0, 2, 1))
    masked = scores.astype(np.float32) * np.float32(a["score_scale"]) + mask
    p = _softmax_f64(masked)
    probs = np.clip(np.round(p / np.float32(a["probs_scale"])), -128, 127).astype(np.int8)
    ctx = imatmul(probs, v).astype(np.float64)
    ctx = np.clip(np.rint(ctx * a["ctx_scale"]), -128, 127).astype(np.int8)
    ctx = ctx.transpose(1, 0, 2).reshape(s, d)
    x = qdense(ctx, w["o"], b["o"], a["rq_scale"]) + x
    f = qdense(x, w["f1"], b["f1"], a["rq_scale"], clip_lo=0)
    return qdense(f, w["f2"], b["f2"], a["rq_scale"]) + x


def reference(cfg: dict, params: dict, x: np.ndarray, weight_bits: int = 8) -> np.ndarray:
    """``x``: int8 [n, S, D] sequences -> int8 [n, S, D] hidden states."""
    a = cfg["assumed"]
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    s = x.shape[1]
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = np.where(j <= i, np.float32(0.0), np.float32(-1e9)).astype(np.float32)
    layers = []
    for layer in range(cfg["num_hidden_layers"]):
        w = {
            t: quantize_weight(params[f"l{layer}.w_{t}"], a["w_scale"], weight_bits)
            for t in TAGS
        }
        b = {t: params[f"l{layer}.b_{t}"] for t in TAGS}
        layers.append((w, b))
    out = np.empty_like(x)
    for n in range(x.shape[0]):
        hs = x[n]
        for w, b in layers:
            hs = _layer(a, w, b, hs, h, d // h, mask)
        out[n] = hs
    return out
