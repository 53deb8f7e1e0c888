"""Plain reference of the Mixtral-8x7B int8 layer stack in numpy.

Imports nothing of repro.  It follows the published block: RMSNorm, GQA
with rotary positions (each query head against its KV head, h // group),
residual, RMSNorm, router softmax over the top-2 logits, the sum over the
two experts of the gate times down(silu(gate) * up), residual.  Integer
GEMMs accumulate exactly in int32 (``bench.refops.imatmul``, XLA's dot, not
repro's kernels; an expert's rows padded with zero rows to a few shapes);
requantizing rounds half to even and clips; the host
math runs the formulas of the configuration's ``assumed.formulas`` in
float64, in their order; the attention softmax runs in float64 after a
float32 dequantize and mask add and is stored as float32, as the
configuration states.  Top-2 takes the lower expert index of two equal
logits.  The int8 residual adds wrap.  The batch is computed one sequence
at a time.  ``weight_bits=4`` is the control: the same model with its
weights held in int4 (each int8 weight rounded to a multiple of 16).
"""

from __future__ import annotations

import math

import numpy as np

from bench.refops import imatmul

TAGS = ("q", "k", "v", "o", "router", "gate_up", "down")


def _int8(y: np.ndarray) -> np.ndarray:
    return np.clip(np.rint(y), -128, 127).astype(np.int8)


def _coarse(w: np.ndarray, bits: int) -> np.ndarray:
    """An int8 weight held in ``bits`` bits: a grid 2**(8 - bits) times
    coarser over the same range."""
    if bits == 8:
        return w
    step = 2 ** (8 - bits)
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1) - 1
    return (np.clip(np.round(w / step), lo, hi) * step).astype(np.int8)


#: an expert's routed rows go to the GEMM padded with zero rows to a
#: multiple of this, so a few shapes serve every routing (each shape is
#: compiled once); a row's result does not depend on the others
ROW_PAD = 128


def _proj(x: np.ndarray, w: np.ndarray, rq_scale: float) -> np.ndarray:
    return _int8(imatmul(x, w).astype(np.float64) * rq_scale)


def _expert_proj(x: np.ndarray, w: np.ndarray, rq_scale: float) -> np.ndarray:
    pad = -x.shape[0] % ROW_PAD
    xp = np.concatenate([x, np.zeros((pad, x.shape[1]), x.dtype)]) if pad else x
    return _proj(xp, w, rq_scale)[: x.shape[0]]


def _rms_norm(x, s, out_scale, eps):
    xi = x.astype(np.int64)
    ss = np.sum(xi * xi, axis=-1, keepdims=True).astype(np.float64)
    r = 1.0 / np.sqrt(ss * (s * s) / x.shape[-1] + eps)
    return _int8((xi.astype(np.float64) * s) * r / out_scale)


def _tables(seq, head_dim, theta):
    inv_freq = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    emb = np.outer(np.arange(seq, dtype=np.float64), inv_freq)
    emb = np.concatenate([emb, emb], axis=-1)
    return np.cos(emb).astype(np.float32), np.sin(emb).astype(np.float32)


def _rope(x, cos, sin, s, out_scale):
    """``x[H, S, dh]`` int8 rotated by position."""
    xf = x.astype(np.float64) * s
    h = x.shape[-1] // 2
    rot = np.concatenate([-xf[..., h:], xf[..., :h]], axis=-1)
    return _int8((xf * cos.astype(np.float64) + rot * sin.astype(np.float64)) / out_scale)


def _softmax_f64(x: np.ndarray) -> np.ndarray:
    xf = x.astype(np.float64)
    e = np.exp(xf - np.max(xf, axis=-1, keepdims=True))
    return (e / np.sum(e, axis=-1, keepdims=True)).astype(np.float32)


def _attention(cfg, w, hs, cos, sin, mask):
    a = cfg["assumed"]
    s, d = hs.shape
    n_h, n_kv, dh = cfg["num_attention_heads"], cfg["num_key_value_heads"], a["head_dim"]
    act = a["act_scale"]
    q_scale = a["score_scale"] * math.sqrt(dh) / act
    h = _rms_norm(hs, act, a["norm_scale"], cfg["rms_norm_eps"])
    q = _proj(h, w["q"], a["rq_scale"]).reshape(s, n_h, dh).transpose(1, 0, 2)
    k = _proj(h, w["k"], a["rq_scale"]).reshape(s, n_kv, dh).transpose(1, 0, 2)
    v = _proj(h, w["v"], a["rq_scale"]).reshape(s, n_kv, dh).transpose(1, 0, 2)
    q = _rope(q, cos, sin, act, q_scale)
    k = _rope(k, cos, sin, act, act)
    kv_of = np.arange(n_h) // (n_h // n_kv)  # the source's repeat_kv
    scores = imatmul(q, k[kv_of].transpose(0, 2, 1))
    masked = scores.astype(np.float32) * np.float32(a["score_scale"]) + mask
    p = _softmax_f64(masked)
    probs = np.clip(np.round(p / np.float32(a["probs_scale"])), -128, 127).astype(np.int8)
    ctx = imatmul(probs, v[kv_of]).astype(np.float64)
    ctx = np.clip(np.rint(ctx * a["ctx_scale"]), -128, 127).astype(np.int8)
    ctx = ctx.transpose(1, 0, 2).reshape(s, d)
    return _proj(ctx, w["o"], a["rq_scale"]) + hs


def _moe(cfg, w, hs):
    a = cfg["assumed"]
    act, top_k = a["act_scale"], cfg["num_experts_per_tok"]
    h = _rms_norm(hs, act, a["norm_scale"], cfg["rms_norm_eps"])
    logits = imatmul(h, w["router"])  # exact int32, as int64
    top = np.argsort(-logits, axis=-1, kind="stable")[:, :top_k]
    chosen = np.take_along_axis(logits, top, axis=-1).astype(np.float64) * a["logit_scale"]
    e = np.exp(chosen - np.max(chosen, axis=-1, keepdims=True))
    gates = e / np.sum(e, axis=-1, keepdims=True)
    f = cfg["intermediate_size"]
    y = np.zeros((*top.shape, hs.shape[-1]), np.float64)  # [S, top_k, D]
    for expert in np.unique(top):
        tok, slot = np.nonzero(top == expert)
        gu = _expert_proj(h[tok], w["gate_up"][expert], a["rq_scale"])
        g = gu[:, :f].astype(np.float64) * act
        u = gu[:, f:].astype(np.float64) * act
        hidden = _int8(g / (1.0 + np.exp(-g)) * u / act)
        y[tok, slot] = _expert_proj(hidden, w["down"][expert], a["rq_scale"])
    acc = gates[:, 0:1] * y[:, 0]
    for j in range(1, top_k):
        acc = acc + gates[:, j : j + 1] * y[:, j]
    return _int8(acc * a["combine_scale"]) + hs


def reference(cfg: dict, params: dict, x: np.ndarray, weight_bits: int = 8) -> np.ndarray:
    """``x``: int8 [n, S, D] sequences -> int8 [n, S, D] hidden states."""
    s = x.shape[1]
    cos, sin = _tables(s, cfg["assumed"]["head_dim"], cfg["rope_theta"])
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    mask = np.where(j <= i, np.float32(0.0), np.float32(-1e9)).astype(np.float32)
    layers = [
        {t: _coarse(params[f"l{layer}.w_{t}"], weight_bits) for t in TAGS}
        for layer in range(cfg["num_hidden_layers"])
    ]
    out = np.empty_like(x)
    for n in range(x.shape[0]):
        hs = x[n]
        for w in layers:
            hs = _moe(cfg, w, _attention(cfg, w, hs, cos, sin, mask))
        out[n] = hs
    return out
