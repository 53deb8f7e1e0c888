"""Mixtral-8x7B decoder layers on repro: the model function handed to
``repro.compile``, its int8 weights and inputs from the seed, and the GEMMs
one call issues.

Each layer follows the published block in int8: RMSNorm, q/k/v projections
without bias, rotary positions on q and k, grouped-query attention with the
4 query heads of each KV group stacked along M (int8 scores, a causal mask
before the softmax, quantized probabilities, int8 context), the output
projection and an int8 residual add; then RMSNorm, the router's int32
logits, its top-2 and the rows sorted by expert, the experts' gate|up
panel as one grouped GEMM, SiLU-mul, the down panel as a second grouped
GEMM, the gate-weighted combine and a second residual add.  The float
steps are ``repro.frontend.nn``'s named host ops; departures from the
published model are listed in the JSON file beside this one.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from bench.counting import Gemm

PROJ = ("q", "k", "v", "o")
MASK_BLOCKED = -1e9


def sample_shape(cfg: dict, traffic: dict) -> tuple[int, ...]:
    return (traffic["seq_len"], cfg["hidden_size"])


def test_sizes(cfg: dict, traffic: dict) -> None:
    """Cut to the sizes the CPU tests drive (in place)."""
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               intermediate_size=128, num_hidden_layers=2)
    cfg["assumed"] = dict(cfg["assumed"], head_dim=16)
    traffic.update(seq_len=16, samples_per_call=2, buckets=[2])


def dims(cfg: dict) -> dict:
    """The block's sizes: hidden ``d``, query heads ``h``, KV heads ``kv``,
    query heads per KV head ``group``, ``head_dim``, expert width ``ffn``,
    ``experts``, ``top_k``, ``layers``."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "d": cfg["hidden_size"], "h": h, "kv": kv, "group": h // kv,
        "head_dim": cfg["assumed"]["head_dim"], "ffn": cfg["intermediate_size"],
        "experts": cfg["num_local_experts"], "top_k": cfg["num_experts_per_tok"],
        "layers": cfg["num_hidden_layers"],
    }


def q_rope_scale(cfg: dict) -> float:
    """The query's rope quantize scale, with the scores' 1/sqrt(head_dim)
    folded in: ``score_scale * sqrt(head_dim) / act_scale``."""
    a = cfg["assumed"]
    return a["score_scale"] * math.sqrt(a["head_dim"]) / a["act_scale"]


def weight_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Per layer: int8 weights in (in, out) layout; the experts' as one
    ``[experts, in, out]`` array each, gate|up side by side along out."""
    m = dims(cfg)
    d, kvd, f, e = m["d"], m["kv"] * m["head_dim"], m["ffn"], m["experts"]
    return {"q": (d, d), "k": (d, kvd), "v": (d, kvd), "o": (d, d),
            "router": (d, e), "gate_up": (e, d, 2 * f), "down": (e, f, d)}


def causal_mask(seq: int, group: int) -> np.ndarray:
    """The causal mask of one KV group's stacked query heads: ``[group *
    seq, seq]``, the plain mask repeated along rows."""
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    mask = np.where(j <= i, 0.0, MASK_BLOCKED).astype(np.float32)
    return np.tile(mask, (group, 1))


def rope_tables(seq: int, head_dim: int, theta: float) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin ``[seq, head_dim]``: angles in float64, stored as float32."""
    inv_freq = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    freqs = np.outer(np.arange(seq, dtype=np.float64), inv_freq)
    emb = np.concatenate([freqs, freqs], axis=-1)
    return np.cos(emb).astype(np.float32), np.sin(emb).astype(np.float32)


def make_params(cfg: dict, key, shape) -> dict[str, np.ndarray]:
    """int8 weights drawn on the default device from the JAX ``key``, one
    matrix (one expert's, for the experts) at a time, each brought to a
    host int8 array: no float32 copy of a layer's experts ever exists.
    Plus the stacked causal mask and the rope tables."""
    import jax
    import jax.numpy as jnp

    # a program without the routed-expert ops fails here, before any draw
    from repro.frontend.nn import grouped_dense, moe_route  # noqa: F401

    a = cfg["assumed"]
    m = dims(cfg)

    @functools.partial(jax.jit, static_argnames=("shape", "std"))
    def draw(key, shape, std):
        w = jnp.round(jax.random.normal(key, shape, jnp.float32) * std)
        return jnp.clip(w, -128, 127).astype(jnp.int8)

    params: dict[str, np.ndarray] = {}
    for layer in range(m["layers"]):
        for i, (tag, wshape) in enumerate(weight_shapes(cfg).items()):
            fan_in = wshape[-2]
            std = (a["router_std"] if tag == "router"
                   else a["branch_gain"] / (a["rq_scale"] * fan_in**0.5))
            k = jax.random.fold_in(jax.random.fold_in(key, layer), i)
            if len(wshape) == 2:
                w = np.asarray(draw(k, wshape, float(std)))
            else:
                w = np.empty(wshape, np.int8)
                for e in range(wshape[0]):
                    w[e] = np.asarray(draw(jax.random.fold_in(k, e), wshape[1:], float(std)))
            params[f"l{layer}.w_{tag}"] = w
    params["mask"] = causal_mask(shape[0], m["group"])
    params["rope_cos"], params["rope_sin"] = rope_tables(
        shape[0], m["head_dim"], cfg["rope_theta"])
    return params


def make_inputs(cfg: dict, rng: np.random.Generator, n: int, shape) -> np.ndarray:
    x = np.rint(rng.normal(0.0, cfg["assumed"]["input_std"], size=(n, *shape)))
    return np.clip(x, -128, 127).astype(np.int8)


def model_fn(cfg: dict):
    import jax
    import jax.numpy as jnp

    from repro.frontend import nn as fnn

    a = cfg["assumed"]
    m = dims(cfg)
    d, kv, group, dh = m["d"], m["kv"], m["group"], m["head_dim"]
    act, eps = a["act_scale"], cfg["rms_norm_eps"]
    q_scale = q_rope_scale(cfg)

    def mixtral_layers(x, params):
        seq = x.shape[-2]
        b = x.size // (seq * d)  # 1 for the per-sample trace
        cos, sin = params["rope_cos"], params["rope_sin"]

        def proj(u, layer, tag):
            acc = fnn.dense(u, params[f"l{layer}.w_{tag}"])
            return jnp.clip(fnn.requantize(acc, a["rq_scale"]), -128, 127)

        def experts(u, layer, tag, sizes):
            acc = fnn.grouped_dense(u, params[f"l{layer}.w_{tag}"], sizes)
            return fnn.requantize(acc, a["rq_scale"])

        for layer in range(m["layers"]):
            h = fnn.rms_norm(x, act, a["norm_scale"], eps)
            # [b, kv, group, S, dh]: a KV group's query heads stack along M
            q = proj(h, layer, "q").reshape(b, seq, kv, group, dh).transpose(0, 2, 3, 1, 4)
            k = proj(h, layer, "k").reshape(b, seq, kv, dh).transpose(0, 2, 1, 3)
            v = proj(h, layer, "v").reshape(b, seq, kv, dh).transpose(0, 2, 1, 3)
            q = fnn.rope(q, cos, sin, act, q_scale).reshape(b * kv, group * seq, dh)
            k = fnn.rope(k, cos, sin, act, act).reshape(b * kv, seq, dh)
            scores = fnn.dense(q, jnp.transpose(k, (0, 2, 1)))
            masked = fnn.dequantize(scores, a["score_scale"]) + params["mask"]
            probs = fnn.quantize(jax.nn.softmax(masked), a["probs_scale"])
            ctx = fnn.requantize(fnn.dense(probs, v.reshape(b * kv, seq, dh)), a["ctx_scale"])
            ctx = ctx.reshape(b, kv, group, seq, dh).transpose(0, 3, 1, 2, 4).reshape(x.shape)
            x = proj(ctx, layer, "o") + x

            h = fnn.rms_norm(x, act, a["norm_scale"], eps).reshape(b * seq, d)
            logits = fnn.dense(h, params[f"l{layer}.w_router"])
            idx = fnn.moe_route(logits, top_k=m["top_k"])
            sizes = fnn.moe_group_sizes(idx, n_experts=m["experts"])
            gate_up = experts(fnn.moe_dispatch(h, idx), layer, "gate_up", sizes)
            y = experts(fnn.silu_mul(gate_up, act, act), layer, "down", sizes)
            moe = fnn.moe_combine(y, idx, logits, a["logit_scale"], a["combine_scale"])
            x = moe.reshape(x.shape) + x
        return x

    return mixtral_layers


def gemms(cfg: dict, shape, batch: int) -> list[Gemm]:
    """The GEMMs of one call over ``batch`` sequences of ``shape[0]``
    tokens.  The attention GEMMs count every query head's square of scores,
    masked or not, as the source (K and V repeated to 32 heads) issues
    them.  The expert GEMMs count ``top_k * tokens / experts`` rows for each
    of the ``experts``: their operations and bytes are those of any routing
    that gives every expert a row."""
    seq = shape[0]
    m = dims(cfg)
    tokens = batch * seq
    rows = m["top_k"] * tokens // m["experts"]
    per_layer = [
        Gemm(tag, tokens, wshape[0], wshape[1], residual=tag == "o")
        for tag, wshape in weight_shapes(cfg).items() if tag in PROJ
    ]
    per_layer += [
        Gemm("router", tokens, m["d"], m["experts"], out_bytes=4),
        Gemm("scores", seq, m["head_dim"], seq, count=batch * m["h"], out_bytes=4),
        Gemm("context", seq, seq, m["head_dim"], count=batch * m["h"], out_bytes=4),
        Gemm("expert_gate_up", rows, m["d"], 2 * m["ffn"], count=m["experts"]),
        Gemm("expert_down", rows, m["ffn"], m["d"], count=m["experts"]),
    ]
    return [
        Gemm(f"l{i}.{g.name}", g.m, g.k, g.n, g.count, g.out_bytes, g.bias, g.residual)
        for i in range(m["layers"])
        for g in per_layer
    ]
