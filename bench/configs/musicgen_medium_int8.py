"""MusicGen-medium decoder layers on repro: the model function handed to
``repro.compile``, its weights and inputs from the seed, and the GEMMs one
call issues.

Each layer is the zoo's ``transformer_block_fn`` generalised to heads:
q/k/v projections through the zoo's quantized dense chain
(``zoo._qdense_jnp``), heads reshaped into the batched dense
(``[B*H, S, head_dim]``), int8 scores, a causal additive mask before the
host softmax, quantized probabilities, int8 context, the output projection
and an int8 residual add, then a quantized FFN (fused quantized ReLU on the
expansion) and a second residual add.  Departures from the published model
are listed in the JSON file beside this one.
"""

from __future__ import annotations

import numpy as np

from bench.counting import Gemm

PROJ = ("q", "k", "v", "o")
MASK_BLOCKED = -1e9


def sample_shape(cfg: dict, traffic: dict) -> tuple[int, ...]:
    return (traffic["seq_len"], cfg["hidden_size"])


def test_sizes(cfg: dict, traffic: dict) -> None:
    """Cut to the sizes the CPU tests drive (in place)."""
    cfg.update(hidden_size=64, num_attention_heads=4, ffn_dim=128, num_hidden_layers=2)
    traffic.update(seq_len=16, samples_per_call=2, buckets=[2])


def _dims(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, d // h, cfg["ffn_dim"], cfg["num_hidden_layers"]


def _shapes(cfg):
    d, _, _, f, _ = _dims(cfg)
    return {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d), "f1": (d, f), "f2": (f, d)}


def causal_mask(seq: int) -> np.ndarray:
    i = np.arange(seq)[:, None]
    j = np.arange(seq)[None, :]
    return np.where(j <= i, 0.0, MASK_BLOCKED).astype(np.float32)


def make_params(cfg: dict, key, shape) -> dict[str, np.ndarray]:
    """Weights (out, in) on the int8 grid, int32 biases and the causal mask.
    Weights and biases are drawn as int8 and int32 in one jitted call on the
    default device from the JAX ``key``; the weights are handed to the model
    as float32 multiples of ``w_scale``, which its quantize maps back to the
    same int8."""
    import jax
    import jax.numpy as jnp

    a = cfg["assumed"]
    shapes = _shapes(cfg)
    n_layers = cfg["num_hidden_layers"]

    @jax.jit
    def make(key):
        out = {}
        keys = jax.random.split(key, 2 * len(shapes) * n_layers)
        i = 0
        for layer in range(n_layers):
            for tag, (d_in, d_out) in shapes.items():
                std = a["branch_gain"] / (a["rq_scale"] * d_in**0.5)
                w = jnp.round(jax.random.normal(keys[i], (d_out, d_in), jnp.float32) * std)
                out[f"l{layer}.w_{tag}"] = jnp.clip(w, -128, 127).astype(jnp.int8)
                out[f"l{layer}.b_{tag}"] = jax.random.randint(
                    keys[i + 1], (d_out,), -a["bias_range"], a["bias_range"], jnp.int32
                )
                i += 2
        return out

    params = {k: np.asarray(v) for k, v in make(key).items()}
    for k, v in params.items():
        if v.dtype == np.int8:
            params[k] = v.astype(np.float32) * np.float32(a["w_scale"])
    params["mask"] = causal_mask(shape[0])
    return params


def make_inputs(cfg: dict, rng: np.random.Generator, n: int, shape) -> np.ndarray:
    x = np.rint(rng.normal(0.0, cfg["assumed"]["input_std"], size=(n, *shape)))
    return np.clip(x, -128, 127).astype(np.int8)


def model_fn(cfg: dict):
    import jax
    import jax.numpy as jnp

    from repro.core import zoo
    from repro.frontend import nn as fnn

    a = cfg["assumed"]
    d, h, dh, _, n_layers = _dims(cfg)

    def musicgen_layers(x, params):
        seq = x.shape[-2]
        b = x.size // (seq * d)  # 1 for the per-sample trace

        def proj(u, layer, tag, clip_lo=-128):
            return zoo._qdense_jnp(
                u, params[f"l{layer}.w_{tag}"], params[f"l{layer}.b_{tag}"],
                w_scale=a["w_scale"], rq_scale=a["rq_scale"], clip_lo=clip_lo,
            )

        def split_heads(u):
            u = jnp.transpose(u.reshape(b, seq, h, dh), (0, 2, 1, 3))
            return u.reshape(b * h, seq, dh)

        for layer in range(n_layers):
            q, k, v = (split_heads(proj(x, layer, t)) for t in ("q", "k", "v"))
            scores = fnn.dense(q, jnp.transpose(k, (0, 2, 1)))
            masked = fnn.dequantize(scores, a["score_scale"]) + params["mask"]
            probs = fnn.quantize(jax.nn.softmax(masked), a["probs_scale"])
            ctx = fnn.requantize(fnn.dense(probs, v), a["ctx_scale"])
            ctx = jnp.transpose(ctx.reshape(b, h, seq, dh), (0, 2, 1, 3)).reshape(x.shape)
            x = proj(ctx, layer, "o") + x
            f = proj(x, layer, "f1", clip_lo=0)
            x = proj(f, layer, "f2") + x
        return x

    return musicgen_layers


def gemms(cfg: dict, shape, batch: int) -> list[Gemm]:
    """The GEMMs of one call over ``batch`` sequences of ``shape[0]`` tokens.
    The attention GEMMs count the whole square of scores, masked or not:
    that is the work the model issues."""
    seq = shape[0]
    _, h, dh, _, n_layers = _dims(cfg)
    m = batch * seq
    per_layer = [
        Gemm(tag, m, d_in, d_out, bias=True, residual=tag in ("o", "f2"))
        for tag, (d_in, d_out) in _shapes(cfg).items()
    ]
    per_layer += [
        Gemm("scores", seq, dh, seq, count=batch * h, out_bytes=4),
        Gemm("context", seq, seq, dh, count=batch * h, out_bytes=4),
    ]
    return [
        Gemm(f"l{i}.{g.name}", g.m, g.k, g.n, g.count, g.out_bytes, g.bias, g.residual)
        for i in range(n_layers)
        for g in per_layer
    ]
