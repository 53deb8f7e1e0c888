"""A synthetic configuration with float answers, added the way a new
configuration is added: an int8 dense chain on repro whose hidden layers
requantize with the fused quantized ReLU and whose last layer dequantizes
its int32 sums to float32 and applies GELU (tanh form)."""

from __future__ import annotations

import numpy as np

from bench.counting import Gemm


def sample_shape(cfg: dict, traffic: dict) -> tuple[int, ...]:
    return (traffic["seq_len"], cfg["layer_widths"][0])


def test_sizes(cfg: dict, traffic: dict) -> None:
    """Already at the sizes the CPU tests drive."""


def _layers(cfg):
    w = cfg["layer_widths"]
    return list(zip(w[:-1], w[1:]))


def make_params(cfg: dict, key, shape) -> dict[str, np.ndarray]:
    """Weights (out, in) on the int8 grid, handed over as float32 multiples
    of ``w_scale``, and int32 biases, drawn in one jitted call."""
    import jax
    import jax.numpy as jnp

    a = cfg["assumed"]
    layers = _layers(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 * len(layers))
        out = {}
        for i, (d_in, d_out) in enumerate(layers):
            std = a["weight_gain"] / (a["rq_scale"] * d_in**0.5)
            w = jnp.round(jax.random.normal(keys[2 * i], (d_out, d_in), jnp.float32) * std)
            out[f"w{i}"] = jnp.clip(w, -128, 127).astype(jnp.int8)
            out[f"b{i}"] = jax.random.randint(
                keys[2 * i + 1], (d_out,), -a["bias_range"], a["bias_range"], jnp.int32
            )
        return out

    params = {k: np.asarray(v) for k, v in make(key).items()}
    for i in range(len(layers)):
        params[f"w{i}"] = params[f"w{i}"].astype(np.float32) * np.float32(a["w_scale"])
    return params


def make_inputs(cfg: dict, rng: np.random.Generator, n: int, shape) -> np.ndarray:
    return rng.integers(-128, 128, size=(n, *shape), dtype=np.int8)


def model_fn(cfg: dict):
    import jax
    import jax.numpy as jnp

    from repro.core import zoo
    from repro.frontend import nn as fnn

    a = cfg["assumed"]
    n_layers = len(_layers(cfg))

    def dense_f32(x, params):
        h = x
        for i in range(n_layers - 1):
            h = zoo._qdense_jnp(h, params[f"w{i}"], params[f"b{i}"], w_scale=a["w_scale"],
                                rq_scale=a["rq_scale"], clip_lo=0)
        last = n_layers - 1
        w_q = fnn.quantize(jnp.transpose(params[f"w{last}"]), a["w_scale"])
        acc = fnn.dense(h, w_q) + params[f"b{last}"]
        return jax.nn.gelu(fnn.dequantize(acc, a["out_scale"]), approximate=True)

    return dense_f32


def gemms(cfg: dict, shape, batch: int) -> list[Gemm]:
    layers = _layers(cfg)
    return [
        Gemm(f"dense{i}", batch * shape[0], d_in, d_out, bias=True,
             out_bytes=4 if i == len(layers) - 1 else 1)
        for i, (d_in, d_out) in enumerate(layers)
    ]
