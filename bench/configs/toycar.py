"""ToyCar on repro: the model function handed to ``repro.compile``, its
weights and inputs from the seed, and the GEMMs one call issues.

The function is the zoo's quantized dense chain (``zoo._qdense_jnp``:
quantize the float weight, dense, bias, requantize, clip) once per layer of
``layer_widths``; every layer but the last clips at 0, the zoo's fused
quantized ReLU.
"""

from __future__ import annotations

import numpy as np

from bench.counting import Gemm


def sample_shape(cfg: dict, traffic: dict) -> tuple[int, ...]:
    return (1, cfg["layer_widths"][0])


def _layers(cfg):
    w = cfg["layer_widths"]
    return list(zip(w[:-1], w[1:]))


def make_params(cfg: dict, key, shape) -> dict[str, np.ndarray]:
    """Weights (out, in) on the int8 grid and int32 biases, drawn as int8
    and int32 in one jitted call on the default device from the JAX
    ``key``; the weights are handed to the model as float32 multiples of
    ``w_scale``, which its quantize maps back to the same int8."""
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
    from repro.core import zoo

    a = cfg["assumed"]
    n_layers = len(_layers(cfg))

    def toycar(x, params):
        h = x
        for i in range(n_layers):
            h = zoo._qdense_jnp(
                h, params[f"w{i}"], params[f"b{i}"],
                w_scale=a["w_scale"], rq_scale=a["rq_scale"],
                clip_lo=0 if i < n_layers - 1 else -128,
            )
        return h

    return toycar


def gemms(cfg: dict, shape, batch: int) -> list[Gemm]:
    """The GEMMs of one call over ``batch`` windows."""
    return [
        Gemm(f"dense{i}", batch, d_in, d_out, bias=True)
        for i, (d_in, d_out) in enumerate(_layers(cfg))
    ]
