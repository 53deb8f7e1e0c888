"""Bring-up check: the compiled ToyCar server and the scheduled Pallas GEMMs
at width, on one TPU chip.

    python chip_smoke.py

One process, one chip, no arguments.  Phases, in order:

  * serve — ``repro.compile("toycar_mlp", Target("tpu_v5e", batch_size=16))``
    through the traced-JAX frontend (the path ``python -m repro.launch.serve
    --zoo toycar_mlp --target tpu_v5e:optimized --batch 16`` takes), checks
    that every accelerator step runs the Mosaic-compiled kernel, answers 64
    requests through ``repro.serve.MicroBatcher`` and compares every answer
    bit for bit with ``ir.execute_graph`` on the hand-built golden graph;
  * kernel at width — the scheduled bf16 GEMM 4096x4096x4096 and the int8
    qGEMM 4096x8192x4096, with the tiles CoSA picks from the ``tpu_v5e``
    description, against ``repro.kernels.ref`` on the device.

There is no CPU fallback: without a TPU the script exits non-zero before any
phase and prints no result.  Any failed check raises.  The last line of
standard output is one JSON object naming the device.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "tpu")
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_REQUESTS = 64
BATCH = 16
SEED = 0
# bf16 products are exact in f32; kernel and reference differ only in the
# order of their f32 sums (K = 4096 terms of magnitude ~1).
BF16_RTOL, BF16_ATOL = 1e-3, 1e-2


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu():
    """The first TPU device and the device count; raises on anything else."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(
            f"no TPU: JAX_PLATFORMS={os.environ['JAX_PLATFORMS']!r} and JAX "
            f"could not start that backend ({e})"
        ) from e
    dev = devices[0]
    check(
        dev.platform == "tpu",
        f"no TPU: JAX found platform {dev.platform!r} ({dev.device_kind}, "
        f"{len(devices)} device(s))",
    )
    from repro.core.lowering import pallas_interpret_mode

    check(
        not pallas_interpret_mode(),
        f"Pallas would run in interpret mode on backend {jax.default_backend()!r}",
    )
    return dev, len(devices)


def phase_serve(n_requests: int = N_REQUESTS) -> None:
    import numpy as np

    import repro
    from repro.core import ir
    from repro.core.lowering import pallas_interpret_mode
    from repro.core.zoo import get_model
    from repro.serve import MicroBatcher

    model = get_model("toycar_mlp")
    t0 = time.perf_counter()
    module = repro.compile(
        "toycar_mlp",
        repro.Target("tpu_v5e", mode="optimized", batch_size=BATCH),
    )
    t_compile = time.perf_counter() - t0
    steps = [op for m in module.modules.values() for op in m.ops.values()]
    check(bool(steps), "toycar_mlp compiled with no accelerator steps")
    # on the chip ``require_tpu`` has shown interpret mode is off, so every
    # step must run the Mosaic-compiled kernel
    interpret = pallas_interpret_mode()
    for op in steps:
        cfg = getattr(op.executor, "kernel_config", None)
        check(
            cfg is not None and cfg.interpret is interpret,
            f"step {op.node.name} does not run the scheduled kernel with "
            f"interpret={interpret}: {cfg}",
        )
    # first call per bucket compiles that bucket's kernels
    t0 = time.perf_counter()
    for b in module.bucket_sizes():
        module.run_many([model.feeds(seed=SEED)] * b)
    t_warm = time.perf_counter() - t0
    print(
        f"[serve] toycar_mlp on tpu_v5e: {len(steps)} accelerator steps over "
        f"buckets {list(module.bucket_sizes())}, all interpret={interpret}"
    )
    print(
        f"[serve] set-up: repro.compile {t_compile:.2f} s, first call per "
        f"bucket (kernel compiles) {t_warm:.2f} s"
    )

    traffic = [model.feeds(seed=SEED + 1 + i) for i in range(n_requests)]
    with MicroBatcher(module, max_batch=BATCH) as mb:
        futures = [mb.submit(feeds) for feeds in traffic]
        answers = [f.result() for f in futures]
        stats = mb.stats

    golden = model.build()
    mismatches = 0
    for feeds, got in zip(traffic, answers):
        want = ir.execute_graph(golden, feeds)
        check(len(got) == len(want), "output count differs from the golden graph")
        for g, w in zip(got, want):
            g = np.asarray(g)
            same = g.shape == w.shape and g.dtype == w.dtype and np.array_equal(g, w)
            mismatches += not same
    print(
        f"[serve] {len(answers)} requests in {stats.batches} dispatches; "
        f"mismatches vs ir.execute_graph: {mismatches}"
    )
    check(len(answers) == n_requests, f"answered {len(answers)} of {n_requests}")
    check(mismatches == 0, f"{mismatches} answers differ from the golden graph")


def phase_kernels(
    bf16_shape: tuple[int, int, int] = (4096, 4096, 4096),
    int8_shape: tuple[int, int, int] = (4096, 8192, 4096),
) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.arch_spec import GemmWorkload
    from repro.core.configurators import build_backend
    from repro.core.descriptions.tpu_v5e import make_tpu_v5e_description
    from repro.core.lowering import pallas_interpret_mode
    from repro.kernels import ops as kops
    from repro.kernels import ref

    backend = build_backend(make_tpu_v5e_description())

    def scheduled_config(m, k, n, in_bytes, **kw):
        wl = GemmWorkload(N=m, C=k, K=n, in_bytes=in_bytes, w_bytes=in_bytes)
        sched = backend.scheduler.schedule(wl).best
        return backend.mapping_gen.to_kernel_config(
            sched, interpret=pallas_interpret_mode(), **kw
        )

    kx, kw, kb = jax.random.split(jax.random.key(SEED), 3)

    # bf16 in, f32 accumulation and output
    m, k, n = bf16_shape
    cfg = scheduled_config(m, k, n, 2)
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = jax.random.normal(kw, (k, n), jnp.bfloat16)
    t0 = time.perf_counter()
    got = kops.matmul(x, w, cfg).block_until_ready()
    t_setup = time.perf_counter() - t0
    want = ref.gemm_ref(x, w, acc_dtype=jnp.float32, out_dtype=jnp.float32)
    bad = int(jnp.sum(jnp.abs(got - want) > BF16_ATOL + BF16_RTOL * jnp.abs(want)))
    err = float(jnp.max(jnp.abs(got - want)))
    print(
        f"[kernel] bf16 {m}x{k}x{n} blocks ({cfg.block_m},{cfg.block_k},"
        f"{cfg.block_n}) {cfg.dataflow}: set-up (compile + first call) "
        f"{t_setup:.2f} s; {bad} elements outside rtol={BF16_RTOL} "
        f"atol={BF16_ATOL}, max |diff| {err:.3g}"
    )
    check(bool(jnp.all(jnp.isfinite(got))), "bf16 GEMM returned non-finite values")
    check(bad == 0, f"bf16 GEMM: {bad} elements outside tolerance")

    # int8 in, int32 accumulation, fused requantize + clip
    m, k, n = int8_shape
    scale = 2.0**-12  # exact in f32: kernel and reference round alike
    cfg = scheduled_config(
        m, k, n, 1,
        acc_dtype="int32",
        out_dtype="int8",
        epilogue={"requant_scale": scale, "clip_lo": -128.0, "clip_hi": 127.0},
        has_bias=True,
    )
    xq = jax.random.randint(kx, (m, k), -128, 128, jnp.int32).astype(jnp.int8)
    wq = jax.random.randint(kw, (k, n), -128, 128, jnp.int32).astype(jnp.int8)
    bias = jax.random.randint(kb, (n,), -(2**16), 2**16, jnp.int32)
    t0 = time.perf_counter()
    got = kops.qmatmul(xq, wq, bias, cfg).block_until_ready()
    t_setup = time.perf_counter() - t0
    want = ref.qgemm_ref(xq, wq, bias, requant_scale=scale)
    bad = int(jnp.sum(got != want))
    print(
        f"[kernel] int8 {m}x{k}x{n} blocks ({cfg.block_m},{cfg.block_k},"
        f"{cfg.block_n}) {cfg.dataflow}: set-up (compile + first call) "
        f"{t_setup:.2f} s; {bad} elements differ from qgemm_ref (exact)"
    )
    check(bad == 0, f"int8 qGEMM: {bad} elements differ from the reference")


def main() -> None:
    dev, count = require_tpu()
    print(f"[device] {dev.platform} {dev.device_kind}, {count} device(s)")
    from repro.launch.compile_cache import use_persistent_compile_cache

    print(f"[device] JAX compile cache: {use_persistent_compile_cache()}")
    phase_serve()
    phase_kernels()
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": count,
                },
            }
        )
    )


if __name__ == "__main__":
    try:
        main()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
