"""Program spans and transfer counters (``repro.core.trace``): exact
counts per bucket execution of a Pallas plan, from one thread and from two
at once; nothing counted on an emulated target; no span constructed while
no profiler runs; and CPU profiler captures, reduced by the benchmark's
``bench/program.py``, that hold one span per execution, step and
transfer."""

from __future__ import annotations

import sys
import threading
from pathlib import Path

import jax
import numpy as np
import pytest

import repro
from repro.core import trace, zoo
from repro.serve import MicroBatcher

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import program, tracing  # noqa: E402

WIDTHS = (32, 16, 16, 8)
W_SCALE, RQ_SCALE = 0.0625, 0.015625


def _params(seed: int = 0) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    params = {}
    for i, (d_in, d_out) in enumerate(zip(WIDTHS[:-1], WIDTHS[1:])):
        params[f"w{i}"] = (rng.integers(-8, 8, (d_out, d_in)) * W_SCALE).astype(np.float32)
        params[f"b{i}"] = rng.integers(-64, 64, (d_out,)).astype(np.int32)
    return params


def _chain(x, params):
    """Three int8 dense layers, the zoo's quantized dense with fused ReLU."""
    h = x
    for i in range(3):
        h = zoo._qdense_jnp(
            h, params[f"w{i}"], params[f"b{i}"], w_scale=W_SCALE, rq_scale=RQ_SCALE,
            clip_lo=0 if i < 2 else -128,
        )
    return h


def _compile(target: str):
    return repro.compile(
        _chain,
        repro.Target(target, cache=False),
        example_inputs={"x": np.zeros((1, WIDTHS[0]), np.int8)},
        params=_params(),
        options=repro.CompileOptions(batch_buckets=(1, 4)),
    )


def _feeds(n: int, seed: int = 1) -> list[dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    return [{"x": rng.integers(-128, 128, (1, WIDTHS[0])).astype(np.int8)} for _ in range(n)]


def _per_execution(bucket: int) -> dict[str, int]:
    """One execution of the chain at ``bucket`` rows: per layer, one upload
    each of the int8 input, the int8 weight panel and the int32 bias, one
    kernel and one sync of the int8 output."""
    layers = list(zip(WIDTHS[:-1], WIDTHS[1:]))
    weights = sum(d_in * d_out for d_in, d_out in layers)
    biases = sum(4 * d_out for _, d_out in layers)
    inputs = sum(bucket * d_in for d_in, _ in layers)
    return {
        "h2d_transfers": 3 * len(layers),
        "h2d_bytes": weights + biases + inputs,
        "d2h_syncs": len(layers),
        "d2h_bytes": sum(bucket * d_out for _, d_out in layers),
        "kernel_launches": len(layers),
        "attn_epilogue_rows": 0,
        "attn_fallback_rows": 0,
        "expert_rows": 0,
        "expert_pad_rows": 0,
        "expert_rows_peak": 0,
        "swiglu_device_rows": 0,
        "swiglu_host_rows": 0,
        "bmm_requant_device_rows": 0,
        "bmm_requant_host_rows": 0,
    }


@pytest.fixture(scope="module")
def tpu_module():
    module = _compile("tpu_v5e")
    module.run_many(_feeds(4))  # traces the kernels once
    module.run_many(_feeds(1))
    return module


@pytest.mark.parametrize("bucket, h2d_bytes", [(1, 896 + 160 + 64), (4, 896 + 160 + 4 * 64)])
def test_counts_per_bucket_execution(tpu_module, bucket, h2d_bytes):
    """Bucket 1 runs the unpadded per-sample plan, bucket 4 the padded one;
    896 B of weights, 160 B of biases, 64 B of step inputs a row."""
    before = trace.snapshot()
    tpu_module.run_many(_feeds(bucket))
    assert trace.since(before) == _per_execution(bucket)
    assert _per_execution(bucket)["h2d_bytes"] == h2d_bytes


def test_counts_stay_exact_across_threads(tpu_module):
    calls, barrier = 6, threading.Barrier(2)
    errors: list[BaseException] = []

    def serve():
        try:
            barrier.wait()
            for _ in range(calls):
                tpu_module.run_many(_feeds(4))
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    before = trace.snapshot()
    threads = [threading.Thread(target=serve) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert trace.since(before) == {
        k: 2 * calls * v for k, v in _per_execution(4).items()
    }


def test_emulated_target_moves_nothing():
    module = _compile("gemmini")
    before = trace.snapshot()
    module.run_many(_feeds(5))
    assert trace.since(before) == dict.fromkeys(trace.COUNTERS, 0)


class _CountingAnnotation(jax.profiler.TraceAnnotation):
    made = 0

    def __init__(self, *args, **kwargs):
        type(self).made += 1
        super().__init__(*args, **kwargs)


def _serve(module, n: int) -> None:
    with MicroBatcher(module, max_batch=4, max_delay_s=0.001) as mb:
        futures = [mb.submit(f) for f in _feeds(n)]
        for f in futures:
            f.result(timeout=60)


def test_no_span_is_made_without_a_profiler(tpu_module, monkeypatch, tmp_path):
    monkeypatch.setattr(trace, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.made = 0
    assert not trace.enabled()
    _serve(tpu_module, 5)
    tpu_module.bucket_module(4).run({"x": np.zeros((4, WIDTHS[0]), np.int8)})
    assert _CountingAnnotation.made == 0
    # the same calls under a profiler do make spans
    with jax.profiler.trace(str(tmp_path)):
        assert trace.enabled()
        _serve(tpu_module, 5)
    assert _CountingAnnotation.made > 0


def _capture(tmp_path, fn) -> program.ProgramTrace:
    with jax.profiler.trace(str(tmp_path)):
        fn()
    return program.reduce_program(tracing.newest_xplane(tmp_path))


def test_cpu_capture_of_run_many_counts_every_step(tpu_module, tmp_path):
    """9 requests over buckets (1, 4): two bucket-4 executions and one
    single-sample one, 3 Pallas steps each; the bytes on the upload and
    sync spans add up to the counters' differences."""
    feeds = _feeds(9)
    before = trace.snapshot()
    p = _capture(tmp_path, lambda: tpu_module.run_many(feeds))
    delta = trace.since(before)
    spans = p.program_spans
    executions, steps = 3, 3
    assert spans["repro.plan.execute"][0] == executions
    assert spans["repro.accel.generalized_dense"][0] == executions * steps
    assert spans["repro.h2d"][0] == delta["h2d_transfers"] == 3 * executions * steps
    assert spans["repro.launch"][0] == spans["repro.d2h"][0] == executions * steps
    assert spans["repro.batch.pack"][0] == spans["repro.batch.unpack"][0] == 2
    assert p.bytes["repro.h2d"] == delta["h2d_bytes"] == 2 * 1312 + 1120
    assert p.bytes["repro.d2h"] == delta["d2h_bytes"]
    # nesting: steps lie inside their executions, uploads inside their steps
    assert spans["repro.accel.generalized_dense"][1] <= spans["repro.plan.execute"][1]
    assert spans["repro.h2d"][1] <= spans["repro.accel.generalized_dense"][1]
    assert p.idle_by_program_span == []  # no chip in a CPU trace


def test_cpu_capture_of_run_many_names_host_steps(tmp_path):
    """Each host step of a plan gets its ``repro.host.<op>`` span, and
    each call one execution."""
    model = zoo.get_model("transformer_block")
    module = repro.compile("transformer_block", repro.Target("gemmini", cache=False))
    feeds = [model.feeds(seed=s) for s in range(3)]
    host_steps = [s.op for s in module.plan.steps if s.lane == "host"]
    assert host_steps and len(host_steps) < len(module.plan.steps)
    p = _capture(tmp_path, lambda: module.run_many(feeds))
    spans = p.program_spans
    assert spans["repro.plan.execute"][0] == 3
    for op in set(host_steps):
        assert spans[f"repro.host.{op}"][0] == 3 * host_steps.count(op)
    assert "repro.h2d" not in spans  # an emulated target moves nothing
