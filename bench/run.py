"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration and a traffic mix, each found by
name under ``bench/`` (see ``bench/spec.py``).  The run:

1. refuses to go on without a TPU, with fewer chips than the cell asks
   for, or with Pallas in interpret mode (exit 3, no result);
2. set-up: draws the weights from the seed on the device in int8,
   compiles the model with ``repro.compile(fn, Target("tpu_v5e",
   mode="optimized"), example_inputs=..., params=...,
   options=CompileOptions(batch_buckets=...))``, warms every shape the mix
   will use, and draws the inputs;
3. measures for ``--seconds`` (``--trace 1``: for at most
   ``TRACE_CAP_S``, under the profiler) — an open loop through
   ``repro.serve.MicroBatcher``, or a closed loop of ``run_many`` — and
   takes what the program's counters (``repro.core.trace``) counted over
   it, read by the ``program_counter`` metrics in every run;
4. reads the chip's peak memory, frees the program, and compares what the
   window answered with the plain reference (``bench/check.py``);
5. prints the check's numbers as the last lines of standard error, and one
   JSON object as the last line of standard output: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
   or with ``--trace 1`` its per-layer metrics), ``device``, with
   ``--trace 1`` ``breakdown``, and last ``check``.

Compiled programs are kept in ``bench/.cache/jax`` (JAX's persistent
cache) and CoSA schedules in ``bench/.cache/schedules``
(``REPRO_CACHE_DIR``), both inside the checkout at fixed paths.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / "bench" / ".cache"
#: longest traced window; a trace of a longer one would take minutes to read
TRACE_CAP_S = 10.0


def _setup_env() -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["REPRO_CACHE_DIR"] = str(CACHE / "schedules")
    os.environ["TPU_LOG_DIR"] = "disabled"


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def jax_key(seed: int):
    """A JAX key that uses every bit of a seed of any size."""
    import jax

    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF) if seed >> 32 else key


class CompileCounter:
    """Backend compiles while ``active`` (JAX's monitoring events)."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.active = False
        self.count = 0

        def on_event(event, duration, **_):
            if self.active and event == dispatch.BACKEND_COMPILE_EVENT:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


_COUNTER: list = []


def _compile_counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    counter = _COUNTER[0]
    counter.active, counter.count = False, 0
    return counter


class GcPauses:
    """Python's cyclic collections inside the window: how many, and the
    longest pause (``gc.callbacks``)."""

    def __init__(self):
        self.pauses: list[float] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def close(self) -> str:
        gc.callbacks.remove(self._on_gc)
        longest = max(self.pauses, default=0.0)
        return f"{len(self.pauses)} collections, longest {longest * 1e3:.3f} ms"


#: seconds of due time per slice of the ``worker:`` line's latency profile
SLICE_S = 5.0


def _worker_line(res, offsets, latencies) -> str:
    """The longest ``run_many`` and the longest gap between two, and the
    95th-percentile latency over the window and per ``SLICE_S`` of due time:
    where a backlog built up and how long it took to drain."""
    import numpy as np

    from bench.record import percentile

    start, end = np.asarray(res.calls.start), np.asarray(res.calls.end)
    if len(start) == 0:
        return "worker: no run_many calls"
    k = int(np.argmax(end - start))
    gaps = start[1:] - end[:-1]
    g = int(np.argmax(gaps)) if len(gaps) else 0
    gap = f"{gaps[g] * 1e3:.3f} ms at {end[g] - res.t0:.3f} s" if len(gaps) else "none"
    slices = (offsets // SLICE_S).astype(int)
    p95 = " ".join(f"{percentile(latencies[slices == s], 95) * 1e3:.1f}"
                   for s in range(int(slices.max()) + 1) if np.any(slices == s))
    return (
        f"worker: longest run_many {(end[k] - start[k]) * 1e3:.3f} ms at "
        f"{start[k] - res.t0:.3f} s, longest gap between calls {gap}; latency p95 "
        f"{percentile(latencies, 95) * 1e3:.3f} ms over the window, per {SLICE_S:g} s "
        f"of due time (ms): {p95}"
    )


def measure(cell, seed: int, seconds: float, trace: bool, devices=None, wrap=None,
            t_start=None):
    """Set up, measure and check one run; returns the result dict and the
    check's lines.  ``devices`` None means the chip check is done here;
    ``wrap(module, params)``, where given, returns what the window drives in
    the compiled module's place (``bench/control.py``)."""
    import jax
    import numpy as np

    import repro
    from bench import device, generator, tracing
    from bench.check import compare, reference_blocks
    from bench.record import Run
    from bench.spec import metric_reader
    from repro.core import trace as counters

    if devices is None:
        devices = device.require_tpu(cell.chips)
    dev_info = device.describe(devices)
    peaks = device.peaks(dev_info["kind"]) if dev_info["platform"] == "tpu" else {}
    _log(f"device: {dev_info['platform']} {dev_info['kind']}, {dev_info['count']} device(s)")
    (CACHE / "jax").mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # no eviction: an evicting cache reads every entry's access-time file
    # on each write, and one entry without it fails every later write
    jax.config.update("jax_compilation_cache_max_size", -1)
    compiles = _compile_counter()
    compiles.active = True
    t_start = time.perf_counter() if t_start is None else t_start
    t_devices = time.perf_counter()

    cfg, traffic, model = cell.config, cell.traffic, cell.model
    shape = model.sample_shape(cfg, traffic)
    rng = np.random.default_rng(seed)
    params = model.make_params(cfg, jax_key(seed), shape)
    t0 = time.perf_counter()
    weights_s = t0 - t_devices
    module = repro.compile(
        model.model_fn(cfg),
        repro.Target("tpu_v5e", mode="optimized"),
        example_inputs={"x": np.zeros(shape, np.int8)},
        params=params,
        options=repro.CompileOptions(batch_buckets=tuple(traffic["buckets"])),
    )
    compile_s = time.perf_counter() - t0
    if wrap is not None:
        module = wrap(module, params)

    window = min(seconds, TRACE_CAP_S) if trace else seconds
    loop = traffic["loop"]
    if loop == "open":
        offsets = generator.arrivals(traffic, window, rng)
        inputs = model.make_inputs(cfg, rng, len(offsets), shape)
        warm = [[{"x": inputs[i % len(inputs)]} for i in range(n)]
                for n in range(1, int(traffic["max_batch"]) + 1)]
    else:
        spc = int(traffic["samples_per_call"])
        pool_x = [model.make_inputs(cfg, rng, spc, shape) for _ in range(generator.CLOSED_POOL)]
        pool = [[{"x": x} for x in xs] for xs in pool_x]
        warm = [pool[0]]
    t0 = time.perf_counter()
    for feeds in warm:
        module.run_many(feeds)
    warm_s = time.perf_counter() - t0

    trace_dir = CACHE / "trace"
    summary = None
    # Everything set-up made goes to the collector's permanent generation
    # for the window, as a server does once it has warmed up: a full
    # collection then scans only what the window made, instead of stalling
    # every thread for the ~0.1 s that scanning JAX's, repro's and the
    # plan's objects takes.
    gc.collect()
    gc.freeze()
    gc_pauses = GcPauses()
    setup_compiles, compiles.count = compiles.count, 0
    setup_s = time.perf_counter() - t_start
    _log(
        f"setup: {setup_s:.3f} s = start, imports and devices {t_devices - t_start:.3f} s, "
        f"weights {weights_s:.3f} s, repro.compile {compile_s:.3f} s, warm-up {warm_s:.3f} s, "
        f"rest {setup_s - (t_devices - t_start) - weights_s - compile_s - warm_s:.3f} s; "
        f"{setup_compiles} backend compiles; device bytes in use "
        f"{device.memory_in_use_bytes(devices)}, peak so far {device.memory_peak_bytes(devices)}"
    )
    if loop == "open":
        with tracing.capture(trace_dir) if trace else contextlib.nullcontext():
            before = counters.snapshot()
            res = generator.drive_open(module, inputs, offsets, traffic, "x")
            counted = counters.since(before)
        t_end = np.nanmax(res.done) if np.isfinite(res.done).any() else res.t0
        grace_end = res.t0 + offsets[-1] + generator.ANSWER_GRACE_S if len(offsets) else res.t0
        latencies = np.where(np.isnan(res.done), grace_end, res.done) - res.due
        answered = int(np.sum([a is not None for a in res.answers]))
        run = Run(
            loop, cfg, traffic, model, shape, peaks, setup_s, compile_s, warm_s,
            window_s=float(t_end - res.t0), samples=answered, calls=res.calls,
            latencies_s=latencies, queue_waits_s=res.dispatched - res.due,
            batches=(res.stats.requests, res.stats.batches, res.max_batch),
        )
        late = res.submitted - res.due
        _log(
            f"generator: {len(offsets)} arrivals at {traffic['rate_per_s']}/s over "
            f"{window} s; late by p50 {np.percentile(late, 50) * 1e3:.3f} ms, "
            f"p99 {np.percentile(late, 99) * 1e3:.3f} ms, max {late.max() * 1e3:.3f} ms "
            f"at {offsets[late.argmax()]:.3f} s"
        )
        _log(_worker_line(res, offsets, latencies))
        attempted, failed = len(offsets), len(offsets) - answered
    else:
        with tracing.capture(trace_dir) if trace else contextlib.nullcontext():
            before = counters.snapshot()
            res = generator.drive_closed(module, pool, window, rng)
            counted = counters.since(before)
        answered = sum(res.calls.samples)
        run = Run(
            loop, cfg, traffic, model, shape, peaks, setup_s, compile_s, warm_s,
            window_s=res.t1 - res.t0, samples=answered, calls=res.calls,
        )
        attempted = answered + res.failed_samples
        failed = res.failed_samples
    compiles.active = False
    gc.unfreeze()
    run.counters = counted
    n_calls = len(run.calls.start)
    _log(
        f"window: {run.window_s:.3f} s, {n_calls} run_many calls, {answered} samples "
        f"answered, {compiles.count} backend compiles inside the window; "
        f"gc: {gc_pauses.close()}"
    )
    _log("counters: " + "; ".join(
        f"{k} {v} ({v / max(n_calls, 1):.6g}/call, {v / max(answered, 1):.6g}/sample)"
        for k, v in counted.items()
    ))
    if trace:
        summary = tracing.reduce_trace(tracing.newest_xplane(trace_dir))
        run.trace = summary
        kernels = ", ".join(f"{k} {s}" for k, s in summary.kernel_s.items())
        _log(
            f"trace: window {summary.window_s:.3f} s, device busy {summary.busy_s:.4f} s, "
            f"GEMM kernels {summary.gemm_s:.4f} s, {summary.n_device_events} device events; "
            f"kernels (s): {kernels or 'none'}"
        )

    metrics = {}
    for m in cell.metrics(trace):
        value = metric_reader(m["name"], cell.root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev_info["memory_peak_bytes"] = device.memory_peak_bytes(devices)
    _log(f"memory: peak {dev_info['memory_peak_bytes']} bytes, in use at the close "
         f"{device.memory_in_use_bytes(devices)}")
    if summary is not None:
        dev_info["busy_s"] = summary.busy_s
        dev_info["window_s"] = summary.window_s

    # the comparison runs on the host once the program's state is freed
    t_check = time.perf_counter()
    if loop == "open":
        got = [a[0] if a is not None else None for a in res.answers]
        del module, res
        gc.collect()
        verdict = compare(got, reference_blocks(cell.ref, cfg, params, inputs), cfg["check"]["limits"])
    else:
        kept = res.kept
        del module, res
        gc.collect()
        got, want = [], []
        for i, outs in kept:
            got += [o[0] for o in outs]
            want.append(reference_blocks(cell.ref, cfg, params, pool_x[i % len(pool_x)]))
        verdict = compare(got, np.concatenate(want) if want else np.zeros((0,)), cfg["check"]["limits"])

    _log(f"reference: {time.perf_counter() - t_check:.3f} s")
    result = {
        "correct": verdict.correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": dev_info,
    }
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.device_ops, "idle_gaps": summary.idle_gaps}
    result["check"] = verdict.as_json()
    return result, verdict.lines()


def main(argv=None) -> int:
    args = parse_args(argv)
    _setup_env()
    from bench.device import NoChip
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    try:
        result, check_lines = measure(
            cell, args.seed, args.seconds, bool(args.trace), t_start=T_START
        )
    except NoChip as e:
        _log(f"bench: {e}")
        return 3
    for line in check_lines:
        _log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
