"""Measured Pallas execution: kernel-path parity with the emulated
executors, schedule-derived kernel configs, and measured DSE
(``CompileOptions(measure_top_k=K)``) including the warm-boot
zero-work guarantee.

Everything here runs the kernels in interpret mode (CPU CI); on a TPU
host the same dispatch path compiles through Mosaic (see
``repro.core.lowering.pallas_interpret_mode``).
"""

import numpy as np
import pytest

import repro
from repro.api import CompileOptions, Target
from repro.core import ir, zoo
from repro.core.lowering import pallas_interpret_mode


def _assert_outputs_match(got, want, context: str):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape and got.dtype == want.dtype, context
    if np.issubdtype(got.dtype, np.integer):
        np.testing.assert_array_equal(got, want, err_msg=context)
    else:
        np.testing.assert_allclose(
            got, want, rtol=1e-4, atol=1e-4, err_msg=context
        )


def test_interpret_mode_env_override(monkeypatch):
    """Interpret mode follows JAX's backend alone: no environment switch
    can put a TPU's kernels into interpret mode."""
    import jax

    assert pallas_interpret_mode() is (jax.default_backend() != "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert pallas_interpret_mode() is True


def test_tpu_target_runs_scheduled_kernel_without_use_pallas(monkeypatch, tmp_path):
    """``Target("tpu_v5e")`` leaves ``use_pallas`` at its default, and every
    accelerator step still runs the scheduled Pallas GEMM — never the jnp
    oracle — and the manifest of a saved module says so."""
    import json

    from repro.core.artifact import save_module
    from repro.kernels import gemm, ops, qgemm

    calls = []

    def spy(x, w, cfg, bias=None):
        calls.append(cfg)
        return gemm.scheduled_gemm(x, w, cfg, bias)

    # the wrappers are jitted: drop traces cached by earlier tests so this
    # run traces through the spy
    ops.matmul.clear_cache()
    ops.qmatmul.clear_cache()
    monkeypatch.setattr(ops, "scheduled_gemm", spy)
    monkeypatch.setattr(qgemm, "scheduled_gemm", spy)
    model = zoo.get_model("toycar_mlp")
    module = repro.compile("toycar_mlp", Target("tpu_v5e", cache=False))
    assert not module.backend.use_pallas
    feeds = model.feeds(seed=2)
    for g, w in zip(module.run(feeds), ir.execute_graph(model.build(), feeds)):
        _assert_outputs_match(g, w, "toycar_mlp/tpu_v5e")
    ops.matmul.clear_cache()
    ops.qmatmul.clear_cache()

    step_cfgs = {op.executor.kernel_config for op in module.ops.values()}
    assert len(module.ops) == model.n_gemms
    # steps that share a config and shape share one trace
    assert calls and len(set(calls)) == len(step_cfgs)
    assert all(cfg.interpret is pallas_interpret_mode() for cfg in calls)
    save_module(module, tmp_path / "toycar")
    manifest = json.loads((tmp_path / "toycar" / "manifest.json").read_text())
    assert manifest["use_pallas"] is True
    assert len(manifest["kernel_configs"]) == model.n_gemms


# -- kernel dispatch parity: pallas vs emulated, across the zoo ---------------


@pytest.mark.parametrize(
    "name", ("mlp_tiny", "qcnn", "toycar_mlp", "transformer_block")
)
@pytest.mark.parametrize("mode", ("optimized", "baseline"))
def test_zoo_pallas_matches_emulated(name, mode):
    """Same graph, same schedules — the Pallas kernel path must agree with
    the emulated tiled-loop executors bit-exactly (int8 zoo models)."""
    model = zoo.get_model(name)
    feeds = model.feeds(seed=3)
    for acc in model.accelerators:
        if acc.startswith("tpu"):
            continue  # tpu desc runs the pallas kernel whatever use_pallas says
        emulated = repro.compile(
            model.build(), Target(acc, mode=mode, cache=False)
        ).run(feeds)
        pallas = repro.compile(
            model.build(), Target(acc, mode=mode, cache=False, use_pallas=True)
        ).run(feeds)
        for p, e in zip(pallas, emulated):
            _assert_outputs_match(p, e, f"{name}/{acc}/{mode}")


def test_batched_pallas_run_many_matches_emulated():
    """The PR-5 bucketed serving path stays bit-exact through the kernel
    dispatch (3-D batched dense lowers to the per-instance kernel loop)."""
    model = zoo.get_model("mlp_tiny")
    traffic = [model.feeds(seed=s) for s in range(5)]
    kwargs = dict(options=CompileOptions(batch_buckets=(1, 4)))
    emulated = repro.compile(
        "mlp_tiny", Target("gemmini", cache=False), **kwargs
    ).run_many(traffic)
    pallas = repro.compile(
        "mlp_tiny", Target("gemmini", cache=False, use_pallas=True), **kwargs
    ).run_many(traffic)
    for outs_p, outs_e in zip(pallas, emulated):
        for p, e in zip(outs_p, outs_e):
            _assert_outputs_match(p, e, "mlp_tiny batched")


def test_transformer_block_pallas_bmm_parity():
    """Attention scores/context are activation-activation batched matmuls —
    the kernel path replays the per-sample GEMM per batch instance."""
    model = zoo.get_model("transformer_block")
    feeds = model.feeds(seed=1)
    emulated = repro.compile(
        model.build(), Target("gemmini", cache=False)
    ).run(feeds)
    pallas = repro.compile(
        model.build(), Target("gemmini", cache=False, use_pallas=True)
    ).run(feeds)
    for p, e in zip(pallas, emulated):
        _assert_outputs_match(p, e, "transformer_block/gemmini")


# -- the batched requantize in the kernel's epilogue ----------------------------

B, M, N = 2, 8, 32


def _context_graph(k: int, scale: float) -> ir.Graph:
    """The attention context: ``requantize(probs @ v)`` over ``B`` instances."""
    x = ir.input_((B, M, k), "int8", name="probs")
    v = ir.input_((B, k, N), "int8", name="v")
    return ir.Graph([ir.requantize(ir.dense(x, v), scale=scale)], name="context")


def _extremes(k: int):
    """Operands at the int8 extremes; instance 0's first row meets ``v``'s
    column 0 of -128s with -128s, so its accumulator is ``k * 2^14``."""
    rng = np.random.default_rng(k)
    x = rng.choice(np.array([-128, 127], np.int8), (B, M, k))
    v = rng.choice(np.array([-128, 127], np.int8), (B, k, N))
    x[0, 0] = -128
    v[0, :, 0] = -128
    return {"probs": x, "v": v}


def _ties(k: int):
    """Every accumulator is ``64 * odd``: an exact half of 2^-7, of both
    signs (the column 0 of ``probs`` is ±64, ``v``'s row 0 odd)."""
    rng = np.random.default_rng(5)
    x = np.zeros((B, M, k), np.int8)
    x[:, :, 0] = rng.choice(np.array([-64, 64], np.int8), (B, M))
    v = rng.integers(-128, 128, (B, k, N)).astype(np.int8)
    v[:, 0] = 2 * rng.integers(-64, 64, (B, N)) + 1
    return {"probs": x, "v": v}


def _saturating(k: int):
    rng = np.random.default_rng(6)
    return {
        "probs": rng.integers(-128, 128, (B, M, k)).astype(np.int8),
        "v": rng.integers(-128, 128, (B, k, N)).astype(np.int8),
    }


# (K, scale, feeds, the epilogue runs on the device)
CONTEXT_CASES = {
    "extremes_k1024": (1024, 2.0**-18, _extremes, True),
    "ties_half_even": (16, 2.0**-7, _ties, True),
    "saturation": (64, 2.0**-7, _saturating, True),
    "k1025_on_the_host": (1025, 2.0**-18, _extremes, False),
    "odd_scale_on_the_host": (64, 0.003, _saturating, False),
}


@pytest.mark.parametrize("case", sorted(CONTEXT_CASES))
def test_batched_requantize_matches_the_host_bit_for_bit(case):
    """The quantized batched step gives ``ir.execute_graph``'s bits, dtype
    included: in the kernel's epilogue where K <= 1024 and the scale is a
    power of two (int8 synced), else in the host's row blocks after the
    int32 kernel (int32 synced); the counters say which."""
    from repro.core import trace

    k, scale, make_feeds, on_device = CONTEXT_CASES[case]
    feeds = make_feeds(k)
    want = ir.execute_graph(_context_graph(k, scale), feeds)[0]
    module = repro.compile(_context_graph(k, scale), Target("tpu_v5e", cache=False))
    assert [s.op for s in module.plan.steps] == ["generalized_dense"]
    before = trace.snapshot()
    got = module.run(feeds)[0]
    moved = trace.since(before)
    _assert_outputs_match(got, want, case)
    rows = B * M
    assert (moved["bmm_requant_device_rows"], moved["bmm_requant_host_rows"]) == (
        (rows, 0) if on_device else (0, rows)
    )
    assert moved["d2h_bytes"] == rows * N * (1 if on_device else 4)
    if case == "extremes_k1024":
        acc = feeds["probs"][0, 0].astype(np.int64) @ feeds["v"][0, :, 0].astype(np.int64)
        assert acc == 2**24 and got[0, 0, 0] == 64
    if case == "ties_half_even":
        assert np.all(np.abs(want) % 2 == 0)  # every k + 1/2 went to the even k
    if case == "saturation":
        assert {-128, 127} <= set(np.unique(got).tolist())
    # the emulated target's float64 epilogue gives the same bits
    emulated = repro.compile(_context_graph(k, scale), Target("gemmini", cache=False))
    _assert_outputs_match(emulated.run(feeds)[0], want, f"{case}/gemmini")


@pytest.mark.parametrize("name", ["mlp_tiny", "qcnn", "toycar_mlp", "transformer_block"])
def test_plans_without_a_batched_dense_are_unchanged_by_the_rule(name, monkeypatch):
    """No batched dense (the unbatched transformer block's context is 2-D):
    the plan's steps are those it has without ``fuse-batched-requantize``."""
    from repro.core import passes

    def steps():
        module = repro.compile(zoo.get_model(name).build(), Target("tpu_v5e", cache=False))
        return [(s.op, s.lane) for s in module.plan.steps]

    with_rule = steps()
    without = tuple(r for r in passes.LEGALIZE_RULES if r.name != "fuse-batched-requantize")
    assert len(without) == len(passes.LEGALIZE_RULES) - 1
    monkeypatch.setattr(passes, "LEGALIZE_RULES", without)
    assert steps() == with_rule


# -- measured DSE: top-K timing + warm-boot zero-work -------------------------


def _qdense_graph():
    rng = np.random.default_rng(0)
    w = rng.integers(-8, 8, size=(64, 48)).astype(np.int8)
    b = rng.integers(-64, 64, size=(48,)).astype(np.int32)
    x = ir.input_((8, 64), "int8", name="x")
    h = ir.bias_add(ir.dense(x, ir.const(w)), ir.const(b))
    h = ir.clip(ir.requantize(h, scale=2.0**-6), lo=-128, hi=127)
    return ir.Graph([h], name="measured_dse_probe")


def test_measured_dse_picks_winner_and_stays_correct(tmp_path):
    feeds = {"x": np.random.default_rng(1).integers(-16, 16, (8, 64)).astype(np.int8)}
    want = ir.execute_graph(_qdense_graph(), feeds)[0]
    module = repro.compile(
        _qdense_graph(),
        Target("gemmini", cache_dir=str(tmp_path)),
        options=CompileOptions(measure_top_k=3, fresh_backend=True),
    )
    backend = module.backend
    assert backend.n_measurements > 0
    assert backend.scheduler.n_solver_calls > 0
    _assert_outputs_match(module.run(feeds)[0], want, "measured winner")
    # the measurement record rides along with the cached schedule
    (node,) = [n for n in module.graph.toposort() if n.target == "accel"]
    sr = backend._schedule_for(node, "proposed", 3)
    assert sr.measured is not None
    assert sr.measured["k"] == len(sr.measured["latencies_s"])
    assert sr.measured["winner"] == int(np.argmin(sr.measured["latencies_s"]))


def test_measured_dse_warm_boot_does_zero_work(tmp_path):
    """The acceptance criterion: recompiling with the same ``measure_top_k``
    against a warm cache performs NO candidate sweeps and NO wall-clock
    measurements — and a later modeled-only compile is warm too (the
    modeled ranking was cached en route to the measured key)."""
    target = Target("gemmini", cache_dir=str(tmp_path))
    opts = CompileOptions(measure_top_k=2, fresh_backend=True)
    cold = repro.compile(_qdense_graph(), target, options=opts)
    assert cold.backend.n_measurements > 0

    warm = repro.compile(_qdense_graph(), target, options=opts)
    assert warm.backend is not cold.backend
    assert warm.backend.n_measurements == 0
    assert warm.backend.scheduler.n_solver_calls == 0

    modeled = repro.compile(
        _qdense_graph(), target, options=CompileOptions(fresh_backend=True)
    )
    assert modeled.backend.scheduler.n_solver_calls == 0


def test_measured_and_modeled_cache_keys_are_distinct(tmp_path):
    """measure_top_k=K results live under their own cache key: a modeled
    compile must never be served a measured entry and vice versa."""
    from repro.core.schedule_cache import ScheduleCache
    from repro.core.strategy import workload_from_node

    target = Target("gemmini", cache_dir=str(tmp_path))
    module = repro.compile(
        _qdense_graph(), target,
        options=CompileOptions(measure_top_k=2, fresh_backend=True),
    )
    (node,) = [n for n in module.graph.toposort() if n.target == "accel"]
    wl = workload_from_node(node)
    fp = module.backend.desc.fingerprint()
    solver = module.backend.scheduler.solver_id()
    modeled_key = ScheduleCache.key_for(wl, fp, "proposed", solver=solver)
    measured_key = ScheduleCache.key_for(
        wl, fp, "proposed", solver=solver, selector="measured2"
    )
    assert modeled_key != measured_key
    cache = module.backend.schedule_cache
    assert cache.get(measured_key) is not None
    assert cache.get(measured_key).measured is not None
    assert cache.get(modeled_key) is not None
    assert cache.get(modeled_key).measured is None


def test_measure_top_k_validation():
    with pytest.raises(ValueError):
        CompileOptions(measure_top_k=0)
    with pytest.raises(ValueError):
        CompileOptions(measure_top_k=-3)
    with pytest.raises(ValueError):
        CompileOptions(measure_top_k=2.5)
