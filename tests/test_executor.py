"""Planned graph executor: plan structure, bit-exact equivalence with the
golden graph's reference semantics across the model zoo x modes x
accelerators (including the Pallas interpret-mode TPU path), run_many
semantics, and the quantized-flag / free-view cycle-model fixes in the
pipeline."""

import numpy as np
import pytest

from repro.core import build_backend, ir
from repro.core.descriptions import (
    make_edge_npu_description,
    make_gemmini_description,
    make_tpu_v5e_description,
)
from repro.core.ir import Graph, Node
from repro.core.pipeline import FREE_VIEW_OPS, CompiledModule, build_plan
from repro.core.zoo import ZOO, get_model, mlp_graph

MAKERS = {
    "gemmini": make_gemmini_description,
    "edge_npu": make_edge_npu_description,
    "tpu_v5e": make_tpu_v5e_description,
}
MODES = ("proposed", "c_toolchain", "naive")

_BACKENDS: dict[str, object] = {}


def _backend(acc: str):
    if acc not in _BACKENDS:
        _BACKENDS[acc] = build_backend(MAKERS[acc]())
    return _BACKENDS[acc]


# -- the plan against the golden graph across the zoo -------------------------


def _assert_matches_reference(planned, ref):
    assert len(planned) == len(ref)
    for p, r in zip(planned, ref):
        p = np.asarray(p)
        assert p.dtype == r.dtype and np.array_equal(p, r)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize(
    "model_name,acc",
    [(m.name, a) for m in ZOO.values() for a in m.accelerators if a != "tpu_v5e"],
)
def test_plan_matches_reference_zoo(model_name, acc, mode):
    model = get_model(model_name)
    mod = _backend(acc).compile_graph(model.build(), mode=mode)
    feeds = model.feeds(seed=3)
    _assert_matches_reference(mod.run(feeds), ir.execute_graph(model.build(), feeds))


@pytest.mark.parametrize("mode", MODES)
def test_plan_matches_reference_tpu_pallas_interpret(mode):
    """The Pallas interpret-mode TPU path is bit-exact with the golden
    graph in all three modes."""
    backend = build_backend(make_tpu_v5e_description(), use_pallas=True)
    model = get_model("mlp_tiny")
    mod = backend.compile_graph(model.build(), mode=mode)
    feeds = model.feeds(seed=5)
    _assert_matches_reference(mod.run(feeds), ir.execute_graph(model.build(), feeds))


# -- plan structure ------------------------------------------------------------


def _tiny_module(mode="proposed"):
    return _backend("gemmini").compile_graph(mlp_graph((16,) * 3), mode=mode)


def test_compile_builds_plan_eagerly():
    mod = _tiny_module()
    assert mod.plan is not None
    # flat loop over planned steps only: inputs/consts are not steps
    assert all(s.op not in ("input", "const") for s in mod.plan.steps)
    # consts were materialized into the arena once
    assert len(mod.plan.const_slots) == 4  # 2 layers x (weight, bias)
    # graph outputs resolve to slots
    assert len(mod.plan.output_slots) == len(mod.graph.outputs)


def test_plan_specializes_const_weight_executors():
    mod = _tiny_module()
    raw_executors = {op.executor for op in mod.ops.values()}
    accel_steps = [s for s in mod.plan.steps if s.op.startswith("generalized")]
    assert accel_steps
    for s in accel_steps:
        # plan-time const binding replaced the generic executor
        assert s.fn not in raw_executors


def test_run_many_reuses_arena_and_results_stay_independent():
    mod = _tiny_module()
    feeds = [
        {"x": np.full((1, 16), i, dtype=np.int8)} for i in range(4)
    ]
    outs = mod.run_many(feeds)
    snapshots = [o[0].copy() for o in outs]
    # re-running with different feeds must not clobber earlier results
    mod.run_many([{"x": np.full((1, 16), 9, dtype=np.int8)}] * 4)
    for out, snap in zip(outs, snapshots):
        assert np.array_equal(out[0], snap)
    for p, f in zip(outs, feeds):
        assert np.array_equal(p[0], ir.execute_graph(mlp_graph((16,) * 3), f)[0])


def test_run_missing_feed_raises_keyerror():
    mod = _tiny_module()
    with pytest.raises(KeyError, match="missing feed for input 'x'"):
        mod.run({})


def test_plan_handles_none_inputs():
    x = ir.input_((4, 8), "int8", name="x")
    w = ir.const(np.ones((8, 8), dtype=np.int8))
    node = Node(
        "generalized_dense",
        [x, w, None],
        {"quantized": False},
        shape=(4, 8),
        dtype="int32",
    )
    mod = _backend("gemmini").compile_graph(Graph([node]), mode="proposed")
    feeds = {"x": np.ones((4, 8), dtype=np.int8)}
    expected = np.full((4, 8), 8, dtype=np.int32)
    assert np.array_equal(mod.run(feeds)[0], expected)


def test_inplace_accumulating_intrinsic_stays_correct():
    """Regression: an in-place-accumulating compute intrinsic (legal for
    the generic tile loop) must not corrupt the specialized fast path's
    shared initial accumulator across repeated runs."""
    desc = make_edge_npu_description()

    def inplace_mma(a_tile, b_tile, acc_tile):
        np.add(
            acc_tile,
            a_tile.astype(np.int32) @ b_tile.astype(np.int32),
            out=acc_tile,
        )
        return acc_tile

    for intr in desc.intrinsics.values():
        if intr.kind == "compute":
            intr.fn = inplace_mma
    backend = build_backend(desc)
    mod = backend.compile_graph(mlp_graph((8, 8, 8)), mode="proposed")
    feeds = {"x": np.full((1, 8), 3, dtype=np.int8)}
    r1 = mod.run(feeds)[0].copy()
    for _ in range(3):  # identical feeds must keep producing identical outputs
        assert np.array_equal(mod.run(feeds)[0], r1)


def test_softmax_charged_as_host_epilogue():
    x = ir.input_((16, 16), "int32", name="x")
    g = Graph([ir.softmax(ir.dequantize(x, scale=0.1))])
    mod = CompiledModule(graph=g, desc=MAKERS["gemmini"](), mode="proposed")
    softmax_only = CompiledModule(
        graph=Graph([ir.softmax(ir.input_((16, 16), "float32", name="x"))]),
        desc=MAKERS["gemmini"](),
        mode="proposed",
    )
    assert softmax_only.modeled_cycles()["host"] > 0
    assert mod.modeled_cycles()["host"] > softmax_only.modeled_cycles()["host"]


# -- satellite: one resolved quantized flag ------------------------------------


def _manual_generalized(attrs):
    rng = np.random.default_rng(0)
    x = ir.input_((4, 16), "int8", name="x")
    w = ir.const(rng.integers(-8, 8, (16, 8)).astype(np.int8))
    b = ir.const(rng.integers(-50, 50, (8,)).astype(np.int32))
    node = Node("generalized_dense", [x, w, b], attrs, shape=(4, 8), dtype="int8")
    feeds = {"x": rng.integers(-128, 128, (4, 16)).astype(np.int8)}
    expected = np.clip(
        np.round(
            (
                feeds["x"].astype(np.int64) @ w.value.astype(np.int64)
                + b.value.astype(np.int64)
            ).astype(np.float64)
            * attrs["requant_scale"]
        ),
        attrs["clip_lo"],
        attrs["clip_hi"],
    ).astype(np.int8)
    return Graph([node]), feeds, expected


@pytest.mark.parametrize("acc", ["gemmini", "edge_npu"])
def test_quantized_flag_from_node_attrs(acc):
    epi = {"quantized": True, "requant_scale": 0.05, "clip_lo": -128, "clip_hi": 127}
    graph, feeds, expected = _manual_generalized(epi)
    mod = _backend(acc).compile_graph(graph, mode="proposed")
    assert np.array_equal(mod.run(feeds)[0], expected)


@pytest.mark.parametrize("acc", ["gemmini", "edge_npu"])
def test_quantized_flag_from_strategy_compute(acc):
    """Regression: a strategy-quantized op whose node attrs lack the
    ``quantized`` flag used to silently skip the requantize/clip epilogue."""
    epi = {"requant_scale": 0.05, "clip_lo": -128, "clip_hi": 127}  # no flag
    graph, feeds, expected = _manual_generalized(epi)
    mod = _backend(acc).compile_graph(graph, mode="proposed")
    assert np.array_equal(mod.run(feeds)[0], expected)


def test_quantized_missing_epilogue_attrs_is_compile_error():
    rng = np.random.default_rng(0)
    x = ir.input_((4, 16), "int8", name="x")
    w = ir.const(rng.integers(-8, 8, (16, 8)).astype(np.int8))
    b = ir.const(rng.integers(-50, 50, (8,)).astype(np.int32))
    node = Node(
        "generalized_dense", [x, w, b], {"quantized": True}, shape=(4, 8), dtype="int8"
    )
    with pytest.raises(ValueError, match="missing required epilogue attrs"):
        _backend("gemmini").compile_graph(Graph([node]), mode="proposed")


# -- satellite: flatten and reshape are both free views ------------------------


def _host_cycles(mid_op_graph):
    mod = CompiledModule(
        graph=mid_op_graph, desc=MAKERS["gemmini"](), mode="proposed"
    )
    return mod.modeled_cycles()["host"]


def test_flatten_and_reshape_cost_the_same():
    assert {"flatten", "reshape"} <= FREE_VIEW_OPS

    def graph_with(op):
        x = ir.input_((2, 4, 8), "int8", name="x")
        if op == "flatten":
            n = Node("flatten", [x], {}, shape=(2, 32), dtype="int8")
        else:
            n = Node("reshape", [x], {"shape": (2, 32)}, shape=(2, 32), dtype="int8")
        return Graph([n])

    flatten_cost = _host_cycles(graph_with("flatten"))
    reshape_cost = _host_cycles(graph_with("reshape"))
    assert flatten_cost == reshape_cost == 0.0
    # a real layout op still gets charged
    x = ir.input_((2, 4, 8), "int8", name="x")
    assert _host_cycles(Graph([ir.transpose(x, (0, 2, 1))])) > 0


def test_flatten_node_executes_like_reshape():
    x = ir.input_((2, 4, 8), "int8", name="x")
    n = Node("flatten", [x], {}, shape=(2, 32), dtype="int8")
    feeds = {"x": np.arange(64, dtype=np.int8).reshape(2, 4, 8)}
    mod = _backend("gemmini").compile_graph(Graph([n]), mode="proposed")
    expected = feeds["x"].reshape(2, 32)
    assert np.array_equal(mod.run(feeds)[0], expected)


# -- build_plan is usable standalone ------------------------------------------


def test_build_plan_standalone_matches_execute_graph():
    g = mlp_graph((16, 16, 16))
    feeds = {"x": np.random.default_rng(7).integers(-128, 128, (1, 16)).astype(np.int8)}
    ref = ir.execute_graph(mlp_graph((16, 16, 16)), feeds)
    plan = build_plan(g, {})
    arena = plan.new_arena()
    out = plan.execute(feeds, arena)
    for o, r in zip(out, ref):
        assert np.array_equal(o, r)
