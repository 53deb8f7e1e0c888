"""Host-op fast path: every op in ``ir.HOST_OPS`` compiles to a direct
closure (``compile_host_op``) whose bits are pinned to the reference
interpreter (``execute_node``) — planned vs. legacy equivalence per op."""

import numpy as np
import pytest

from repro.core import ir
from repro.core.executor import build_plan, compile_host_op
from repro.core.ir import HOST_OPS, Graph, Node

RNG = np.random.default_rng(7)

#: im2col is a registered-preprocessing *name* (descriptions lower conv
#: through it inside the executor); it has no standalone graph builder or
#: interpreter semantics, so it is the one host op without a golden graph.
UNTESTABLE = {"im2col"}


def _f32(*shape):
    return RNG.normal(size=shape).astype(np.float32)


def _i8(*shape):
    return RNG.integers(-128, 128, shape).astype(np.int8)


def _i32(*shape):
    return RNG.integers(-1000, 1000, shape).astype(np.int32)


def _case(op, graph_fn, feeds):
    return pytest.param(graph_fn, feeds, id=op)


x_f = lambda name="x": ir.input_((3, 8), "float32", name=name)  # noqa: E731
x_i8 = lambda name="x": ir.input_((3, 8), "int8", name=name)  # noqa: E731
x_i32 = lambda name="x": ir.input_((3, 8), "int32", name=name)  # noqa: E731

CASES = [
    _case("add", lambda: ir.add(x_i32(), ir.input_((3, 8), "int32", name="y")),
          {"x": _i32(3, 8), "y": _i32(3, 8)}),
    _case("sub", lambda: ir.sub(x_i32(), ir.input_((3, 8), "int32", name="y")),
          {"x": _i32(3, 8), "y": _i32(3, 8)}),
    _case("mul", lambda: ir.mul(x_f(), ir.input_((3, 8), "float32", name="y")),
          {"x": _f32(3, 8), "y": _f32(3, 8)}),
    _case("relu", lambda: ir.relu(x_f()), {"x": _f32(3, 8)}),
    _case("gelu", lambda: ir.gelu(x_f()), {"x": _f32(3, 8)}),
    _case("clip", lambda: ir.clip(x_i32(), lo=-20, hi=20), {"x": _i32(3, 8)}),
    _case("requantize", lambda: ir.requantize(x_i32(), scale=0.037),
          {"x": _i32(3, 8)}),
    _case("quantize", lambda: ir.quantize(x_f(), scale=0.05), {"x": _f32(3, 8)}),
    _case("dequantize", lambda: ir.dequantize(x_i8(), scale=0.05),
          {"x": _i8(3, 8)}),
    _case("bias_add", lambda: ir.bias_add(x_i32(), ir.input_((8,), "int32", name="b")),
          {"x": _i32(3, 8), "b": _i32(8)}),
    _case("transpose",
          lambda: ir.transpose(ir.input_((2, 3, 4), "float32", name="x"), (2, 0, 1)),
          {"x": _f32(2, 3, 4)}),
    _case("reshape",
          lambda: ir.reshape(ir.input_((2, 3, 4), "float32", name="x"), (4, 6)),
          {"x": _f32(2, 3, 4)}),
    _case("flatten",
          lambda: Node("flatten", [ir.input_((2, 3, 4), "int8", name="x")], {},
                       shape=(2, 12), dtype="int8"),
          {"x": _i8(2, 3, 4)}),
    _case("softmax",
          lambda: ir.softmax(ir.dequantize(x_i8(), scale=0.1)),
          {"x": _i8(3, 8)}),
    _case("max_pool2d",
          lambda: ir.max_pool2d(ir.input_((2, 6, 6, 3), "int8", name="x"), 2, 2),
          {"x": _i8(2, 6, 6, 3)}),
    _case("shard_slice",
          lambda: ir.shard_slice(ir.input_((4, 8), "int32", name="x"), 1, 1, 2),
          {"x": _i32(4, 8)}),
    _case("kv_cache_read",
          lambda: ir.kv_cache_read(ir.input_((16, 8), "int8", name="x")),
          {"x": _i8(16, 8)}),
    _case("kv_cache_append",
          lambda: ir.kv_cache_append(
              ir.input_((16, 8), "int8", name="x"),
              ir.input_((1, 8), "int8", name="u"),
              ir.input_((), "int32", name="pos"),
          ),
          {"x": _i8(16, 8), "u": _i8(1, 8),
           "pos": np.asarray(5, np.int32)}),
    _case("rms_norm",
          lambda: ir.rms_norm(x_i8(), in_scale=0.0625, out_scale=0.03125, eps=1e-5),
          {"x": _i8(3, 8)}),
    _case("rope",
          lambda: ir.rope(x_i8(), ir.input_((3, 8), "float32", name="c"),
                          ir.input_((3, 8), "float32", name="s"),
                          in_scale=0.0625, out_scale=0.0441941738),
          {"x": _i8(3, 8), "c": np.cos(_f32(3, 8)), "s": np.sin(_f32(3, 8))}),
    _case("silu_mul", lambda: ir.silu_mul(x_i8(), in_scale=0.0625, out_scale=0.0625),
          {"x": _i8(3, 8)}),
    _case("moe_route", lambda: ir.moe_route(x_i32(), top_k=2), {"x": _i32(3, 8)}),
    _case("moe_group_sizes",
          lambda: ir.moe_group_sizes(ir.input_((3, 2), "int32", name="x"), n_experts=4),
          {"x": np.array([[0, 3], [3, 1], [0, 3]], np.int32)}),
    _case("moe_dispatch",
          lambda: ir.moe_dispatch(x_i8(), ir.input_((3, 2), "int32", name="i")),
          {"x": _i8(3, 8), "i": np.array([[2, 0], [0, 1], [1, 2]], np.int32)}),
    _case("moe_combine",
          lambda: ir.moe_combine(ir.input_((6, 8), "int8", name="y"),
                                 ir.input_((3, 2), "int32", name="i"),
                                 ir.input_((3, 4), "int32", name="l"),
                                 logit_scale=2.0**-4, scale=1.0),
          {"y": _i8(6, 8), "i": np.array([[2, 0], [0, 1], [1, 3]], np.int32),
           "l": _i32(3, 4)}),
]


def test_cases_cover_every_host_op():
    covered = {c.id for c in CASES}
    assert covered >= (HOST_OPS - UNTESTABLE), (
        f"missing host-op equivalence cases: {sorted(HOST_OPS - UNTESTABLE - covered)}"
    )


@pytest.mark.parametrize("graph_fn,feeds", CASES)
def test_planned_bits_match_legacy(graph_fn, feeds):
    g = Graph([graph_fn()])
    ref = ir.execute_graph(g, feeds)[0]
    plan = build_plan(g, {})
    got = plan.execute(feeds, plan.new_arena())[0]
    assert got.dtype == ref.dtype and got.shape == ref.shape
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("graph_fn,feeds", CASES)
def test_host_op_compiles_to_direct_closure(graph_fn, feeds):
    """Every host op must take the specialized fast path — not the generic
    ``execute_node`` fallback closure (the gelu regression this pins)."""
    root = graph_fn()
    fn = compile_host_op(root)
    assert "_n" not in (fn.__code__.co_varnames + tuple(fn.__defaults__ or ())), (
        f"{root.op} fell through to the interpreter fallback"
    )


def test_gelu_matches_generalized_epilogue_bits():
    """One gelu definition everywhere: host op, interpreter, and the fused
    generalized epilogue agree bit-for-bit."""
    x = _f32(4, 8)
    host = compile_host_op(ir.gelu(ir.input_((4, 8), "float32", name="x")))(x)
    w = np.eye(8, dtype=np.float32)
    node = Node(
        "generalized_dense",
        [ir.input_((4, 8), "float32", name="x"), ir.const(w), None],
        {"quantized": False, "activation": "gelu"},
        shape=(4, 8),
        dtype="float32",
    )
    fused = ir.execute_node(node, [x, w, None])
    assert np.array_equal(host, fused)
