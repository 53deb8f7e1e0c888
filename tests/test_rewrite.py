"""Declarative rewrite engine: one minimal golden test per pattern (fires
on the minimal graph, refuses when an intermediate has a second consumer
or is a graph output), engine bookkeeping, description-contributed
patterns, and a multi-output-graph compile/run test."""

import numpy as np
import pytest

from repro.core import build_backend, ir
from repro.core.descriptions import make_gemmini_description
from repro.core.ir import Graph, Node
from repro.core.passes import (
    CONV_POOL_RULES,
    FOLD_TRANSPOSE_RULES,
    LEGALIZE_RULES,
    RESIDUAL_RULES,
    legalize,
)
from repro.core.rewrite import Match, P, any_, apply_rules, match_pattern, rule


def _ops(g: Graph) -> list[str]:
    return [n.op for n in g.toposort()]


def _qchain(x=None):
    """Minimal quantized chain: clip(requantize(bias_add(dense(x, w))))."""
    rng = np.random.default_rng(0)
    x = x if x is not None else ir.input_((2, 16), "int8", name="x")
    w = ir.const(rng.integers(-8, 8, (x.shape[-1], 8)).astype(np.int8))
    b = ir.const(rng.integers(-50, 50, (8,)).astype(np.int32))
    return ir.clip(ir.requantize(ir.bias_add(ir.dense(x, w), b), scale=0.05))


# -- per-pattern golden tests --------------------------------------------------


def test_fuse_quantized_fires_on_minimal_graph():
    g = Graph([_qchain()])
    n = apply_rules(g, LEGALIZE_RULES)
    assert n == 1
    assert _ops(g) == ["input", "const", "const", "generalized_dense"]
    gen = g.outputs[0]
    assert gen.attrs["quantized"] is True
    assert gen.attrs["requant_scale"] == 0.05


def test_fuse_quantized_refuses_second_consumer():
    """A second consumer of the intermediate requantize blocks the fusion
    (its value is observable), but the inner bias fusion still applies."""
    chain = _qchain()
    rq = chain.inputs[0]
    g = Graph([chain, ir.relu(rq)])
    apply_rules(g, LEGALIZE_RULES)
    ops = _ops(g)
    assert "clip" in ops and "requantize" in ops  # chain NOT fused
    assert "generalized_dense" in ops  # bias_add(dense) still fused


def test_fuse_quantized_refuses_interior_graph_output():
    """An interior node that is itself a graph output must survive."""
    chain = _qchain()
    rq = chain.inputs[0]
    g = Graph([chain, rq])
    apply_rules(g, LEGALIZE_RULES)
    ops = _ops(g)
    assert "requantize" in ops and "clip" in ops
    assert rq in g.outputs
    # semantics preserved end to end
    feeds = {"x": np.random.default_rng(1).integers(-128, 128, (2, 16)).astype(np.int8)}
    ref_chain = _qchain()
    ref = ir.execute_graph(Graph([ref_chain, ref_chain.inputs[0]]), feeds)
    got = ir.execute_graph(g, feeds)
    for r, o in zip(ref, got):
        assert np.array_equal(r, o)


def test_fuse_activation_fires_and_refuses():
    x = ir.input_((4, 32), "float32", name="x")
    w = ir.const(np.ones((32, 16), np.float32))
    b = ir.const(np.zeros(16, np.float32))
    out = ir.relu(ir.bias_add(ir.dense(x, w), b))
    g = legalize(Graph([out]))
    gen = g.outputs[0]
    assert gen.op == "generalized_dense" and gen.attrs["activation"] == "relu"

    # second consumer of the bias_add blocks the *activation* fusion; the
    # bare bias fusion still applies (its root may be shared), so both
    # activations read one generalized op with no fused activation.
    x2 = ir.input_((4, 32), "float32", name="x")
    ba = ir.bias_add(ir.dense(x2, w), b)
    g2 = legalize(Graph([ir.relu(ba), ir.gelu(ba)]))
    ops2 = _ops(g2)
    assert "relu" in ops2 and "gelu" in ops2
    (gen,) = [n for n in g2.toposort() if n.op == "generalized_dense"]
    assert gen.attrs["activation"] is None


def test_fuse_gelu_activation():
    x = ir.input_((4, 32), "float32", name="x")
    w = ir.const(np.ones((32, 16), np.float32))
    b = ir.const(np.zeros(16, np.float32))
    g = legalize(Graph([ir.gelu(ir.bias_add(ir.dense(x, w), b))]))
    assert g.outputs[0].attrs["activation"] == "gelu"


def _batched(x_dtype="int8", w_dims=3):
    """The attention context's operands: ``probs [2, 8, 16]`` and ``v``,
    ``[2, 16, 4]`` (batched) or ``[16, 4]``."""
    x = ir.input_((2, 8, 16), x_dtype, name="probs")
    w_shape = (2, 16, 4) if w_dims == 3 else (16, 4)
    return x, ir.input_(w_shape, x_dtype, name="v")


def _batched_feeds(w_dims=3):
    rng = np.random.default_rng(2)
    w_shape = (2, 16, 4) if w_dims == 3 else (16, 4)
    return {
        "probs": rng.integers(-128, 128, (2, 8, 16)).astype(np.int8),
        "v": rng.integers(-128, 128, w_shape).astype(np.int8),
    }


@pytest.mark.parametrize("dtype, lo, hi", [("int8", -128, 127), ("int16", -32768, 32767)])
def test_fuse_batched_requantize_fires_on_minimal_graph(dtype, lo, hi):
    """requantize(batched int8 dense) becomes one quantized generalized dense
    with an absent bias, saturating to the requantize's dtype."""
    def build():
        return Graph([ir.requantize(ir.dense(*_batched()), scale=2.0**-7, out_dtype=dtype)])

    g = build()
    counters: dict[str, int] = {}
    assert apply_rules(g, LEGALIZE_RULES, counters=counters) == 1
    assert counters == {"fuse-batched-requantize": 1}
    assert _ops(g) == ["input", "input", "generalized_dense"]
    gen = g.outputs[0]
    assert gen.inputs[2] is None and gen.shape == (2, 8, 4) and gen.dtype == dtype
    assert gen.attrs["quantized"] is True and gen.attrs["requant_scale"] == 2.0**-7
    assert (gen.attrs["clip_lo"], gen.attrs["clip_hi"]) == (lo, hi)
    feeds = _batched_feeds()
    (got,), (want,) = ir.execute_graph(g, feeds), ir.execute_graph(build(), feeds)
    assert got.dtype == want.dtype and np.array_equal(got, want)


@pytest.mark.parametrize("blocker", ["second_consumer", "graph_output"])
def test_fuse_batched_requantize_refuses_an_observable_intermediate(blocker):
    d = ir.dense(*_batched())
    rq = ir.requantize(d, scale=2.0**-7)
    g = Graph([rq, ir.relu(d)] if blocker == "second_consumer" else [rq, d])
    assert apply_rules(g, LEGALIZE_RULES) == 0
    assert "requantize" in _ops(g) and "dense" in _ops(g)


def _clipped_2d():
    rq = ir.requantize(ir.dense(*_batched(w_dims=2)), scale=2.0**-7)
    return ir.clip(rq, lo=-128, hi=127)


OTHER_CASES = {
    # float operands: not an int8 GEMM
    "float": (lambda: ir.requantize(ir.dense(*_batched("float32")), scale=0.5), {}),
    # a 2-D second operand without a clip: no rule's chain
    "2d": (lambda: ir.requantize(ir.dense(*_batched(w_dims=2)), scale=2.0**-7), {}),
    # a 2-D second operand with a clip: the 2-D rule's chain
    "2d_clip": (_clipped_2d, {"fuse-quantized-epilogue-nobias": 1}),
    # a requantize to a float dtype saturates nowhere
    "float_requantize": (
        lambda: ir.requantize(ir.dense(*_batched()), scale=2.0**-7, out_dtype="float32"),
        {},
    ),
}


@pytest.mark.parametrize("case", sorted(OTHER_CASES))
def test_fuse_batched_requantize_leaves_other_chains_to_other_rules(case):
    build, fired = OTHER_CASES[case]
    g = Graph([build()])
    counters: dict[str, int] = {}
    apply_rules(g, LEGALIZE_RULES, counters=counters)
    assert counters == fired


def test_fold_transpose_transpose_identity_and_composed():
    x = ir.input_((2, 3, 4), "float32", name="x")
    g = Graph([ir.transpose(ir.transpose(x, (2, 1, 0)), (2, 1, 0))])
    assert apply_rules(g, FOLD_TRANSPOSE_RULES) == 1
    assert g.outputs[0] is x  # identity composition folds to the source

    y = ir.input_((2, 3, 4), "float32", name="y")
    g2 = Graph([ir.transpose(ir.transpose(y, (1, 0, 2)), (2, 1, 0))])
    assert apply_rules(g2, FOLD_TRANSPOSE_RULES) == 1
    (t,) = [n for n in g2.toposort() if n.op == "transpose"]
    assert t.attrs["perm"] == (2, 0, 1) and t.shape == (4, 2, 3)
    xv = np.random.default_rng(0).normal(size=(2, 3, 4)).astype(np.float32)
    ref = xv.transpose((1, 0, 2)).transpose((2, 1, 0))
    assert np.array_equal(ir.execute_graph(g2, {"y": xv})[0], ref)


def test_fold_transpose_transpose_refuses_shared_inner():
    x = ir.input_((2, 3, 4), "float32", name="x")
    inner = ir.transpose(x, (2, 1, 0))
    g = Graph([ir.transpose(inner, (2, 1, 0)), ir.relu(inner)])
    assert apply_rules(g, FOLD_TRANSPOSE_RULES) == 0
    assert _ops(g).count("transpose") == 2


def test_fold_transpose_into_dense():
    k = ir.input_((16, 64), "int8", name="k")
    q = ir.input_((16, 64), "int8", name="q")
    g = Graph([ir.dense(q, ir.transpose(k, (1, 0)))])
    assert apply_rules(g, FOLD_TRANSPOSE_RULES) == 1
    gen = g.outputs[0]
    assert gen.op == "dense" and gen.attrs["transpose_b"] is True
    assert gen.inputs[1] is k and "transpose" not in _ops(g)


def test_fold_transpose_into_dense_refuses_const_and_shared():
    # constant weight: constant folding will remove the transpose entirely,
    # which beats re-reading it transposed per run — the rule declines.
    x = ir.input_((4, 8), "int8", name="x")
    w = ir.const(np.ones((16, 8), np.int8))
    g = Graph([ir.dense(x, ir.transpose(w, (1, 0)))])
    assert apply_rules(g, FOLD_TRANSPOSE_RULES) == 0

    # shared transpose: a second consumer keeps the layout op alive
    k = ir.input_((16, 64), "int8", name="k")
    t = ir.transpose(k, (1, 0))
    g2 = Graph([ir.dense(ir.input_((16, 64), "int8", name="q"), t), ir.relu(t)])
    assert apply_rules(g2, FOLD_TRANSPOSE_RULES) == 0


def _gen_dense(x, k=8, quantized=False, seed=0):
    rng = np.random.default_rng(seed)
    w = ir.const(rng.integers(-8, 8, (x.shape[-1], k)).astype(np.int8))
    b = ir.const(rng.integers(-50, 50, (k,)).astype(np.int32))
    attrs = {"quantized": False, "activation": None}
    if quantized:
        attrs = {"quantized": True, "requant_scale": 0.05, "clip_lo": -128, "clip_hi": 127}
    return Node(
        "generalized_dense", [x, w, b], attrs, shape=(*x.shape[:-1], k), dtype="int8" if quantized else "int32"
    )


def test_fuse_residual_fires_minimal():
    x = ir.input_((4, 8), "int8", name="x")
    gen = _gen_dense(x, k=8, quantized=True)
    g = Graph([ir.add(gen, x)])
    assert apply_rules(g, RESIDUAL_RULES) == 1
    fused = g.outputs[0]
    assert fused.op == "generalized_dense" and fused.attrs["residual"] is True
    assert len(fused.inputs) == 4 and fused.inputs[3] is x


def test_fuse_residual_rhs_and_refusals():
    x = ir.input_((4, 8), "int8", name="x")
    gen = _gen_dense(x, k=8, quantized=True)
    g = Graph([ir.add(x, gen)])  # generalized op on the rhs
    assert apply_rules(g, RESIDUAL_RULES) == 1
    assert g.outputs[0].attrs["residual"] is True

    # a second consumer of the generalized op blocks the fusion
    gen2 = _gen_dense(ir.input_((4, 8), "int8", name="x"), k=8, quantized=True)
    g2 = Graph([ir.add(gen2, gen2.inputs[0]), ir.relu(gen2)])
    assert apply_rules(g2, RESIDUAL_RULES) == 0

    # shape-changing (broadcast) adds are declined
    gen3 = _gen_dense(ir.input_((4, 8), "int8", name="x"), k=8, quantized=True)
    row = ir.const(np.ones((1, 8), np.int8))
    assert apply_rules(Graph([ir.add(gen3, row)]), RESIDUAL_RULES) == 0


def test_fuse_conv_pool_fires_minimal():
    x = ir.input_((1, 6, 6, 4), "int8", name="x")
    w = ir.const(np.ones((3, 3, 4, 8), np.int8))
    conv = Node(
        "generalized_conv2d",
        [x, w, None],
        {"stride": 1, "padding": 0, "quantized": True, "requant_scale": 0.1,
         "clip_lo": -128, "clip_hi": 127},
        shape=(1, 4, 4, 8),
        dtype="int8",
    )
    g = Graph([ir.max_pool2d(conv, size=2, stride=2)])
    assert apply_rules(g, CONV_POOL_RULES) == 1
    fused = g.outputs[0]
    assert fused.op == "generalized_conv2d"
    assert fused.attrs["pool"] == {"size": 2, "stride": 2, "conv_shape": (1, 4, 4, 8)}
    assert fused.shape == (1, 2, 2, 8)


def test_fuse_conv_pool_refuses_shared_conv():
    x = ir.input_((1, 6, 6, 4), "int8", name="x")
    w = ir.const(np.ones((3, 3, 4, 8), np.int8))
    conv = Node(
        "generalized_conv2d", [x, w, None],
        {"stride": 1, "padding": 0, "quantized": False, "activation": None},
        shape=(1, 4, 4, 8), dtype="int32",
    )
    g = Graph([ir.max_pool2d(conv, 2), ir.relu(conv)])
    assert apply_rules(g, CONV_POOL_RULES) == 0


# -- engine mechanics ----------------------------------------------------------


def test_match_pattern_wildcard_and_arity():
    x = ir.input_((2, 4), "int8", name="x")
    w = ir.const(np.ones((4, 4), np.int8))
    d = ir.dense(x, w)
    g = Graph([d])
    cons = {n: list(c) for n, c in g.consumers().items()}
    m = match_pattern(P("dense", any_("a"), any_("b")), d, cons, set())
    assert m is not None and m["a"] is x and m["b"] is w
    # wrong arity: dense has 2 inputs
    assert match_pattern(P("dense", any_()), d, cons, set()) is None


def test_wildcard_captures_absent_operand_as_none():
    """The documented contract: ``any_("name")`` matches an absent (None)
    operand and the capture reads back as None — build fns must not
    KeyError on bias-less generalized ops."""
    x = ir.input_((2, 4), "int8", name="x")
    w = ir.const(np.ones((4, 4), np.int8))
    gen = Node(
        "generalized_dense", [x, w, None], {"quantized": False, "activation": None},
        shape=(2, 4), dtype="int32",
    )
    g = Graph([ir.relu(gen)])
    cons = {n: list(c) for n, c in g.consumers().items()}
    pat = P("relu", P("generalized_dense", any_("a"), any_("w"), any_("bias")))
    m = match_pattern(pat, g.outputs[0], cons, set())
    assert m is not None
    assert m["bias"] is None and m["a"] is x and m["w"] is w


def test_rule_priority_is_list_order():
    """At one anchor, the first rule in the list wins."""
    hits = []

    @rule("first", P("relu", any_("src")))
    def r1(m: Match, g):
        hits.append("first")
        return None  # decline: the next rule gets a chance

    @rule("second", P("relu", any_("src")))
    def r2(m: Match, g):
        hits.append("second")
        return None

    g = Graph([ir.relu(ir.input_((2,), "float32", name="x"))])
    apply_rules(g, (r1, r2))
    assert hits == ["first", "second"]


def test_counters_record_rule_fires():
    g = Graph([_qchain()])
    counters: dict[str, int] = {}
    apply_rules(g, LEGALIZE_RULES, counters=counters)
    assert counters == {"fuse-quantized-epilogue": 1}


def test_description_contributed_pattern():
    """Targets plug in their own fusion patterns through the description —
    no traversal code, just a pattern and a build function."""
    desc = make_gemmini_description()

    @desc.register_rewrite_pattern(
        "absorb-requantize", P("requantize", P("generalized_dense", capture="gen"))
    )
    def absorb(m: Match, g):
        gen, root = m["gen"], m.root
        if gen.attrs.get("quantized"):
            return None
        return Node(
            gen.op,
            list(gen.inputs),
            {**gen.attrs, "quantized": True, "requant_scale": root.attrs["scale"],
             "clip_lo": -128, "clip_hi": 127},
            shape=root.shape,
            dtype=root.dtype,
        )

    rng = np.random.default_rng(0)
    x = ir.input_((2, 16), "int8", name="x")
    w = ir.const(rng.integers(-8, 8, (16, 8)).astype(np.int8))
    b = ir.const(rng.integers(-20, 20, (8,)).astype(np.int32))
    graph = ir.Graph([ir.requantize(ir.bias_add(ir.dense(x, w), b), scale=0.5)])
    ref = ir.execute_graph(
        ir.Graph([ir.requantize(ir.bias_add(ir.dense(x, w), b), scale=0.5)]),
        {"x": np.full((2, 16), 3, np.int8)},
    )[0]

    backend = build_backend(desc)
    mod = backend.compile_graph(graph, mode="proposed")
    assert mod.pass_report.rewrites_by_pass().get("target_patterns") == 1
    gen = [n for n in mod.graph.toposort() if n.op == "generalized_dense"]
    assert gen and gen[0].attrs["quantized"] is True
    out = mod.run({"x": np.full((2, 16), 3, np.int8)})[0]
    assert np.array_equal(out, ref)


def test_fixed_point_guard():
    """A rule that rewrites a node to an equivalent new node forever must
    hit the round guard instead of spinning."""

    @rule("spin", P("relu", any_("src")))
    def spin(m: Match, g):
        return Node("relu", [m["src"]], {}, shape=m.root.shape, dtype=m.root.dtype)

    g = Graph([ir.relu(ir.input_((2,), "float32", name="x"))])
    with pytest.raises(RuntimeError, match="fixed point"):
        apply_rules(g, (spin,), max_rounds=5)


# -- multi-output graphs through the full pipeline -----------------------------


def test_multi_output_graph_compiles_and_runs():
    """Both outputs of a multi-output graph survive compilation in every
    mode, with the first output feeding the second chain AND being
    observable — planned and reference agree."""
    def build():
        x = ir.input_((2, 16), "int8", name="x")
        h1 = _qchain(x)
        h2 = _qchain(h1)
        return Graph([h1, h2], name="two_heads")

    feeds = {"x": np.random.default_rng(2).integers(-128, 128, (2, 16)).astype(np.int8)}
    ref = ir.execute_graph(build(), feeds)
    backend = build_backend(make_gemmini_description())
    for mode in ("proposed", "c_toolchain", "naive"):
        mod = backend.compile_graph(build(), mode=mode)
        planned = mod.run(feeds)
        assert len(planned) == 2
        for p, r in zip(planned, ref):
            assert np.array_equal(p, r), mode
    # in optimized modes both chains legalized even though h1 is an output
    mod_opt = backend.compile_graph(build(), mode="proposed")
    gens = [n for n in mod_opt.graph.toposort() if n.op == "generalized_dense"]
    assert len(gens) == 2
    assert mod_opt.graph.outputs[0] is gens[0]
