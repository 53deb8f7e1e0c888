"""PassManager: per-pass instrumentation, per-mode pipelines, the new
optimization passes (CSE / DCE / fold_constants single sweep), graph
traversal caching, trace/dump debugging hooks, and the cycle-model
no-regression guarantees of the fusion passes."""

import numpy as np

from repro.core import build_backend, ir
from repro.core.descriptions import make_gemmini_description
from repro.core.ir import Graph
from repro.core.pass_manager import PassContext, PassManager
from repro.core.passes import fold_constants, frontend_passes, passes_for_mode
from repro.core.zoo import get_model

BACKEND = build_backend(make_gemmini_description())
DESC = BACKEND.desc


def _qdense_graph():
    rng = np.random.default_rng(0)
    x = ir.input_((4, 32), "int8", name="x")
    w_fp = ir.const(rng.normal(size=(16, 32)).astype(np.float32), name="w")
    w_q = ir.quantize(ir.transpose(w_fp, (1, 0)), scale=0.05)
    b = ir.const(np.zeros(16, np.int32), name="b")
    out = ir.clip(ir.requantize(ir.bias_add(ir.dense(x, w_q), b), scale=0.1))
    return ir.Graph([out], name="qdense")


# -- report structure ----------------------------------------------------------


def test_report_records_every_pass():
    g = _qdense_graph()
    pm = PassManager(frontend_passes(DESC))
    report = pm.run(g, PassContext(desc=DESC, mode="proposed"))
    names = [p.name for p in report.passes]
    assert names == [
        "fold_transpose",
        "legalize",
        "fuse_residual",
        "fuse_conv_pool",
        "fold_constants",
        "cse",
        "dce",
        "partition",
    ]
    by_pass = report.rewrites_by_pass()
    assert by_pass["legalize"] == 1 and by_pass["fold_constants"] == 2
    assert report.total_rewrites >= 4
    for p in report.passes:
        assert p.duration_ms >= 0 and p.nodes_before >= p.nodes_after - 1
    d = report.to_dict()
    assert d["graph"] == "qdense" and d["mode"] == "proposed"
    assert d["passes"][1]["rules"] == {"fuse-quantized-epilogue": 1}
    assert "legalize" in report.summary()


def test_naive_mode_is_partition_only():
    names = [p.name for p in passes_for_mode(DESC, "naive")]
    assert names == ["partition"]
    # ...and the optimized modes share one full pipeline
    assert [p.name for p in passes_for_mode(DESC, "proposed")] == [
        p.name for p in passes_for_mode(DESC, "c_toolchain")
    ]


def test_compile_attaches_pass_report():
    mod = BACKEND.compile_graph(_qdense_graph(), mode="proposed")
    assert mod.pass_report is not None
    assert mod.pass_report.rewrites_by_pass()["legalize"] == 1
    assert mod.pass_report.mode == "proposed"


# -- the new optimization passes ----------------------------------------------


def test_cse_merges_duplicate_subexpressions():
    rng = np.random.default_rng(0)
    x = ir.input_((2, 16), "int8", name="x")
    w1 = ir.const(rng.integers(-8, 8, (16, 8)).astype(np.int8))
    w2 = ir.const(np.array(w1.value))  # value-equal, distinct node
    out = ir.add(ir.dense(x, w1), ir.dense(x, w2))
    g = Graph([out], name="dup")
    feeds = {"x": rng.integers(-128, 128, (2, 16)).astype(np.int8)}
    ref = ir.execute_graph(Graph([ir.add(ir.dense(x, w1), ir.dense(x, w2))]), feeds)[0]

    mod = BACKEND.compile_graph(g, mode="proposed")
    assert mod.pass_report.rewrites_by_pass()["cse"] >= 2  # const + dense
    denses = [n for n in mod.graph.toposort() if n.op == "dense"]
    assert len(denses) == 1  # one shared GEMM, scheduled once
    assert np.array_equal(mod.run(feeds)[0], ref)


def test_dce_removes_no_effect_nodes():
    x = ir.input_((2, 16), "int8", name="x")
    h = ir.transpose(x, (0, 1))  # identity perm
    h = ir.reshape(h, (2, 16))  # identity reshape
    h = ir.clip(h, lo=-128, hi=127)  # full int8 range: cannot clip
    g = Graph([ir.relu(h)], name="noop_chain")
    feeds = {"x": np.random.default_rng(0).integers(-128, 128, (2, 16)).astype(np.int8)}
    ref = np.maximum(feeds["x"], 0)

    mod = BACKEND.compile_graph(g, mode="proposed")
    assert mod.pass_report.rewrites_by_pass()["dce"] == 3
    assert [n.op for n in mod.graph.toposort()] == ["input", "relu"]
    assert np.array_equal(mod.run(feeds)[0], ref)


def test_dce_keeps_effective_clip_and_transpose():
    x = ir.input_((2, 16), "int8", name="x")
    g = Graph([ir.clip(ir.transpose(x, (1, 0)), lo=0, hi=127)])
    mod = BACKEND.compile_graph(g, mode="proposed")
    assert mod.pass_report.rewrites_by_pass()["dce"] == 0
    ops = [n.op for n in mod.graph.toposort()]
    assert "transpose" in ops and "clip" in ops


def test_fold_constants_single_sweep_collapses_chains():
    """The whole const preprocessing chain (transpose -> quantize) folds in
    one pass invocation — no per-rewrite graph restarts."""
    g = _qdense_graph()
    fold_constants(g)
    ops = [n.op for n in g.toposort()]
    assert "transpose" not in ops and "quantize" not in ops


# -- graph traversal caching ---------------------------------------------------


def test_toposort_and_consumers_are_cached():
    g = _qdense_graph()
    o1 = g.toposort()
    assert g.toposort() is o1  # cache hit: same list object
    c1 = g.consumers()
    assert g.consumers() is c1


def test_replace_node_invalidates_cache():
    g = _qdense_graph()
    o1 = list(g.toposort())
    old = g.outputs[0]
    new = ir.relu(old.inputs[0])
    g.replace_node(old, new)
    o2 = g.toposort()
    assert old not in o2 and new in o2
    assert o2 is not o1


def test_invalidate_after_manual_mutation():
    g = _qdense_graph()
    clip = g.outputs[0]
    g.toposort()
    g.outputs = [clip.inputs[0]]  # manual structural edit...
    g.invalidate()  # ...requires explicit invalidation
    assert clip not in g.toposort()


# -- debugging hooks -----------------------------------------------------------


def test_pass_dump_writes_before_after(tmp_path):
    g = _qdense_graph()
    pm = PassManager(frontend_passes(DESC))
    pm.run(g, PassContext(desc=DESC, mode="proposed", dump_dir=tmp_path))
    files = sorted(p.name for p in tmp_path.iterdir())
    assert any("legalize_before" in f for f in files)
    assert any("legalize_after" in f for f in files)
    assert any("partition_after" in f for f in files)


def test_pass_trace_env(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_PASS_TRACE", "1")
    pm = PassManager(frontend_passes(DESC))
    pm.run(_qdense_graph(), PassContext(desc=DESC, mode="proposed"))
    err = capsys.readouterr().err
    assert "[pass] qdense:legalize" in err


# -- fusion passes never cost cycles ------------------------------------------


def _cycles(model_name, mode, optimize):
    model = get_model(model_name)
    passes = None if optimize else frontend_passes(DESC, optimize=False)
    mod = BACKEND.compile_graph(model.build(), mode=mode, passes=passes)
    return mod.modeled_cycles()["total"], mod


def test_residual_and_transpose_fusion_cost_no_worse():
    opt, mod_opt = _cycles("transformer_block", "proposed", True)
    base, _ = _cycles("transformer_block", "proposed", False)
    assert opt <= base
    by_pass = mod_opt.pass_report.rewrites_by_pass()
    assert by_pass["fuse_residual"] == 2 and by_pass["fold_transpose"] == 1


def test_conv_pool_fusion_cost_no_worse():
    opt, mod_opt = _cycles("qcnn", "proposed", True)
    base, _ = _cycles("qcnn", "proposed", False)
    assert opt <= base
    assert mod_opt.pass_report.rewrites_by_pass()["fuse_conv_pool"] == 1


def test_optimized_pipeline_stays_bit_exact_vs_unoptimized():
    for name in ("transformer_block", "qcnn"):
        model = get_model(name)
        feeds = model.feeds(seed=11)
        ref = ir.execute_graph(model.build(), feeds)
        _, mod = _cycles(name, "proposed", True)
        # recompile: _cycles built its module from a fresh graph already
        for p, r in zip(mod.run(feeds), ref):
            assert np.array_equal(p, r), name


def test_custom_pass_list_override():
    """compile(passes=...) replaces the mode pipeline (here: nothing runs,
    so nothing is partitioned and the graph stays host-only)."""
    mod = BACKEND.compile_graph(_qdense_graph(), mode="proposed", passes=[])
    assert mod.pass_report.passes == []
    assert not mod.ops
    feeds = {"x": np.random.default_rng(1).integers(-128, 128, (4, 32)).astype(np.int8)}
    ref = ir.execute_graph(_qdense_graph(), feeds)[0]
    assert np.array_equal(mod.run(feeds)[0], ref)


def test_gelu_residual_epilogue_in_reference_executor():
    """The generalized-op reference semantics cover the fused gelu
    activation and residual epilogues (execute_node parity for rewritten
    graphs)."""
    rng = np.random.default_rng(0)
    x = ir.input_((4, 16), "float32", name="x")
    w = ir.const(rng.normal(size=(16, 16)).astype(np.float32))
    b = ir.const(rng.normal(size=(16,)).astype(np.float32))
    out = ir.add(ir.gelu(ir.bias_add(ir.dense(x, w), b)), x)
    g = Graph([out])
    feeds = {"x": rng.normal(size=(4, 16)).astype(np.float32)}
    ref = ir.execute_graph(Graph([ir.add(ir.gelu(ir.bias_add(ir.dense(x, w), b)), x)]), feeds)[0]
    from repro.core.passes import LEGALIZE_RULES, RESIDUAL_RULES
    from repro.core.rewrite import apply_rules

    apply_rules(g, LEGALIZE_RULES)
    apply_rules(g, RESIDUAL_RULES)
    (gen,) = [n for n in g.toposort() if n.op == "generalized_dense"]
    assert gen.attrs["activation"] == "gelu" and gen.attrs["residual"] is True
    assert np.array_equal(ir.execute_graph(g, feeds)[0], ref)
