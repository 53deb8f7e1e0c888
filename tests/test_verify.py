"""The static verification layer (``repro.core.verify``).

The heart of this file is the seeded mutation corpus: deliberately broken
graphs / plans / shard sets, each caught by the verifier with its distinct
diagnostic code — and never by a crash (every corpus entry goes through
the collect-style API, which returns diagnostics instead of raising).
Around it: the pass-invariant gate (offending pass named), the wired-in
``validate_schedule`` (corrupt cached schedules fail compilation), the
tampered-artifact rejection on ``repro.load``, the cache_spec artifact
round-trip regression, and the zero-diagnostic smoke across compile
shapes.
"""

import json

import numpy as np
import pytest

import repro
from repro.core import ir
from repro.core.artifact import (
    _read_arrays,
    graph_fingerprint,
    graph_from_dict,
)
from repro.core.executor import ExecutionPlan, PlanStep
from repro.core.ir import CacheSpec
from repro.core.pass_manager import GraphPass, PassContext, PassManager
from repro.core.verify import (
    VerifyError,
    collect,
    resolve_verify,
    verify_collectives,
    verify_graph,
    verify_plan,
)

GEMMINI = repro.Target("gemmini", mode="optimized")


def codes(diags):
    return {d.code for d in diags}


def qdense_graph():
    """A small, *legal* quantized dense graph (the corpus mutates copies)."""
    x = ir.input_((4, 8), "int8", name="x")
    w = ir.const(np.ones((8, 16), dtype=np.int8), name="w")
    y = ir.dense(x, w)
    return ir.Graph(outputs=[y], name="qdense"), x, w, y


# ---------------------------------------------------------------------------
# the mutation corpus: graph-level entries
# ---------------------------------------------------------------------------


def test_legal_graph_is_clean():
    g, *_ = qdense_graph()
    assert verify_graph(g) == []


def test_wrong_dense_k_dim_is_G_SHAPE():
    g, _x, _w, y = qdense_graph()
    y.shape = (4, 12)  # K says 16
    assert "G_SHAPE" in codes(verify_graph(g))


def test_transposed_b_k_dim_is_checked_from_the_right_axis():
    x = ir.input_((4, 8), "int8", name="x")
    w = ir.const(np.ones((16, 8), dtype=np.int8))  # (K, C) storage
    y = ir.Node("dense", [x, w], {"transpose_b": True}, shape=(4, 16), dtype="int32")
    g = ir.Graph(outputs=[y], name="tb")
    assert verify_graph(g) == []
    y.shape = (4, 8)  # the untransposed reading
    assert "G_SHAPE" in codes(verify_graph(g))


def test_dtype_illegal_offload_is_G_TARGET():
    desc = repro.REGISTRY.get("gemmini")
    assert not desc.supports_dtype("dense", "float32")  # int8 datapath
    x = ir.input_((4, 8), "float32", name="x")
    w = ir.const(np.ones((8, 16), dtype=np.float32))
    y = ir.dense(x, w)
    y.target = "accel"
    g = ir.Graph(outputs=[y], name="float_offload")
    assert "G_TARGET" in codes(verify_graph(g, desc))
    # the same graph is fine when the op stays on the host
    y.target = "host"
    assert verify_graph(g, desc) == []


def test_offloaded_cache_op_is_G_TARGET():
    cache = ir.input_((8, 4), "int8", name="k_cache")
    read = ir.kv_cache_read(cache)
    read.target = "accel"  # cache ops are host-resident by contract
    g = ir.Graph(outputs=[read], name="cache_offload")
    assert "G_TARGET" in codes(verify_graph(g))


def test_cycle_is_G_CYCLE():
    x = ir.input_((2, 2), "float32", name="x")
    a = ir.relu(x)
    b = ir.relu(a)
    a.inputs[0] = b  # a <-> b
    g = ir.Graph(outputs=[b], name="cyclic")
    diags = verify_graph(g)
    assert codes(diags) == {"G_CYCLE"}


def test_dangling_input_is_G_DANGLING():
    x = ir.input_((2, 2), "float32", name="x")
    r = ir.relu(x)
    r.inputs[0] = None
    g = ir.Graph(outputs=[r], name="dangling")
    assert "G_DANGLING" in codes(verify_graph(g))


def test_generalized_bias_may_be_none_but_x_may_not():
    x = ir.input_((4, 8), "int8", name="x")
    w = ir.const(np.ones((8, 16), dtype=np.int8))
    y = ir.Node(
        "generalized_dense", [x, w, None], {}, shape=(4, 16), dtype="int32"
    )
    g = ir.Graph(outputs=[y], name="gen")
    assert verify_graph(g) == []  # absent bias is legal
    y.inputs[0] = None
    assert "G_DANGLING" in codes(verify_graph(g))


def test_bad_cache_spec_wiring_is_G_CACHE():
    cache = ir.input_((8, 4), "int8", name="k_cache")
    upd = ir.input_((1, 4), "int8", name="upd")
    pos = ir.input_((), "int32", name="pos")
    new = ir.kv_cache_append(cache, upd, pos)
    g = ir.Graph(outputs=[new], name="dec")
    g.cache_spec = CacheSpec(max_len=8, state=(("k_cache", 0),))
    assert verify_graph(g) == []
    # state names a non-existent cache input
    g.cache_spec = CacheSpec(max_len=8, state=(("v_cache", 0),))
    assert "G_CACHE" in codes(verify_graph(g))
    # state wires to an out-of-range output index
    g.cache_spec = CacheSpec(max_len=8, state=(("k_cache", 3),))
    assert "G_CACHE" in codes(verify_graph(g))
    # spec capacity disagrees with the cache input's sequence dim
    g.cache_spec = CacheSpec(max_len=64, state=(("k_cache", 0),))
    assert "G_CACHE" in codes(verify_graph(g))


def test_bad_transpose_perm_is_G_ATTRS():
    x = ir.input_((2, 3), "float32", name="x")
    t = ir.transpose(x, (1, 0))
    t.attrs["perm"] = (0, 0)
    g = ir.Graph(outputs=[t], name="perm")
    assert "G_ATTRS" in codes(verify_graph(g))


def test_missing_required_attr_is_G_ATTRS():
    x = ir.input_((2, 3), "int32", name="x")
    c = ir.clip(x)
    del c.attrs["lo"]
    g = ir.Graph(outputs=[c], name="noclip")
    assert "G_ATTRS" in codes(verify_graph(g))


def test_unknown_op_is_G_OP():
    x = ir.input_((2, 2), "float32", name="x")
    y = ir.Node("frobnicate", [x], shape=(2, 2), dtype="float32")
    g = ir.Graph(outputs=[y], name="unknown")
    assert "G_OP" in codes(verify_graph(g))


def test_duplicate_input_names_is_G_SSA():
    a = ir.input_((2, 2), "float32", name="x")
    b = ir.input_((2, 2), "float32", name="x")  # feeds are keyed by name
    g = ir.Graph(outputs=[ir.add(a, b)], name="dup")
    assert "G_SSA" in codes(verify_graph(g))


def test_dtype_preservation_violation_is_G_DTYPE():
    x = ir.input_((2, 2), "int8", name="x")
    r = ir.relu(x)
    r.dtype = "float32"  # relu preserves its operand dtype
    g = ir.Graph(outputs=[r], name="dtype")
    assert "G_DTYPE" in codes(verify_graph(g))


def test_mixed_dense_operand_dtypes_is_G_DTYPE():
    x = ir.input_((4, 8), "int8", name="x")
    w = ir.const(np.ones((8, 16), dtype=np.float32))
    y = ir.dense(x, w)
    g = ir.Graph(outputs=[y], name="mixed")
    assert "G_DTYPE" in codes(verify_graph(g))


def test_collective_rank_outside_parts_is_G_ATTRS():
    x = ir.input_((4, 8), "int8", name="x")
    ag = ir.all_gather(x, 1, group="g0", rank=0, parts=2)
    ag.attrs["rank"] = 5
    g = ir.Graph(outputs=[ag], name="coll")
    assert "G_ATTRS" in codes(verify_graph(g))


def test_const_value_disagreeing_with_node_is_G_SHAPE_and_G_DTYPE():
    w = ir.const(np.ones((3, 3), dtype=np.int8))
    w.shape = (2, 2)
    w.dtype = "int32"
    g = ir.Graph(outputs=[ir.relu(w)], name="badconst")
    got = codes(verify_graph(g))
    assert "G_SHAPE" in got and "G_DTYPE" in got


# ---------------------------------------------------------------------------
# the mutation corpus: plan-level entries
# ---------------------------------------------------------------------------


def _step(slot, args, op="relu", name="s", lane="host"):
    return PlanStep(slot, lambda *a: a[0] if a else None, tuple(args), op, name, lane)


def _plan(steps, *, n_slots=8, inputs=(("x", 1),), outputs=(1,)):
    return ExecutionPlan(
        n_slots=n_slots,
        input_slots=tuple(inputs),
        const_slots=(),
        steps=tuple(steps),
        output_slots=tuple(outputs),
    )


def test_read_before_write_is_P_UNWRITTEN():
    plan = _plan([_step(2, (5,))], outputs=(2,))
    assert "P_UNWRITTEN" in codes(verify_plan(plan))


def test_clobbered_slot_is_P_CLOBBER():
    plan = _plan([_step(2, (1,)), _step(2, (1,), name="again")], outputs=(2,))
    assert "P_CLOBBER" in codes(verify_plan(plan))


def test_step_writing_an_input_slot_is_P_CLOBBER():
    plan = _plan([_step(1, (1,))], outputs=(1,))
    assert "P_CLOBBER" in codes(verify_plan(plan))


def test_undefined_output_slot_is_P_OUTPUT():
    plan = _plan([_step(2, (1,))], outputs=(5,))
    assert "P_OUTPUT" in codes(verify_plan(plan))


def test_slot_outside_arena_is_P_BOUNDS():
    plan = _plan([_step(9, (1,))], n_slots=4, outputs=(1,))
    assert "P_BOUNDS" in codes(verify_plan(plan))


def test_compiled_plans_are_clean():
    # naive mode interleaves host epilogues with accel GEMMs
    module = repro.compile("mlp_tiny", target=repro.Target("gemmini", mode="naive"))
    plan = module.finalize()
    assert {s.lane for s in plan.steps} == {"host", "accel"}
    assert verify_plan(plan) == []


# ---------------------------------------------------------------------------
# the mutation corpus: collective (cross-shard) entries
# ---------------------------------------------------------------------------


def _coll(group, rank, *, op="all_gather", parts=2, axis=1, dtype="int8", shape=(4, 4)):
    return {
        "group": group,
        "op": op,
        "rank": rank,
        "parts": parts,
        "axis": axis,
        "dtype": dtype,
        "shape": shape,
        "node": f"{group}_r{rank}",
    }


def test_consistent_shard_sequences_are_clean():
    seqs = {
        0: [_coll("g0", 0), _coll("g1", 0)],
        1: [_coll("g0", 1), _coll("g1", 1)],
    }
    assert verify_collectives(seqs) == []


def test_mismatched_shard_collective_order_is_C_ORDER():
    seqs = {
        0: [_coll("g0", 0), _coll("g1", 0)],
        1: [_coll("g1", 1), _coll("g0", 1)],  # the deadlock shape
    }
    assert "C_ORDER" in codes(verify_collectives(seqs))


def test_mismatched_contribution_shape_is_C_MISMATCH():
    seqs = {
        0: [_coll("g0", 0, shape=(4, 4))],
        1: [_coll("g0", 1, shape=(2, 4))],
    }
    assert "C_MISMATCH" in codes(verify_collectives(seqs))


def test_absent_rank_is_C_MISMATCH():
    seqs = {0: [_coll("g0", 0)], 1: []}  # rank 1 never joins g0
    assert "C_MISMATCH" in codes(verify_collectives(seqs))


def test_doubly_issued_group_is_C_MISMATCH():
    seqs = {
        0: [_coll("g0", 0), _coll("g0", 0)],
        1: [_coll("g0", 1)],
    }
    assert "C_MISMATCH" in codes(verify_collectives(seqs))


def test_real_sharded_compile_is_clean_and_exposes_sequences():
    module = repro.compile(
        "transformer_block",
        target=repro.Target("gemmini", mode="optimized", mesh=(1, 2)),
        options=repro.CompileOptions(verify="each"),
    )
    seqs = module.collective_sequences()
    assert set(seqs) == {(0, 0), (0, 1)}
    assert all(len(s) > 0 for s in seqs.values())
    assert verify_collectives(module.shards) == []
    # swapping two collectives on ONE shard is exactly the deadlock the
    # checker exists for
    broken = {k: list(v) for k, v in seqs.items()}
    broken[(0, 1)] = [broken[(0, 1)][1], broken[(0, 1)][0]] + broken[(0, 1)][2:]
    assert "C_ORDER" in codes(verify_collectives(broken))


# ---------------------------------------------------------------------------
# the dispatching front door + the zero-diagnostic smoke
# ---------------------------------------------------------------------------


def test_collect_dispatches_and_verify_raises():
    g, _x, _w, y = qdense_graph()
    assert repro.verify(g) == []
    y.shape = (4, 12)
    with pytest.raises(repro.VerifyError) as ei:
        repro.verify(g)
    assert any(d.code == "G_SHAPE" for d in ei.value.diagnostics)
    assert "G_SHAPE" in str(ei.value)
    with pytest.raises(TypeError):
        collect(42)


def test_zero_diagnostics_across_compile_shapes():
    # single-device modules across modes
    for mode in ("naive", "baseline", "optimized"):
        m = repro.compile("mlp_tiny", target=repro.Target("gemmini", mode=mode))
        assert collect(m) == [], mode
    # a stateful decode module
    assert collect(repro.compile("attn_decode", target=GEMMINI)) == []
    # a batched module (all buckets + the per-sample plan)
    batched = repro.compile(
        "mlp_tiny",
        target=GEMMINI,
        options=repro.CompileOptions(batch_buckets=(1, 2)),
    )
    assert collect(batched) == []


def test_resolve_verify_modes(monkeypatch):
    assert resolve_verify("each") == "each"
    assert resolve_verify("final") == "final"
    assert resolve_verify("off") == "off"
    assert resolve_verify("1") == "each"
    monkeypatch.delenv("REPRO_VERIFY", raising=False)
    assert resolve_verify(None) == "off"
    monkeypatch.setenv("REPRO_VERIFY", "each")
    assert resolve_verify(None) == "each"
    monkeypatch.setenv("REPRO_VERIFY", "1")
    assert resolve_verify(None) == "each"
    with pytest.raises(ValueError):
        resolve_verify("sometimes")
    with pytest.raises(ValueError):
        repro.CompileOptions(verify="sometimes")


# ---------------------------------------------------------------------------
# the pass-invariant gate
# ---------------------------------------------------------------------------


def test_pass_gate_attributes_the_offending_pass():
    x = ir.input_((2, 4), "int8", name="x")
    g = ir.Graph(outputs=[ir.relu(x)], name="gated")

    def breaker(graph, ctx):
        graph.outputs[0].dtype = "float32"  # relu must preserve int8
        return 1

    pm = PassManager(
        [
            GraphPass(name="benign", fn=lambda graph, ctx: 0),
            GraphPass(name="breaker", fn=breaker),
        ],
        verify="each",
    )
    with pytest.raises(VerifyError) as ei:
        pm.run(g, PassContext())
    assert "breaker" in str(ei.value)
    assert "benign" not in str(ei.value)
    assert any(d.code == "G_DTYPE" for d in ei.value.diagnostics)


def test_pass_gate_final_mode_checks_once_at_the_end():
    x = ir.input_((2, 4), "int8", name="x")
    g = ir.Graph(outputs=[ir.relu(x)], name="finalgate")

    def break_then_fix(graph, ctx):
        graph.outputs[0].dtype = "float32"
        return 1

    def fixer(graph, ctx):
        graph.outputs[0].dtype = "int8"
        return 1

    # transiently broken between passes is fine under 'final'
    pm = PassManager(
        [GraphPass(name="b", fn=break_then_fix), GraphPass(name="f", fn=fixer)],
        verify="final",
    )
    pm.run(g, PassContext())  # does not raise
    # but a pipeline that ENDS broken is caught
    pm2 = PassManager([GraphPass(name="b", fn=break_then_fix)], verify="final")
    with pytest.raises(VerifyError):
        pm2.run(g, PassContext())


def test_pass_gate_off_by_default():
    x = ir.input_((2, 4), "int8", name="x")
    g = ir.Graph(outputs=[ir.relu(x)], name="ungated")

    def breaker(graph, ctx):
        graph.outputs[0].dtype = "float32"
        return 1

    pm = PassManager([GraphPass(name="breaker", fn=breaker)])
    pm.run(g, PassContext())  # verify defaults to off: no raise


def test_compile_options_verify_each_end_to_end():
    m = repro.compile(
        "mlp_tiny",
        target=GEMMINI,
        options=repro.CompileOptions(verify="each"),
    )
    assert collect(m) == []


# ---------------------------------------------------------------------------
# satellite: validate_schedule wired into the compile path
# ---------------------------------------------------------------------------


def test_corrupt_cached_schedule_fails_compile_with_S_SCHEDULE(tmp_path):
    from repro.core.strategy import workload_from_node

    target = repro.Target("gemmini", mode="optimized", cache_dir=tmp_path)
    fresh = repro.CompileOptions(fresh_backend=True)
    module = repro.compile("mlp_tiny", target=target, options=fresh)
    backend = module.backend
    node = next(n for n in module.graph.toposort() if n.target == "accel")
    key = backend._cache_key(workload_from_node(node), "proposed")
    cached = backend.schedule_cache.get(key)
    assert cached is not None
    # corrupt the persisted winner: inflate one DRAM-level factor so the
    # factor product no longer covers the padded dim
    cached.best.temporal[-1]["N"] *= 7
    backend.schedule_cache.put(key, cached)
    backend.schedule_cache.flush()
    with pytest.raises(repro.VerifyError) as ei:
        repro.compile("mlp_tiny", target=target, options=fresh)
    diags = ei.value.diagnostics
    assert any(d.code == "S_SCHEDULE" for d in diags)
    # the report names the offending node and the coverage violation
    assert "selected schedule for node" in str(ei.value)
    assert "factors product" in str(ei.value)


# ---------------------------------------------------------------------------
# satellite + acceptance: artifacts are verified before execution
# ---------------------------------------------------------------------------


def _tamper_host_node_shape(path):
    """Hand-edit a saved artifact: grow one host node's shape and recompute
    the graph fingerprint, so every *content* check passes and only static
    verification can notice (the plan skeleton carries no shapes)."""
    manifest = json.loads((path / "manifest.json").read_text())
    node = next(
        nd
        for nd in manifest["graph"]["nodes"]
        if nd["op"] in ("requantize", "clip", "bias_add", "quantize")
    )
    node["shape"] = [d + 1 for d in node["shape"]]
    arrays = _read_arrays(path, manifest)
    tampered = graph_from_dict(manifest["graph"], arrays)
    manifest["graph_fingerprint"] = graph_fingerprint(tampered)
    (path / "manifest.json").write_text(json.dumps(manifest))


def test_graph_tampered_artifact_is_rejected_by_the_verifier(tmp_path):
    module = repro.compile("mlp_tiny", target=repro.Target("gemmini", mode="naive"))
    p = tmp_path / "art"
    repro.save(module, p)
    assert collect(repro.load(p)) == []  # round trip verifies clean
    _tamper_host_node_shape(p)
    # rejected statically — a VerifyError naming the inconsistency, not an
    # ArtifactError (the fingerprint matches) and not a runtime crash
    with pytest.raises(repro.VerifyError) as ei:
        repro.load(p)
    assert any(d.code == "G_SHAPE" for d in ei.value.diagnostics)


def test_artifact_store_treats_verify_failure_as_miss(tmp_path):
    target = repro.Target("gemmini", mode="naive")
    opts = repro.CompileOptions(artifact_dir=tmp_path, fresh_backend=True)
    repro.compile("mlp_tiny", target=target, options=opts)
    entry = next(tmp_path.glob("*/*/manifest.json")).parent
    _tamper_host_node_shape(entry)
    # the write-through store must recompile (miss + warning), never raise
    with pytest.warns(RuntimeWarning, match="unusable compile artifact"):
        module = repro.compile("mlp_tiny", target=target, options=opts)
    assert collect(module) == []


# ---------------------------------------------------------------------------
# satellite: the cache_spec serialization gap the verifier work surfaced
# ---------------------------------------------------------------------------


def test_cache_spec_survives_artifact_round_trip(tmp_path):
    module = repro.compile("attn_decode", target=GEMMINI)
    spec = module.graph.cache_spec
    assert spec is not None and spec.state  # a real decode-state contract
    repro.save(module, tmp_path / "dec")
    restored = repro.load(tmp_path / "dec")
    assert restored.graph.cache_spec == spec
    # the decode loop the spec encodes actually works on the restored
    # module: cache outputs feed back as next-step cache inputs
    from repro.core.zoo import DECODE_ZOO

    feeds = DECODE_ZOO["attn_decode"].feeds()
    outs = restored.run(feeds)
    for in_name, out_idx in spec.state:
        got = outs[out_idx]
        want_shape = dict(
            (n, s) for n, s, _ in restored.input_signature()
        )[in_name]
        assert got.shape == want_shape and str(got.dtype) == spec.dtype


def test_cache_spec_is_part_of_the_graph_fingerprint():
    module = repro.compile("attn_decode", target=GEMMINI)
    g = module.graph
    bare = ir.Graph(outputs=g.outputs, name=g.name, cache_spec=None)
    assert graph_fingerprint(g) != graph_fingerprint(bare)
