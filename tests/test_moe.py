"""The routed-expert (Mixtral-style) path: the IR's new ops and their
references, the importer's named calls and ``ragged_dot``, verify's
transfer entries, the grouped Pallas kernel in interpret mode against
numpy, and the small Mixtral stack through ``repro.compile`` against the
benchmark's plain reference, exactly."""

from __future__ import annotations

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import executor, ir, lowering, trace
from repro.core.scheduler import grouped_block_m
from repro.core.verify import verify_graph, verify_plan
from repro.frontend import nn as fnn
from repro.frontend.importer import SUPPORTED_PRIMITIVES, trace_model
from repro.kernels import grouped as kgrouped
from repro.kernels.gemm import GemmKernelConfig

ROOT = Path(__file__).resolve().parents[1]
RNG = np.random.default_rng(16)


def _i8(*shape):
    return RNG.integers(-128, 128, shape).astype(np.int8)


# ---------------------------------------------------------------------------
# the IR's references
# ---------------------------------------------------------------------------


def test_rms_norm_ref_normalises_each_row():
    x = _i8(4, 64)
    out = ir.rms_norm_ref(x, 0.0625, 2.0**-5, 1e-5)
    xf = x.astype(np.float64) * 0.0625
    want = xf / np.sqrt(np.mean(xf * xf, axis=-1, keepdims=True) + 1e-5) / 2.0**-5
    assert out.dtype == np.int8 and out.shape == x.shape
    assert np.max(np.abs(out - np.clip(np.rint(want), -128, 127))) <= 1


def test_rope_ref_is_the_identity_at_position_zero_and_keeps_norms():
    from bench.spec import load_module

    model = load_module(ROOT / "bench/configs/mixtral_8x7b_int8.py", "mixtral_cfg")
    cos, sin = model.rope_tables(8, 16, 1e6)
    x = _i8(3, 8, 16)
    out = ir.rope_ref(x, cos, sin, 1.0, 1.0)
    assert np.array_equal(out[:, 0], x[:, 0])
    xf = x.astype(np.float64)
    rot = np.concatenate([-xf[..., 8:], xf[..., :8]], axis=-1)
    exact = xf * cos + rot * sin
    assert np.allclose(np.linalg.norm(exact, axis=-1), np.linalg.norm(xf, axis=-1))
    assert np.array_equal(out, np.clip(np.rint(exact), -128, 127))


def test_silu_mul_ref_gates_the_up_half():
    x = _i8(5, 16)
    g, u = x[:, :8].astype(np.float64) / 16, x[:, 8:].astype(np.float64) / 16
    want = np.clip(np.rint(g / (1 + np.exp(-g)) * u * 16), -128, 127)
    assert np.array_equal(ir.silu_mul_ref(x, 1 / 16, 1 / 16), want)


def test_route_takes_the_lower_expert_of_equal_logits():
    logits = np.array([[5, 9, 9, 1], [3, 3, 3, 3], [7, 2, 8, 8]], np.int32)
    assert ir.moe_route_ref(logits, 2).tolist() == [[1, 2], [0, 1], [2, 3]]
    assert ir.moe_route_ref(logits, 3).tolist() == [[1, 2, 0], [0, 1, 2], [2, 3, 0]]


def test_dispatch_sorts_rows_by_expert_and_combine_undoes_it():
    x = _i8(6, 8)
    idx = np.array([[2, 0], [0, 1], [1, 2], [2, 3], [3, 0], [0, 2]], np.int32)
    rows = ir.moe_dispatch_ref(x, idx)
    experts = np.sort(idx.reshape(-1), kind="stable")
    assert rows.shape == (12, 8)
    # token order within an expert: expert 0 gets tokens 0, 1, 4, 5
    assert np.array_equal(rows[experts == 0], x[[0, 1, 4, 5]])
    # equal expert rows weighted by gates that sum to one give the token back
    logits = RNG.integers(-300, 300, (6, 4)).astype(np.int32)
    back = ir.moe_combine_ref(rows, idx, logits, 2.0**-6, 1.0)
    assert np.array_equal(back, x)


def test_combine_weights_follow_the_softmax_of_the_chosen_logits():
    idx = np.array([[1, 0]], np.int32)
    logits = np.array([[0, 64, -999]], np.int32)  # gate of expert 1: sigmoid(1)
    y = np.array([[10], [100]], np.int8)  # rows of experts 0 and 1
    g1 = 1 / (1 + np.exp(-1.0))
    want = np.rint(g1 * 100 + (1 - g1) * 10)
    assert ir.moe_combine_ref(y, idx, logits, 2.0**-6, 1.0)[0, 0] == want


def _host_op_calls():
    ang = RNG.normal(size=(12, 16))
    idx = ir.moe_route_ref(RNG.integers(-500, 500, (40, 8)).astype(np.int32), 2)
    logits = RNG.integers(-500, 500, (40, 8)).astype(np.int32)
    return {
        "rms_norm": lambda: ir.rms_norm_ref(_i8(3, 40, 64), 0.0625, 2.0**-5, 1e-5),
        "rope": lambda: ir.rope_ref(_i8(2, 5, 12, 16), np.cos(ang).astype(np.float32),
                                    np.sin(ang).astype(np.float32), 0.0625, 0.044),
        "silu_mul": lambda: ir.silu_mul_ref(_i8(2, 37, 96), 0.0625, 0.0625),
        "moe_combine": lambda: ir.moe_combine_ref(_i8(80, 64), idx, logits, 2.0**-7, 1.0),
    }


@pytest.mark.parametrize("op", ["rms_norm", "rope", "silu_mul", "moe_combine"])
def test_host_ops_give_the_same_bits_in_any_row_blocks(op, monkeypatch):
    """The host ops run in row blocks (``ir.HOST_BLOCK_ELEMS``); blocks of a
    few rows, not dividing the rows evenly, give the bits of one block."""
    call = _host_op_calls()[op]
    state = RNG.bit_generator.state
    monkeypatch.setattr(ir, "HOST_BLOCK_ELEMS", 1 << 30)
    whole = call()
    RNG.bit_generator.state = state
    monkeypatch.setattr(ir, "HOST_BLOCK_ELEMS", 150)
    assert np.array_equal(call(), whole)


@pytest.mark.parametrize("sizes", [[3, 0, 5, 1], [0, 0, 9, 0], [2, 2, 2, 3]])
def test_grouped_dense_ref_multiplies_each_group_by_its_panel(sizes):
    x, w = _i8(9, 16), _i8(4, 16, 8)
    got = ir.grouped_dense_ref(x, w, np.array(sizes, np.int32))
    starts = np.cumsum([0] + sizes)
    for g in range(4):
        rows = slice(starts[g], starts[g + 1])
        assert np.array_equal(got[rows], x[rows].astype(np.int64) @ w[g].astype(np.int64))


# ---------------------------------------------------------------------------
# the importer and verify
# ---------------------------------------------------------------------------


def _moe_block(x, params):
    """A small routed-expert block in ``frontend.nn``'s spelling."""
    h = fnn.rms_norm(x, 0.0625, 2.0**-5, 1e-5)
    q = fnn.rope(h.reshape(2, 8, 8), params["cos"], params["sin"], 0.0625, 0.0625)
    logits = fnn.dense(q.reshape(16, 8), params["router"])
    idx = fnn.moe_route(logits, top_k=2)
    sizes = fnn.moe_group_sizes(idx, n_experts=4)
    rows = fnn.moe_dispatch(q.reshape(16, 8), idx)
    gu = fnn.requantize(fnn.grouped_dense(rows, params["w1"], sizes), 2.0**-6)
    y = fnn.requantize(fnn.grouped_dense(fnn.silu_mul(gu, 0.0625, 0.0625), params["w2"],
                                         sizes), 2.0**-6)
    return fnn.moe_combine(y, idx, logits, 2.0**-8, 1.0)


def _moe_params():
    ang = RNG.normal(size=(8, 8))
    return {"cos": np.cos(ang).astype(np.float32), "sin": np.sin(ang).astype(np.float32),
            "router": _i8(8, 4), "w1": _i8(4, 8, 16), "w2": _i8(4, 8, 8)}


def test_importer_maps_named_moe_calls_and_ragged_dot():
    for prim in ("ragged_dot_general", "jit"):
        assert prim in SUPPORTED_PRIMITIVES
    for name in ("rms_norm", "rope", "silu_mul", "moe_route", "moe_combine"):
        assert name in SUPPORTED_PRIMITIVES["jit"]
    params = _moe_params()
    g = trace_model(_moe_block, {"x": np.zeros((16, 8), np.int8)}, params)
    ops = [n.op for n in g.toposort()]
    for op in ir.MOE_HOST_OPS:
        assert op in ops, op
    assert ops.count("grouped_dense") == 2
    by_op = {n.op: n for n in g.toposort()}
    assert by_op["moe_route"].attrs == {"top_k": 2}
    assert by_op["moe_group_sizes"].attrs == {"n_experts": 4}
    # scalars keep their float64 value: the reference applies the same
    assert by_op["rms_norm"].attrs["eps"] == 1e-5
    assert by_op["moe_combine"].attrs == {"logit_scale": 2.0**-8, "scale": 1.0}
    assert verify_graph(g) == []


def test_importer_graph_routes_as_jax_does():
    """The integer steps (route, group sizes, dispatch, grouped GEMM) agree
    exactly with the jnp spelling run by JAX itself."""
    params = _moe_params()
    x = _i8(16, 8)
    logits = np.asarray(fnn.dense(x, params["router"]))
    idx = np.asarray(fnn.moe_route(logits, top_k=2))
    assert np.array_equal(idx, ir.moe_route_ref(logits, 2))
    sizes = np.asarray(fnn.moe_group_sizes(idx, n_experts=4))
    assert np.array_equal(sizes, np.bincount(idx.ravel(), minlength=4))
    rows = np.asarray(fnn.moe_dispatch(x, idx))
    assert np.array_equal(rows, ir.moe_dispatch_ref(x, idx))
    acc = np.asarray(fnn.grouped_dense(rows, params["w1"], sizes))
    assert np.array_equal(acc, ir.grouped_dense_ref(rows, params["w1"], sizes))


def _bad_shape(node: ir.Node, shape) -> ir.Graph:
    node.shape = tuple(shape)
    return ir.Graph([node])


VERIFY_FAULTS = {
    "rms_norm": lambda: _bad_shape(ir.rms_norm(ir.input_((4, 8), "int8", name="x"),
                                               in_scale=1.0, out_scale=1.0, eps=0.0), (4, 9)),
    "silu_mul": lambda: _bad_shape(ir.silu_mul(ir.input_((4, 8), "int8", name="x"),
                                               in_scale=1.0, out_scale=1.0), (4, 8)),
    "moe_route": lambda: _bad_shape(ir.moe_route(ir.input_((4, 8), "int32", name="x"), 2),
                                    (4, 3)),
    "moe_dispatch": lambda: _bad_shape(ir.moe_dispatch(
        ir.input_((4, 8), "int8", name="x"), ir.input_((4, 2), "int32", name="i")), (4, 8)),
    "grouped_dense": lambda: _bad_shape(ir.grouped_dense(
        ir.input_((6, 8), "int8", name="x"), ir.input_((3, 8, 4), "int8", name="w"),
        ir.input_((3,), "int32", name="g")), (6, 5)),
}


@pytest.mark.parametrize("make", VERIFY_FAULTS.values(), ids=list(VERIFY_FAULTS))
def test_verify_rederives_each_new_ops_shape(make):
    diags = verify_graph(make())
    assert any(d.code == "G_SHAPE" and "re-derived" in d.message for d in diags), diags


def test_verify_checks_fixed_dtypes_and_group_count():
    node = ir.moe_combine(ir.input_((4, 8), "int8", name="y"),
                          ir.input_((2, 2), "int32", name="i"),
                          ir.input_((2, 4), "int32", name="l"), logit_scale=1.0, scale=1.0)
    node.dtype = "int32"
    assert any(d.code == "G_DTYPE" for d in verify_graph(ir.Graph([node])))
    x, w = ir.input_((6, 8), "int8", name="x"), ir.input_((3, 8, 4), "int8", name="w")
    bad = ir.Node("grouped_dense", [x, w, ir.input_((2,), "int32", name="g")],
                  shape=(6, 4), dtype="int32")
    assert any("group sizes" in d.message for d in verify_graph(ir.Graph([bad])))


# ---------------------------------------------------------------------------
# the grouped kernel
# ---------------------------------------------------------------------------


def _cfg(block_m=8, block_n=8, scale=2.0**-6):
    return GemmKernelConfig(block_m=block_m, block_k=16, block_n=block_n,
                            acc_dtype="int32", out_dtype="int8", requant_scale=scale,
                            clip_lo=-128.0, clip_hi=127.0, interpret=True)


# rows per expert: uneven, one expert empty, every row on one expert, groups
# straddling row tiles of 8, and every expert exactly one tile
ROUTINGS = {
    "uneven": [5, 0, 17, 1, 9, 0, 3, 13],
    "one_expert": [0, 0, 0, 48, 0, 0, 0, 0],
    "straddle": [7, 9, 1, 15, 8, 2, 3, 3],
    "tile_aligned": [8, 8, 8, 8, 8, 8, 8, 8],
}


@pytest.mark.parametrize("sizes", ROUTINGS.values(), ids=list(ROUTINGS))
def test_grouped_kernel_matches_numpy(sizes):
    rows = sum(sizes)
    x, w = _i8(rows, 16), _i8(8, 16, 16)
    gs = np.array(sizes, np.int32)
    got = np.asarray(kgrouped.grouped_qmatmul(jnp.asarray(x), jnp.asarray(w),
                                              jnp.asarray(gs), cfg=_cfg()))
    acc = ir.grouped_dense_ref(x, w, gs)
    want = np.clip(np.round(acc.astype(np.float64) * 2.0**-6), -128, 127).astype(np.int8)
    assert got.dtype == np.int8 and np.array_equal(got, want)


def test_tile_layout_counts_padding_and_the_busiest_expert():
    assert kgrouped.tile_layout(np.array(ROUTINGS["uneven"]), 8) == (10, 32, 17)
    assert kgrouped.tile_layout(np.array(ROUTINGS["tile_aligned"]), 8) == (8, 0, 8)
    # the kernel's static tile count bounds every routing of these rows
    for sizes in ROUTINGS.values():
        used, _, _ = kgrouped.tile_layout(np.array(sizes), 8)
        assert used <= kgrouped.max_row_tiles(sum(sizes), 8, 8)


def test_grouped_row_tile_keeps_expected_padding_within_a_sixteenth():
    # Mixtral at 2 x 512 tokens: 2048 routed rows over 8 experts
    assert grouped_block_m(2048, 8, 32, 1024) == 32
    assert grouped_block_m(2048, 8, 8, 1024) == 32
    assert grouped_block_m(64, 8, 8, 128) == 8  # never below the alignment
    assert grouped_block_m(1 << 20, 8, 32, 256) == 256  # nor above the cap


# ---------------------------------------------------------------------------
# the small Mixtral stack against the benchmark's plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mixtral():
    sys.path.insert(0, str(ROOT))
    from bench.spec import load_module

    cfg = json.loads((ROOT / "bench/configs/mixtral_8x7b_int8.json").read_text())
    traffic = json.loads((ROOT / "bench/traffic/prefill512.json").read_text())
    model = load_module(ROOT / "bench/configs/mixtral_8x7b_int8.py", "mixtral_cfg")
    ref = load_module(ROOT / "bench/configs/mixtral_8x7b_int8_ref.py", "mixtral_ref")
    model.test_sizes(cfg, traffic)
    shape = model.sample_shape(cfg, traffic)
    params = model.make_params(cfg, jax.random.key(16), shape)
    # experts 2 and 5 get the same router column and different expert
    # weights: every token ties them, and a tie broken the other way changes
    # the answer
    for layer in range(cfg["num_hidden_layers"]):
        router = params[f"l{layer}.w_router"].copy()
        router[:, 5] = router[:, 2]
        params[f"l{layer}.w_router"] = router
    module = repro.compile(
        model.model_fn(cfg), repro.Target("tpu_v5e", mode="optimized"),
        example_inputs={"x": np.zeros(shape, np.int8)}, params=params,
        options=repro.CompileOptions(batch_buckets=(2,)),
    )
    return cfg, model, ref, params, shape, module


def _second_and_third_tie(cfg, params, x, ref) -> bool:
    """Whether some token's 2nd and 3rd router logits tie in layer 0."""
    a = cfg["assumed"]
    attn = ref._attention(cfg, {t: params[f"l0.w_{t}"] for t in ref.TAGS}, x,
                          *ref._tables(x.shape[0], a["head_dim"], cfg["rope_theta"]),
                          np.where(np.tri(x.shape[0]) > 0, 0, -1e9).astype(np.float32))
    h2 = ref._rms_norm(attn, a["act_scale"], a["norm_scale"], cfg["rms_norm_eps"])
    logits = np.sort(ref.imatmul(h2, params["l0.w_router"]), axis=-1)[:, ::-1]
    return bool(np.any((logits[:, 1] == logits[:, 2]) & (logits[:, 0] > logits[:, 1])))


def test_mixtral_stack_matches_the_reference_exactly(mixtral):
    cfg, model, ref, params, shape, module = mixtral
    x = model.make_inputs(cfg, np.random.default_rng(7), 4, shape)
    assert _second_and_third_tie(cfg, params, x[0], ref)
    before = trace.snapshot()
    out = module.run_many([{"x": s} for s in x[:2]])
    counted = trace.since(before)
    got = np.stack([o[0] for o in out])
    want = ref.reference(cfg, params, x[:2])
    assert np.array_equal(got, want)
    assert not np.array_equal(ref.reference(cfg, params, x[:2], weight_bits=4), want)
    # 2 layers x 2 grouped GEMMs x (2 x 16 tokens x top-2) routed rows
    assert counted["expert_rows"] == 2 * 2 * 64
    assert counted["expert_rows_peak"] >= counted["expert_rows"]
    # per layer: q, k, v, o, the router, 2 x 2 scores and 2 x 2 context
    # instances, two grouped launches
    assert counted["kernel_launches"] == 2 * (5 + 4 + 4 + 2)
    # SiLU-mul on the device over each layer's routed rows, none on the host
    assert counted["swiglu_device_rows"] == 2 * 64
    assert counted["swiglu_host_rows"] == 0


def test_expert_weights_stay_on_the_device_after_the_first_call(mixtral):
    cfg, model, _, params, shape, module = mixtral
    x = model.make_inputs(cfg, np.random.default_rng(8), 2, shape)
    feeds = [{"x": s} for s in x]
    module.run_many(feeds)
    before = trace.snapshot()
    module.run_many(feeds)
    up = trace.since(before)["h2d_bytes"]
    experts = sum(params[f"l{i}.w_{t}"].nbytes for i in range(2) for t in ("gate_up", "down"))
    projections = sum(params[f"l{i}.w_{t}"].nbytes for i in range(2)
                      for t in ("q", "k", "v", "o", "router"))
    assert projections < up < experts


def test_mixtral_plan_has_one_grouped_step_per_expert_projection(mixtral):
    _, _, _, _, _, module = mixtral
    plan = module.modules[2].plan
    ops = [s.op for s in plan.steps]
    # gate|up and SiLU-mul in one swiglu step, down a grouped step
    assert ops.count("swiglu") == 2
    assert ops.count("generalized_grouped_dense") == 2
    assert "silu_mul" not in ops
    # q, k, v and o take the quantized chain without a bias
    assert ops.count("generalized_dense") == 2 * 4
    assert ops.count("attn_scores") == 2
    for op in ("rms_norm", "rope", "moe_route", "moe_combine"):
        assert op in ops
    # arena def/use and the two-lane race detector over the fused plan
    assert verify_plan(plan) == []
    cycles = module.modules[2].modeled_cycles()
    assert np.isfinite(cycles["total"]) and cycles["accel"] > 0 and cycles["host"] > 0


# ---------------------------------------------------------------------------
# SiLU-mul on the device, exact through the table
# ---------------------------------------------------------------------------

MIXTRAL_SCALES = (0.0625, 0.0625)


def _all_pairs(scales):
    """Every int8 (gate, up) pair once, in a random order, as rows of
    ``[gate | up]``."""
    pairs = RNG.permutation(65536)
    gate = (pairs // 256 - 128).astype(np.int8).reshape(128, 512)
    up = (pairs % 256 - 128).astype(np.int8).reshape(128, 512)
    return np.concatenate([gate, up], axis=1), scales


def _codes(rows):
    """The pair code ``(gate + 128) * 256 + (up + 128)`` of each element."""
    f = rows.shape[1] // 2
    return (rows[:, :f].astype(np.int32) + 128) * 256 + rows[:, f:].astype(np.int32) + 128


SWIGLU_CASES = {
    "all_pairs_mixtral": lambda: _all_pairs(MIXTRAL_SCALES),
    "all_pairs_odd_scales": lambda: _all_pairs((0.0473, 0.0391)),
    "random_mixtral_width": lambda: (_i8(24, 2 * 14336), MIXTRAL_SCALES),
}


@pytest.mark.parametrize("case", SWIGLU_CASES.values(), ids=list(SWIGLU_CASES))
def test_swiglu_table_and_device_form_give_the_bits_of_silu_mul_ref(case):
    rows, (s_in, s_out) = case()
    want = ir.silu_mul_ref(rows, s_in, s_out)
    code = _codes(rows)
    assert np.array_equal(lowering.swiglu_table(s_in, s_out)[code], want)
    got = lowering._swiglu_device(jnp.asarray(rows), in_scale=s_in, out_scale=s_out,
                                  patch=lowering.swiglu_patch(s_in, s_out))
    assert got.dtype == jnp.int8 and np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("error, scales", [(0.0, MIXTRAL_SCALES), (2.0**-9, (0.0611, 0.0589))],
                         ids=["this_device", "sloppy_device"])
def test_swiglu_patch_holds_every_pair_the_float32_form_misses(error, scales, monkeypatch):
    """The plan's check over all pairs patches each pair the device rounds
    otherwise, here also for a device whose float32 value errs by 2^-9
    (scales no other test traces, so the jitted forms trace it anew)."""
    exact = lowering._swiglu_q
    monkeypatch.setattr(lowering, "_swiglu_q",
                        lambda rows, s_in, s_out: exact(rows, s_in, s_out) * (1 + error))
    rows, (s_in, s_out) = _all_pairs(scales)
    want = ir.silu_mul_ref(rows, s_in, s_out)
    patch = lowering.swiglu_patch(s_in, s_out)
    device = functools.partial(lowering._swiglu_device, jnp.asarray(rows), in_scale=s_in,
                               out_scale=s_out)
    code = _codes(rows)
    missed = set(code[np.asarray(device(patch=())) != want].tolist())
    assert missed <= {c for c, _ in patch}
    assert np.array_equal(np.asarray(device(patch=patch)), want)
    if error:
        assert missed
    else:  # a short patch: the pairs within SWIGLU_TOL of a half
        assert 0 < len(patch) <= 64


W_UP, W_DOWN = _i8(4, 16, 32), _i8(4, 16, 8)


def _swiglu_graph(consumer: str) -> ir.Graph:
    """Grouped gate|up -> requantize -> silu_mul -> ``consumer``: the down
    grouped GEMM (requantized), or an int8 add of the silu_mul to itself."""
    x = ir.input_((24, 16), "int8", name="x")
    sizes = ir.input_((4,), "int32", name="sizes")
    gu = ir.requantize(ir.grouped_dense(x, ir.const(W_UP, name="w_up"), sizes), 2.0**-6)
    act = ir.silu_mul(gu, in_scale=0.0625, out_scale=0.0625)
    if consumer == "down":
        out = ir.requantize(ir.grouped_dense(act, ir.const(W_DOWN, name="w_down"), sizes),
                            2.0**-6)
    else:
        out = ir.add(act, act)
    return ir.Graph([out], name=f"swiglu_{consumer}")


SWIGLU_FEEDS = {"x": _i8(24, 16), "sizes": np.array([5, 0, 12, 7], np.int32)}


@pytest.mark.parametrize("consumer, on_device", [("down", True), ("add", False)])
def test_swiglu_step_keeps_its_result_only_for_a_grouped_step(consumer, on_device):
    module = repro.compile(_swiglu_graph(consumer), repro.Target("tpu_v5e", cache=False))
    plan = module.plan
    (step,) = [s for s in plan.steps if s.op == "swiglu"]
    assert "silu_mul" not in [s.op for s in plan.steps]
    arena = plan.new_arena()
    before = trace.snapshot()
    got = plan.execute(SWIGLU_FEEDS, arena)
    counted = trace.since(before)
    assert isinstance(arena[step.slot], jax.Array) is on_device
    assert isinstance(arena[step.slot], np.ndarray) is not on_device
    want = ir.execute_graph(_swiglu_graph(consumer), SWIGLU_FEEDS)
    assert np.array_equal(np.asarray(got[0]), want[0])
    assert (counted["swiglu_device_rows"], counted["swiglu_host_rows"]) == (24, 0)


@pytest.mark.parametrize("target", ["gemmini", "edge_npu"])
def test_emulated_target_keeps_silu_mul_on_the_host(target):
    module = repro.compile(_swiglu_graph("down"), repro.Target(target, cache=False))
    ops = [s.op for s in module.plan.steps]
    assert "swiglu" not in ops and "silu_mul" in ops
    before = trace.snapshot()
    got = module.run(SWIGLU_FEEDS)
    counted = trace.since(before)
    assert np.array_equal(got[0], ir.execute_graph(_swiglu_graph("down"), SWIGLU_FEEDS)[0])
    assert (counted["swiglu_device_rows"], counted["swiglu_host_rows"]) == (0, 24)


def _bench_plan(name: str):
    """The plan of a benchmark cell's bucket module at its test sizes."""
    from bench import run, spec

    cell = spec.load_cell(name)
    cell.model.test_sizes(cell.config, cell.traffic)
    shape = cell.model.sample_shape(cell.config, cell.traffic)
    params = cell.model.make_params(cell.config, run.jax_key(3), shape)
    buckets = tuple(cell.traffic["buckets"])
    module = repro.compile(
        cell.model.model_fn(cell.config), repro.Target("tpu_v5e", mode="optimized"),
        example_inputs={"x": np.zeros(shape, np.int8)}, params=params,
        options=repro.CompileOptions(batch_buckets=buckets),
    )
    return module.bucket_module(buckets[-1])


@pytest.mark.parametrize("name", ["musicgen.prefill512", "toycar.stream"])
def test_plans_without_experts_are_unchanged_by_the_swiglu_matcher(name, monkeypatch):
    bucket = _bench_plan(name)
    stages = bucket.plan.stage_assignment()
    assert "swiglu" not in [s["op"] for s in stages]
    monkeypatch.setattr(executor, "_swiglu_epilogues", lambda *a: {})
    assert executor.build_plan(bucket.graph, bucket.ops).stage_assignment() == stages
