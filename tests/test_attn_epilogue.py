"""The attention-score epilogue on the device (``lowering._attn_scores_step``):
a Pallas scores GEMM followed by ``dequantize -> [add(const mask)] ->
softmax(last axis) -> quantize(int8)`` plans as one ``attn_scores`` step.
Its int8 result equals the host chain's bit for bit: rows the rounding
guard flags are recomputed by the host chain, and the guard catches a
device error far above float32's.  Every other graph keeps its host steps.
Runs on the CPU with the ``tpu_v5e`` target (Pallas in interpret mode)."""

from __future__ import annotations

import dataclasses
import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import ir, lowering, trace, zoo
from repro.core.artifact import load_module, save_module
from repro.core.executor import build_plan

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import program, run, spec, tracing  # noqa: E402

TIE_SCALE = 2.0**-13
PROBS_SCALE = 1.0 / 128.0


def _target(name: str = "tpu_v5e", **kw) -> repro.Target:
    return repro.Target(name, cache=False, **kw)


def _unfused_plan(module):
    """The plan the module's graph gets with no fused step: the same
    executors, wrapped so they offer no ``fuse_attn_epilogue``."""
    ops = {
        n: dataclasses.replace(op, executor=functools.partial(op.executor))
        for n, op in module.ops.items()
    }
    return build_plan(module.graph, ops)


def _run_plan(plan, feeds):
    return plan.execute(feeds, plan.new_arena())


def _assert_same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def _ops(plan) -> list[str]:
    return [s.op for s in plan.steps]


# -- hand-built scores graphs ------------------------------------------------


def _scores_graph(b, s, k=2, n=256, *, scale=TIE_SCALE, mask=None,
                  mask_input=False, axis=-1, quantize=True, mask_first=False):
    """``quantize(softmax(dequantize(q @ kt) [+ mask]))`` over scores
    ``[b, s, n]``."""
    q = ir.input_((b, s, k), "int8", name="q")
    kt = ir.input_((b, k, n), "int8", name="kt")
    x = ir.dequantize(ir.dense(q, kt), scale=scale)
    if mask is not None:
        if mask_input:
            m = ir.input_(mask.shape, "float32", name="mask")
        else:
            m = ir.const(mask, name="mask")
        x = ir.add(m, x) if mask_first else ir.add(x, m)
    p = ir.softmax(x, axis=axis)
    out = ir.quantize(p, scale=PROBS_SCALE) if quantize else p
    return ir.Graph([out], name="scores")


def _tie_feeds(b: int, s: int, tie_rows: int, near: bool, seed: int = 0):
    """Scores ``q_i . k_j`` over ``k = 2`` and rows of 256 keys.  Rows are
    random (spread up to 1.5 after the scale) except: instance 0's first
    ``tie_rows`` are uniform, every ``q`` exactly 0.5; with ``near``,
    instance 1's rows are ``[1, 0, ...]`` (255 ``q`` under 0.5 by 2.4e-7)
    and ``[-1, 0, ...]`` (over 0.5 by as much)."""
    rng = np.random.default_rng(seed)
    q = np.zeros((b, s, 2), np.int8)
    kt = np.zeros((b, 2, 256), np.int8)
    q[:, :, 0] = rng.integers(64, 128, (b, s))
    kt[:, 0, :] = rng.integers(-127, 128, (b, 256))
    q[0, :tie_rows, 0] = 0
    if near:
        q[1, :, 0] = 0
        q[1, : s // 2, 1] = 1
        q[1, s // 2 :, 1] = -1
        kt[1, 1, 0] = 1
    return {"q": q, "kt": kt}


def _compile(graph, target=None):
    return repro.compile(graph, target or _target())


# -- bit-exact against the unfused plan ----------------------------------------


def _tiny_musicgen(seed: int):
    cell = spec.load_cell("musicgen.prefill512")
    cfg, traffic, model = cell.config, cell.traffic, cell.model
    cfg.update(hidden_size=64, num_attention_heads=4, ffn_dim=128, num_hidden_layers=2)
    traffic.update(seq_len=16, samples_per_call=2, buckets=[2])
    shape = model.sample_shape(cfg, traffic)
    params = model.make_params(cfg, run.jax_key(seed), shape)
    module = repro.compile(
        model.model_fn(cfg), _target(mode="optimized"),
        example_inputs={"x": np.zeros(shape, np.int8)}, params=params,
        options=repro.CompileOptions(batch_buckets=(2,)),
    )
    x = model.make_inputs(cfg, np.random.default_rng(seed), 2, shape)
    return cell, cfg, params, module.bucket_module(2), x


@pytest.mark.parametrize("seed", [0, 1, 2**33 + 5])
def test_musicgen_fused_plan_matches_unfused_and_reference(seed):
    cell, cfg, params, module, x = _tiny_musicgen(seed)
    assert _ops(module.plan).count("attn_scores") == cfg["num_hidden_layers"]
    before = trace.snapshot()
    got = module.run({"x": x})
    moved = trace.since(before)
    _assert_same(got, _run_plan(_unfused_plan(module), {"x": x}))
    np.testing.assert_array_equal(got[0], cell.ref.reference(cfg, params, x))
    rows = 2 * cfg["num_attention_heads"] * 16
    assert moved["attn_epilogue_rows"] == cfg["num_hidden_layers"] * rows


def test_musicgen_plan_has_four_fewer_steps_per_layer():
    _, cfg, _, module, _ = _tiny_musicgen(0)
    fused, plain = _ops(module.plan), _ops(_unfused_plan(module))
    assert len(plain) - len(fused) == 4 * cfg["num_hidden_layers"]
    for op in ("dequantize", "add", "softmax", "quantize"):
        assert plain.count(op) == cfg["num_hidden_layers"] and op not in fused
    assert plain.count("dense") == fused.count("dense") + fused.count("attn_scores")


@pytest.mark.parametrize("batch", [None, 3])
@pytest.mark.parametrize("seed", [0, 4])
def test_transformer_block_fused_plan_matches_unfused(batch, seed):
    model = zoo.get_model("transformer_block")
    module = _compile(model.build(batch=batch))
    assert _ops(module.plan).count("attn_scores") == 1
    rng = np.random.default_rng(seed)
    shape = model.input_shape if batch is None else model.batched_input_shape(batch)
    for _ in range(2):
        feeds = {"x": rng.integers(-128, 128, shape).astype(np.int8)}
        want = _run_plan(_unfused_plan(module), feeds)
        _assert_same(module.run(feeds), want)
        _assert_same(module.run(feeds, pipelined=True), want)
        _assert_same(want, ir.execute_graph(model.build(batch=batch), feeds))


# -- the rounding guard ----------------------------------------------------------


@pytest.mark.parametrize("near", [False, True], ids=["gathered", "whole-scores"])
def test_near_ties_fall_back_to_the_host_chain_exactly(near):
    """A few tie rows come back through the compact gather of flagged
    rows (capacity: a sixteenth of the rows); with instance 1's near ties
    the step flags more and syncs its whole scores instead."""
    b, s, n, ties = 4, 128, 256, 3
    module = _compile(_scores_graph(b, s))
    assert _ops(module.plan) == ["attn_scores"]
    feeds = _tie_feeds(b, s, ties, near)
    before = trace.snapshot()
    got = module.run(feeds)
    moved = trace.since(before)
    want = ir.execute_graph(_scores_graph(b, s), feeds)
    _assert_same(got, want)
    rows, capacity = b * s, b * s // 16
    flagged = moved["attn_fallback_rows"]
    assert moved["attn_epilogue_rows"] == rows
    assert ties + (s if near else 0) <= flagged < rows
    assert (flagged > capacity) is near
    # the result, the flags, then the gathered rows or the whole scores
    synced = rows * n + rows + 4 * n * (rows if near else capacity)
    assert moved["d2h_bytes"] == synced and moved["d2h_syncs"] == 3
    # the host chain rounds the ties half to even and the near ties apart
    assert (want[0][0, :ties] == 0).all()
    if near:
        assert (want[0][1, : s // 2, 1:] == 0).all() and (want[0][1, s // 2 :, 1:] == 1).all()


def _erring_epilogue(flag: bool):
    """The device epilogue with every ``q`` too large by 2^-20 relative,
    its rounding guard on or forced off."""

    @functools.partial(jax.jit, static_argnames=("shape", "scale", "probs_scale", "capacity"))
    def epilogue(s, mask, *, shape, scale, probs_scale, capacity):
        s = s.reshape(shape)
        q = lowering._attn_q(s, mask, scale, probs_scale) * jnp.float32(1 + 2.0**-20)
        out, flags, rows = lowering._attn_out(q, s, capacity)
        return out, flags if flag else jnp.zeros_like(flags), rows

    return epilogue


@pytest.mark.parametrize("flag", [True, False], ids=["guarded", "unguarded"])
def test_guard_catches_a_device_error(monkeypatch, flag):
    b, s = 3, 8
    module = _compile(_scores_graph(b, s))
    feeds = _tie_feeds(b, s, 2, near=True)
    want = ir.execute_graph(_scores_graph(b, s), feeds)
    monkeypatch.setattr(lowering, "_attn_epilogue", _erring_epilogue(flag))
    got = module.run(feeds)[0]
    if flag:
        np.testing.assert_array_equal(got, want[0])
    else:
        # the rows just under 0.5 round up: the test data reaches the error
        assert not np.array_equal(got, want[0])
        assert (got[1, : s // 2, 1:] == 1).all()


def test_guard_bound_covers_the_rows_it_admits():
    """``ATTN_TOL``'s argument: a row of ``ATTN_MAX_ROW`` keeps its
    worst-case error ``(n + 408)u`` under the tolerance; a longer row
    keeps its host steps."""
    u = 2.0**-24
    assert (lowering.ATTN_MAX_ROW + 408) * u * (1 + 2.0**-10) < lowering.ATTN_TOL
    for n, fused in ((lowering.ATTN_MAX_ROW, True), (lowering.ATTN_MAX_ROW + 1, False)):
        module = _compile(_scores_graph(1, 2, n=n))
        assert ("attn_scores" in _ops(module.plan)) is fused


# -- what keeps its host steps -------------------------------------------------------


def _mask(s: int) -> np.ndarray:
    i, j = np.arange(s)[:, None], np.arange(256)[None, :]
    return np.where(j <= i, 0.0, -1e9).astype(np.float32)


NEGATIVE = {
    "float_softmax": lambda: _scores_graph(2, 8, quantize=False),
    "computed_mask": lambda: _scores_graph(2, 8, mask=_mask(8), mask_input=True),
    "first_axis": lambda: _scores_graph(2, 8, axis=0),
    "scale_not_power_of_two": lambda: _scores_graph(2, 8, scale=0.001),
}


@pytest.mark.parametrize("case", sorted(NEGATIVE))
def test_other_graphs_keep_their_host_steps(case):
    module = _compile(NEGATIVE[case]())
    assert _ops(module.plan) == _ops(_unfused_plan(module))
    assert "attn_scores" not in _ops(module.plan) and "softmax" in _ops(module.plan)


@pytest.mark.parametrize("acc", ["gemmini", "edge_npu"])
def test_emulated_targets_keep_their_host_steps(acc):
    model = zoo.get_model("transformer_block")
    assert acc in model.accelerators
    module = _compile(model.build(), _target(acc))
    assert _ops(module.plan) == _ops(_unfused_plan(module))
    assert "softmax" in _ops(module.plan)


@pytest.mark.parametrize("mask_first", [False, True])
def test_const_mask_in_either_order(mask_first):
    b, s = 2, 8
    graph = functools.partial(_scores_graph, b, s, mask=_mask(s), mask_first=mask_first)
    module = _compile(graph())
    assert _ops(module.plan) == ["attn_scores"]
    feeds = _tie_feeds(b, s, 2, near=True, seed=5)
    before = trace.snapshot()
    _assert_same(module.run(feeds), ir.execute_graph(graph(), feeds))
    _assert_same(module.run(feeds), ir.execute_graph(graph(), feeds))
    # q and kt go up on each call, the mask once, with the first
    per_call = feeds["q"].nbytes + feeds["kt"].nbytes
    assert trace.since(before)["h2d_bytes"] == 2 * per_call + _mask(s).nbytes


# -- boot, shards, spans -------------------------------------------------------------


def test_artifact_boot_answers_like_compile(tmp_path):
    model = zoo.get_model("transformer_block")
    module = _compile(model.build(batch=2))
    save_module(module, tmp_path / "block")
    booted = load_module(tmp_path / "block")
    assert _ops(booted.plan) == _ops(module.plan)
    assert "attn_scores" in _ops(booted.plan)
    rng = np.random.default_rng(7)
    feeds = {"x": rng.integers(-128, 128, model.batched_input_shape(2)).astype(np.int8)}
    _assert_same(booted.run(feeds), module.run(feeds))


def test_sharded_plans_engage_on_each_shard():
    model = zoo.get_model("transformer_block")
    feeds = model.feeds(seed=2)
    single = repro.compile("transformer_block", _target())
    sharded = repro.compile("transformer_block", _target(devices=2))
    for shard in sharded.shards.values():
        assert "attn_scores" in _ops(shard.plan)
    _assert_same(sharded.run(feeds), single.run(feeds))


def test_counters_agree_with_spans(tmp_path):
    b, s = 3, 40
    module = _compile(_scores_graph(b, s))
    feeds = [_tie_feeds(b, s, 3, near=False, seed=i) for i in range(3)]
    module.run(feeds[0])  # traces the kernels and the epilogue
    before = trace.snapshot()
    with jax.profiler.trace(str(tmp_path)):
        module.run_many(feeds)
    moved = trace.since(before)
    p = program.reduce_program(tracing.newest_xplane(tmp_path))
    spans = p.program_spans
    assert spans["repro.accel.attn_scores"][0] == 3
    assert moved["attn_epilogue_rows"] == 3 * b * s
    # every call flags rows; the fallback runs inside the fused step
    assert spans["repro.host.softmax_fallback"][0] == 3
    assert spans["repro.host.softmax_fallback"][1] <= spans["repro.accel.attn_scores"][1]
    assert moved["attn_fallback_rows"] >= 3 * 3  # the tie rows at least
    assert spans["repro.d2h"][0] == moved["d2h_syncs"]
    assert p.bytes["repro.d2h"] == moved["d2h_bytes"]
    assert p.bytes["repro.h2d"] == moved["h2d_bytes"]
    assert "repro.host.softmax" not in spans
