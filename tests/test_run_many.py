"""``run_many`` over a stream of feeds: front-door-compiled zoo modules
against the hand-built golden graph, step failures that leave the module
usable, and the lane each step is named by."""

import dataclasses

import numpy as np
import pytest

import repro
from repro.core import ir
from repro.core.executor import step_span
from repro.core.zoo import ZOO, get_model

MATRIX = [
    (name, accel, mode)
    for name in sorted(ZOO)
    for accel in get_model(name).accelerators
    if accel in ("gemmini", "edge_npu")
    for mode in ("optimized", "naive")  # fused and host-op-heavy plans
]


def _compile(name, accel, mode="optimized"):
    return repro.compile(name, repro.Target(accel, mode=mode, cache=False))


def _assert_matches_reference(model, traffic, got):
    assert len(got) == len(traffic)
    for feeds, outs in zip(traffic, got):
        ref = ir.execute_graph(model.build(), feeds)
        assert len(outs) == len(ref)
        for x, r in zip(outs, ref):
            x = np.asarray(x)
            assert x.dtype == r.dtype
            np.testing.assert_array_equal(x, r)


@pytest.mark.parametrize("name,accel,mode", MATRIX)
def test_run_many_over_a_stream_matches_the_reference(name, accel, mode):
    module = _compile(name, accel, mode)
    model = get_model(name)
    traffic = [model.feeds(seed=s) for s in range(5)]
    _assert_matches_reference(model, traffic, module.run_many(traffic))


def _poison_step(module, lane: str, trip_on: int):
    """Swap the module's plan for one whose first ``lane`` step raises on
    its ``trip_on``-th call (and only then)."""
    plan = module.finalize()
    k = next(i for i, s in enumerate(plan.steps) if s.lane == lane)
    orig = plan.steps[k].fn
    calls = [0]

    def poisoned(*args):
        calls[0] += 1
        if calls[0] == trip_on:
            raise RuntimeError(f"injected {lane} failure")
        return orig(*args)

    steps = list(plan.steps)
    steps[k] = dataclasses.replace(steps[k], fn=poisoned)
    module.plan = dataclasses.replace(plan, steps=tuple(steps))


@pytest.mark.parametrize("lane", ["accel", "host"])
def test_step_failure_propagates_and_the_module_recovers(lane):
    # baseline mode keeps host epilogues beside the offloaded GEMMs
    module = _compile("qcnn", "gemmini", "baseline")
    model = get_model("qcnn")
    traffic = [model.feeds(seed=s) for s in range(6)]
    _poison_step(module, lane, trip_on=3)
    module.run_many(traffic[:1])
    (arena,) = module._arena_pool
    with pytest.raises(RuntimeError, match=f"injected {lane} failure"):
        module.run_many(traffic[1:])
    # the arena of the aborted stream went back to the pool
    assert len(module._arena_pool) == 1 and module._arena_pool[0] is arena
    # the half-written arena does not leak into the next stream
    _assert_matches_reference(model, traffic, module.run_many(traffic))


def test_step_lane_matches_offload_decisions():
    module = _compile("qcnn", "gemmini", "baseline")
    plan = module.finalize()
    offloaded = {n.name for n in module.ops}
    lanes = [s.lane for s in plan.steps]
    assert "host" in lanes and lanes.count("accel") == len(module.ops)
    for s in plan.steps:
        assert s.lane == ("accel" if s.name in offloaded else "host")
        assert step_span(s) == f"repro.{s.lane}.{s.op}"


def test_run_many_of_no_traffic_is_empty():
    assert _compile("mlp_tiny", "gemmini").run_many([]) == []
