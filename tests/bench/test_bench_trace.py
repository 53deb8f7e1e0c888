"""The reduction from a profiler trace to device numbers: the union of
device op intervals, the GEMM kernels' and every Mosaic kernel's device
time, and the host activity
each idle gap falls in — on synthetic intervals and on a small trace
recorded on a TPU v5e (``bench/testdata/``)."""

from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import tracing  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "toycar_clip.xplane.pb"


def test_union_merges_overlapping_intervals():
    iv = np.asarray([[0, 2], [1, 3], [3, 4], [6, 7], [6.5, 6.8]], dtype=float)
    assert tracing._union(iv).tolist() == [[0, 4], [6, 7]]


def test_gap_goes_to_the_innermost_live_host_span():
    outer = (0.0, 100.0, "bench.run_many")
    inner = (10.0, 20.0, "PjitFunction(qmatmul)")
    other_thread = [(30.0, 60.0, "TransferToDevice")]
    names = tracing._innermost_at([[outer, inner], other_thread], np.asarray([5.0, 15.0, 25.0, 45.0, 150.0]))
    assert names == [
        "bench.run_many", "PjitFunction(qmatmul)", "bench.run_many",
        "TransferToDevice", "no host span",
    ]


QGEMM = (
    '%qmatmul.1 = s8[16,128]{1,0:T(8,128)(4,1)} custom-call(s8[16,128]{1,0:T(8,128)(4,1)} '
    '%x_q.1, s8[128,128]{1,0:T(8,128)(4,1)} %w_q.1, s32[1,128]{1,0:T(1,128)S(1)} %bitcast.1), '
    'custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={}}'
)


def test_gemm_kernel_class_and_op_key():
    assert tracing.is_gemm_kernel(QGEMM)
    assert tracing.is_gemm_kernel(QGEMM.replace("%qmatmul.1", "%matmul.1").replace("s8[16", "s32[16"))
    pad = "%pad.0 = s8[64,128]{1,0:T(8,128)(4,1)S(1)} pad(s8[64,64]{1,0} %x.1, s8[] %constant)"
    assert not tracing.is_gemm_kernel(pad)
    assert not tracing.is_gemm_kernel(QGEMM.replace("%qmatmul.1", "%softmax.1"))
    assert tracing.op_key(QGEMM) == "%qmatmul.1 = s8[16,128]"
    assert tracing.kernel_name(QGEMM) == "%qmatmul"
    assert tracing.kernel_name(QGEMM.replace("%qmatmul.1", "%attn_scores.12")) == "%attn_scores"
    assert tracing.kernel_name(pad) is None


def test_recorded_trace_reduces_to_pinned_numbers():
    """12 calls of toycar.clip (309 windows each, bucket 320) on a TPU v5e."""
    s = tracing.reduce_trace(RECORDED)
    assert s.window_s == pytest.approx(0.487518686)
    assert s.busy_s == pytest.approx(0.000125924)
    assert s.gemm_s == pytest.approx(8.0667e-05)
    assert s.n_device_events == 432
    # the GEMM kernels: seven layers with 128 outputs (the 8-wide one padded
    # to 128) and the last with 640, 12 calls each
    assert [op for op, _ in s.device_ops[:3:2]] == [
        "%qmatmul.1 = s8[320,128]", "%qmatmul.1 = s8[320,640]"
    ]
    assert s.device_ops[0][1] + s.device_ops[2][1] == pytest.approx(s.gemm_s)
    # the one Mosaic kernel of the trace is the GEMM; gemm_s is its sum
    assert s.kernel_s == {"%qmatmul": pytest.approx(8.0667e-05)}
    assert sum(s.kernel_s.get(k, 0.0) for k in tracing.GEMM_KERNELS) == s.gemm_s
    assert len(s.device_ops) == len(s.idle_gaps) == tracing.TOP
    assert s.idle_gaps[0][0] == "no host span"
    # device time is a sum over events, so the top ops cannot exceed busy time
    # by more than overlap allows on one chip
    assert sum(t for _, t in s.device_ops) <= s.busy_s * 1.001
    idle = 100 * (1 - s.busy_s / s.window_s)
    assert idle == pytest.approx(99.97417, abs=1e-4)
