"""The reader of ``swiglu_device_share``, on the CPU: the share of the
SiLU·mul rows the fused ``swiglu`` step computed on the device, from the
program's counters over the window, and no reading from a program that
lacks those counters."""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402


@pytest.mark.parametrize("counters, share", [
    (None, None),  # a run that took no counters
    ({"h2d_bytes": 1}, None),  # a program without the swiglu counters
    ({"swiglu_device_rows": 0, "swiglu_host_rows": 0}, None),  # no SiLU·mul
    ({"swiglu_device_rows": 16384, "swiglu_host_rows": 0}, 100.0),
    ({"swiglu_device_rows": 3, "swiglu_host_rows": 1}, 75.0),
])
def test_swiglu_device_share_reads_the_window_counters(counters, share):
    run = SimpleNamespace(counters=counters)
    assert spec.metric_reader("swiglu_device_share")(run) == share
