"""The reader of ``requant_device_share``, on the CPU: the share of the
batched int8 dense rows whose requantize the GEMM kernel's epilogue did on
the device, from the program's counters over the window, and no reading
from a program that lacks those counters."""

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
    ({"h2d_bytes": 1}, None),  # a program without the requantize counters
    ({"bmm_requant_device_rows": 0, "bmm_requant_host_rows": 0}, None),  # no batched dense
    ({"bmm_requant_device_rows": 32768, "bmm_requant_host_rows": 0}, 100.0),
    ({"bmm_requant_device_rows": 1, "bmm_requant_host_rows": 3}, 25.0),
])
def test_requant_device_share_reads_the_window_counters(counters, share):
    run = SimpleNamespace(counters=counters)
    assert spec.metric_reader("requant_device_share")(run) == share
