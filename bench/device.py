"""The chip under the run: refusal without a TPU, its peaks, its memory.

There is no CPU fallback and no interpret mode: a run that finds no TPU,
fewer chips than the cell asks for, or Pallas kernels that would run in
interpret mode stops before it prints a result.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    pass


def require_tpu(chips: int):
    """The devices of the run; raises ``NoChip`` on anything but a TPU with
    at least ``chips`` devices on which Pallas compiles through Mosaic."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU: JAX could not start a backend ({e})") from e
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(
            f"no TPU: JAX found platform {dev.platform!r} ({dev.device_kind}, "
            f"{len(devices)} device(s))"
        )
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips; JAX found {len(devices)}")
    from repro.core.lowering import pallas_interpret_mode

    if pallas_interpret_mode():
        raise NoChip(f"Pallas would run in interpret mode on {jax.default_backend()!r}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise NoChip(f"no peaks for device kind {device_kind!r} in {PEAKS_FILE.name}")
    return table[device_kind]


def describe(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks_seen = [p for p in peaks_seen if p is not None]
    return max(peaks_seen) if peaks_seen else None


def memory_in_use_bytes(devices) -> int | None:
    """Bytes in use now on the fullest chip, where the backend reports it."""
    seen = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    seen = [b for b in seen if b is not None]
    return max(seen) if seen else None
