"""The device a number came from, its published peaks, its memory peak."""

from __future__ import annotations

import json
import os

PEAKS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class DeviceError(RuntimeError):
    """JAX did not find the device the cell asks for."""


def stamp() -> dict:
    """What every result line carries, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require(platform: str, chips: int) -> dict:
    """Exactly ``chips`` devices of ``platform``, or no run: an unattached
    machine silently gives ``CpuDevice``, and a cell never falls back."""
    found = stamp()
    if found["platform"] != platform or found["count"] != chips:
        raise DeviceError(
            f"the cell needs {chips} {platform} device(s), JAX found {found}")
    return found


def peaks(kind: str) -> dict:
    with open(PEAKS_PATH) as f:
        table = json.load(f)
    if kind.startswith("_") or kind not in table:
        known = sorted(k for k in table if not k.startswith("_"))
        raise DeviceError(
            f"no published peaks for device_kind {kind!r} in {PEAKS_PATH} "
            f"(known: {known}); add a sourced row")
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes of the fullest local device: the peak of live buffers
    (``peak_bytes_in_use``) plus the peak of what loaded programs reserve
    for their temporaries (``peak_bytes_reserved``). This TPU runtime keeps
    the two apart (my chip probe, PR 22: a program with 4.295 GB of temps
    over a 1 GiB argument left peak_bytes_in_use at 1.075 GB and
    peak_bytes_reserved at 4.295 GB), and a training step needs both at
    once. 0 where the backend reports nothing (the CPU backend)."""
    import jax

    def peak(dev):
        stats = dev.memory_stats() or {}
        return (int(stats.get("peak_bytes_in_use", 0))
                + int(stats.get("peak_bytes_reserved", 0)))

    return max(peak(d) for d in jax.local_devices())
