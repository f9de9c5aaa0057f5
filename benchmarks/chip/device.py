"""What the run ran on: device facts, the fullest chip's peak memory,
and the table of published peaks keyed by ``device_kind``."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_facts(devices) -> dict:
    """``platform``, ``kind`` and ``count`` as JAX reports them."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_chips(devices, chips: int) -> list:
    """The first ``chips`` TPU devices; raises :class:`NoAccelerator`
    when JAX has none or too few (the benchmark never runs on the CPU)."""
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips, JAX found {len(devices)}")
    return list(devices[:chips])


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices`` (None where the
    backend keeps no statistics)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``. A kind not in the table
    is an error, never a default."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {path.name}; known: {sorted(table)}")
    return table[device_kind]
