"""The chip a run stands on: its check, its peaks, the compile cache and
the compile clock.

A run that finds no TPU, or fewer chips than its cell asks for, stops with
``NoChip`` and prints no result: no number from another backend is ever
written under a device metric's name.
"""
from __future__ import annotations

import json
import os
import zlib
from pathlib import Path
from typing import Any, Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """No accelerator of the kind the benchmark measures."""


def enable_compile_cache(root: Path) -> str:
    """Persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` where it
    is set, else ``<root>/.jax_cache`` (a fixed path: the path is part of the
    cache key). Every program is cached, however quick its compile, so a
    second run of a cell compiles nothing. Under ``JAX_PLATFORMS=cpu`` (the
    tests) nothing is cached. Call before the first compile."""
    import jax
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return ""                     # nothing of the chip's to keep
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check_chip(chips: int):
    """The devices the cell runs on; raises NoChip off a TPU or short of
    ``chips`` devices."""
    import jax
    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform "
                     f"{devs[0].platform if devs else None!r}")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX reports {len(devs)}")
    return devs[:chips]


def peaks(device_kind: str) -> Dict[str, Any]:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise NoChip(f"device kind {device_kind!r} is not in {PEAKS_FILE}")
    return table[device_kind]


def device_info(devices) -> Dict[str, Any]:
    """Platform, kind, count and the peak bytes of the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileClock:
    """Counts JAX backend compiles (a persistent-cache hit counts too, as
    its retrieval); ``mark()``/``since()`` count the compiles of a stretch
    such as the measured window."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, seconds, **_):
        if event == COMPILE_EVENT:
            self.count += 1

    def mark(self) -> None:
        self._mark = self.count

    def since(self) -> int:
        return self.count - self._mark


def seed_key(seed: int, stream: str):
    """A PRNG key for one named stream of ``seed`` (any size of integer)."""
    import jax
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, zlib.crc32(stream.encode()) & 0x7FFFFFFF)
