"""The device under the run: which it is, whether it is the one the cell asks
for, its published peaks, its memory high-water mark, and where compiled
programs are kept."""
from __future__ import annotations

import json
import os
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": jax.device_count()}


def require_tpu(chips: int) -> dict:
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} TPU chip(s); JAX reports "
                     f"{info['count']} x {info['platform']} ({info['kind']})")
    return info


def peaks(kind: str) -> dict:
    """Published peaks of a `device_kind`. A kind that is not in the table is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in {PEAKS_FILE}")
    return table[kind]


def memory_stats() -> dict:
    """memory_stats() of the fullest chip ({} where the backend has none)."""
    import jax
    best = {}
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        if _peak(s) >= _peak(best):
            best = s
    return {k: int(v) for k, v in best.items()}


def _peak(stats: dict) -> int:
    # On this runtime live buffers count under peak_bytes_in_use and the
    # compiled programs' temporaries under peak_bytes_reserved (PERF.md,
    # PR 21): the footprint is their sum, and never more than the chip.
    both = int(stats.get("peak_bytes_in_use", 0)) + int(
        stats.get("peak_bytes_reserved", 0))
    limit = int(stats.get("bytes_limit", 0))
    return min(both, limit) if limit else both


def memory_peak_bytes() -> int:
    return _peak(memory_stats())


def enable_compile_cache(repo_root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the checkout
    (or where JAX_COMPILATION_CACHE_DIR says). Every compile is kept, however
    short, so that a second run of a cell compiles nothing."""
    import jax
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(Path(repo_root) / ".jax_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileCounter:
    """Counts backend compiles (or cache loads) process-wide, other threads'
    too. The window has to see none."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._EVENT:
            self.count += 1
