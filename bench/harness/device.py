"""The chip: refusal without one, compile cache, peak memory, compiles."""
from __future__ import annotations

import os
from pathlib import Path

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def enable_compile_cache(checkout: Path) -> str:
    """Persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``, else
    at the fixed ``<checkout>/.jax_cache`` (the path is part of the key)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        checkout / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # the kernels compile in well under the default 1 s threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts programs built for this process: JAX reports a backend
    compile for each, whether it was compiled or loaded from the
    persistent cache."""

    def __init__(self):
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1

    def snapshot(self) -> int:
        """Programs built or loaded so far."""
        return self.compiles


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def describe(devices, memory_peak: int) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak}
