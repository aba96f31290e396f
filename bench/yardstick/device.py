"""The chip: refuse to run without one, describe it, read its memory peak.

Also fixes JAX's persistent compilation cache at one path inside the
checkout, so that only the first run of a cell there compiles.
"""
from __future__ import annotations

import os

from yardstick.registry import ROOT

CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    pass


def configure_cache() -> str:
    """Point JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    and cache every program, however fast it compiled. Call before the first
    compile."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(CACHE_DIR)


def require_tpu(chips: int):
    """The first ``chips`` TPU devices; raises NoChip when JAX finds no TPU or
    fewer chips than the cell asks for. Never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform if devices else None!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``, as the runtime reports."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
