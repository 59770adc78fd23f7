"""The device a run stands on, its compile events and its memory peak."""
from __future__ import annotations

import os
import sys

__all__ = ["require_chips", "CompileClock", "memory_peak", "enable_cache",
           "trace_dir"]

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def require_chips(chips: int, accelerator: bool = True) -> dict:
    """JAX's devices as the result line names them.  Exits non-zero, with
    no result, unless JAX sees at least ``chips`` TPU chips (the check is
    skipped only where a test drives the harness on the CPU)."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
    if accelerator and info["platform"] != "tpu":
        print(f"no TPU: JAX runs on {info['platform']}", file=sys.stderr)
        raise SystemExit(3)
    if info["count"] < chips:
        print(f"need {chips} chips, JAX sees {info['count']}",
              file=sys.stderr)
        raise SystemExit(3)
    return info


def enable_cache() -> str:
    """JAX's persistent compile cache at the repository's fixed path (or
    where ``JAX_COMPILATION_CACHE_DIR`` already points), keeping every
    program, however quick to compile, so a second run compiles nothing."""
    import jax

    from repro.runtime import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileClock:
    """Seconds and count of JAX's tracing, lowering and compiling; a
    persistent-cache hit counts only its retrieval."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
        if event == _BACKEND_COMPILE:
            self.compiles += 1


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no count)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def trace_dir(root, workload: str, seed: int) -> str:
    path = os.path.join(root, ".bench_out", "trace", f"{workload}-{seed}")
    os.makedirs(path, exist_ok=True)
    return path
