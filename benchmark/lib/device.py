"""The device as JAX reports it, the table of peaks, and a count of what
JAX compiles. A measuring path that finds no TPU fails here."""

from __future__ import annotations

import json

from .manifest import BENCH_DIR


class NoChip(SystemExit):
    pass


def require_chips(n, allow_cpu=False):
    """The first ``n`` devices, or exit non-zero: no accelerator, or
    fewer chips than the cell asks for. ``allow_cpu`` is for the CPU
    rehearsal under benchmark/tests, never for the benchmark's command."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"benchmark: needs a TPU; JAX found "
                     f"{devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < n:
        raise NoChip(f"benchmark: the cell needs {n} chip(s) but JAX found "
                     f"{len(devices)}")
    return devices[:n]


def peaks_for(device_kind):
    """Peaks of one chip of this kind; an unknown kind is an error."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks known for device_kind {device_kind!r}: "
                       f"add it to benchmark/peaks.json with its source")
    return table[device_kind]


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip (0 where the backend does
    not report it, as on the CPU)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def describe(devices, **extra):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices), **extra}


class CompileWatch:
    """Counts the programs JAX lowers and the seconds its backend spends
    compiling, so that a window in which something compiled shows."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon
        self.lowered = 0
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, name, secs, **kw):
        if name == self.LOWER:
            self.lowered += 1
        elif name == self.COMPILE:
            self.compile_s += secs
