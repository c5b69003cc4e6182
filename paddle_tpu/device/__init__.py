"""Device API (reference: python/paddle/device/__init__.py:250 set_device,
:419 Event, :569 Stream, :900 synchronize).

On TPU the PJRT runtime owns streams/allocation; Event/Stream are provided
as API-parity objects mapping to jax's async dispatch (block_until_ready)."""

from __future__ import annotations

import jax

__all__ = ["set_device", "get_device", "get_all_devices", "device_count",
           "synchronize", "is_compiled_with_cuda", "is_compiled_with_tpu",
           "is_compiled_with_rocm", "is_compiled_with_xpu",
           "is_compiled_with_custom_device", "Stream", "Event",
           "get_available_device", "get_available_custom_device", "cuda"]

_current_device = [None]


def set_device(device: str):
    """paddle.set_device parity. Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0'
    (gpu maps to whatever accelerator jax exposes). Asking for an
    accelerator on a host that has none, or for an index past the last
    device, is an error — never a quiet CPU or a clamped index."""
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name in ("tpu", "gpu", "xpu", "npu", "custom"):
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if not devs:
            raise RuntimeError(
                f"set_device({device!r}): JAX found no accelerator "
                f"(devices: {[d.platform for d in jax.devices()]})")
    else:
        devs = jax.devices("cpu")
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"set_device({device!r}): index {idx} out of range, "
            f"{len(devs)} {name} device(s)")
    _current_device[0] = devs[idx]
    return _current_device[0]


def get_device() -> str:
    d = _current_device[0]
    if d is None:
        d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def get_current_device():
    d = _current_device[0]
    return d if d is not None else jax.devices()[0]


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_device():
    return get_all_devices()


def get_available_custom_device():
    return []


def device_count() -> int:
    return jax.device_count()


def synchronize(device=None):
    """Block until all queued device work completes (reference
    device/__init__.py:900; PJRT equivalent of stream sync)."""
    jax.effects_barrier()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_custom_device(device_name: str = "") -> bool:
    return device_name == "tpu"


class Stream:
    """API-parity stream. XLA/PJRT serializes per-device execution; multiple
    streams map onto jax's async dispatch, so this is ordering metadata."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self.device = device

    def record(self, stream=None):
        pass

    def query(self) -> bool:
        return True

    def synchronize(self):
        synchronize()


class _CudaNamespace:
    """paddle.device.cuda shim so CUDA-written scripts run (reference
    python/paddle/device/cuda)."""

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def max_memory_allocated(device=None):
        return max_memory_allocated(device)

    @staticmethod
    def memory_allocated(device=None):
        return memory_allocated(device)

    Stream = Stream
    Event = Event


cuda = _CudaNamespace()


# ---- round-2 parity additions (reference: python/paddle/device/__init__.py)

class IPUPlace:
    """Accepted for API compat; no IPU backend on TPU builds."""


class XPUPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id


_current_streams = {}


def current_stream(device=None):
    """The current Stream for a device (reference: device current_stream).
    XLA's async dispatch owns real streams; this handle exists for
    ordering APIs (wait_event/record_event are host-side no-ops that
    block_until_ready)."""
    key = device or get_device()
    if key not in _current_streams:
        _current_streams[key] = Stream()
    return _current_streams[key]


def set_stream(stream):
    key = get_device()
    prev = _current_streams.get(key)
    _current_streams[key] = stream
    return prev


class stream_guard:
    """Context manager swapping the current stream (reference:
    device stream_guard)."""

    def __init__(self, stream):
        self._stream = stream

    def __enter__(self):
        key = get_device()
        self._had_prev = key in _current_streams
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        if self._had_prev:
            set_stream(self._prev)
        else:
            _current_streams.pop(get_device(), None)
        return False


def get_cudnn_version():
    """None: no cuDNN in a TPU build (reference returns int or None)."""
    return None


def get_all_device_type():
    import jax
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return []


def is_compiled_with_cinn():
    return False


def is_compiled_with_ipu():
    return False


__all__ += ["IPUPlace", "XPUPlace", "current_stream", "set_stream",
            "stream_guard", "get_cudnn_version", "get_all_device_type",
            "get_all_custom_device_type", "is_compiled_with_cinn",
            "is_compiled_with_ipu"]


# ---- round-3: allocator-facade stats + OOM diagnostics (reference:
# fluid/memory/allocation/allocator_facade.h:45 + memory/stats.h
# STAT_GPU_MEM peak tracking; device/cuda max_memory_allocated). PJRT owns
# the real allocator; the facade here accounts LIVE jax arrays per device
# (backend memory_stats() when the runtime exposes it) and keeps the
# process-level peak the reference's Stat objects track.

_MEM_PEAK: dict = {}
_PEAK_BASE: dict = {}


def _device_key(device=None):
    import jax
    if device is None:
        return jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        # "gpu:0" / "tpu:1" / "0" — reference device-string forms
        idx = int(device.split(":")[-1]) if device.split(":")[-1].isdigit() \
            else 0
        return jax.devices()[idx]
    return device


def memory_stats(device=None) -> dict:
    """Allocator stats: backend PJRT stats when available, else live-array
    accounting. Keys mirror the reference's memory/stats.h naming."""
    import jax
    dev = _device_key(device)
    backend = None
    if hasattr(dev, "memory_stats"):
        backend = dev.memory_stats()

    def _dev_bytes(a):
        """Bytes of `a` RESIDENT ON dev — shard-level accounting so a
        mesh-sharded array isn't charged its global size on every
        device it touches."""
        try:
            return sum(sh.data.nbytes for sh in a.addressable_shards
                       if sh.device == dev)
        except Exception:  # noqa: BLE001 — shard objects unavailable
            devs = getattr(a, "devices", lambda: set())()
            if dev not in devs:
                return 0
            try:
                # exact per-device bytes from the sharding's shard shape
                # (replicated -> full size, sharded -> slice size); never
                # charge the GLOBAL size per device
                shp = a.sharding.shard_shape(a.shape)
                n = 1
                for s in shp:
                    n *= s
                return n * a.dtype.itemsize
            except Exception:  # noqa: BLE001 — even split approximation
                return a.nbytes // max(len(devs), 1)

    pairs = [(a, _dev_bytes(a)) for a in jax.live_arrays()]
    pairs = [(a, b) for a, b in pairs if b > 0]
    in_use = sum(b for _, b in pairs)
    # backend peak is process-lifetime and non-resettable; track a
    # baseline so reset_max_memory_allocated() actually resets
    backend_peak = (backend or {}).get("peak_bytes_in_use", 0)
    base = _PEAK_BASE.get(dev, 0)
    peak = max(_MEM_PEAK.get(dev, 0), in_use,
               max(backend_peak - base, 0))
    _MEM_PEAK[dev] = peak
    largest = sorted(pairs, key=lambda p: p[1], reverse=True)[:5]
    return {
        "bytes_in_use": (backend or {}).get("bytes_in_use", in_use),
        "peak_bytes_in_use": peak,
        "num_live_arrays": len(pairs),
        "largest_arrays": [
            {"shape": tuple(a.shape), "dtype": str(a.dtype),
             "nbytes": b} for a, b in largest],
        "backend": backend,
    }


def memory_allocated(device=None) -> int:
    """reference device/cuda memory_allocated — live bytes on device."""
    return int(memory_stats(device)["bytes_in_use"])


def max_memory_allocated(device=None) -> int:
    """reference max_memory_allocated — process-lifetime peak, sampled
    at every stats call (PJRT exposes no allocation callbacks)."""
    return int(memory_stats(device)["peak_bytes_in_use"])


def memory_reserved(device=None) -> int:
    """PJRT reserves what it uses; reserved == allocated here."""
    return memory_allocated(device)


def max_memory_reserved(device=None) -> int:
    return max_memory_allocated(device)


def reset_max_memory_allocated(device=None):
    dev = _device_key(device)
    if hasattr(dev, "memory_stats"):
        backend = dev.memory_stats() or {}
        _PEAK_BASE[dev] = backend.get("peak_bytes_in_use", 0)
    _MEM_PEAK[dev] = 0
    _MEM_PEAK[dev] = memory_allocated(device)


def reset_max_memory_reserved(device=None):
    reset_max_memory_allocated(device)


def explain_oom(exc, model=None, optimizer=None) -> str:
    """Build the OOM diagnostic the reference's allocator raises
    (auto_growth_best_fit_allocator's 'Cannot allocate ... memory info'
    block): what is resident, who owns it, and what to do about it."""
    first = (str(exc).splitlines() or ["<no message>"])[0]
    lines = ["Device out of memory (XLA RESOURCE_EXHAUSTED).",
             f"  original: {first[:200]}"]
    try:
        st = memory_stats()
        lines.append(f"  live: {st['bytes_in_use'] / 2**30:.2f} GiB in "
                     f"{st['num_live_arrays']} arrays "
                     f"(peak {st['peak_bytes_in_use'] / 2**30:.2f} GiB)")
        for a in st["largest_arrays"]:
            lines.append(f"    largest: {a['shape']} {a['dtype']} "
                         f"{a['nbytes'] / 2**20:.1f} MiB")
    except Exception:  # noqa: BLE001 — diagnostics must not mask the OOM
        pass
    if model is not None:
        try:
            pb = sum(p._value.nbytes for p in model.parameters())
            lines.append(f"  model parameters: {pb / 2**30:.2f} GiB")
        except Exception:  # noqa: BLE001
            pass
    if optimizer is not None:
        try:
            ob = sum(a.nbytes for arrs in optimizer._accumulators.values()
                     for a in arrs)
            lines.append(f"  optimizer state: {ob / 2**30:.2f} GiB")
        except Exception:  # noqa: BLE001
            pass
    lines.append("  remedies: enable recompute (cfg.recompute=True), "
                 "shard optimizer state (ZeRO: apply_sharding_specs), "
                 "reduce batch/sequence, or raise mp/pp degrees.")
    return "\n".join(lines)


def _wrap_oom(exc, model=None, optimizer=None):
    """Re-raise an XLA RESOURCE_EXHAUSTED with the diagnostic attached;
    returns False for non-OOM errors (caller re-raises the original)."""
    if "RESOURCE_EXHAUSTED" not in str(exc) and \
            "Out of memory" not in str(exc):
        return False
    raise RuntimeError(explain_oom(exc, model, optimizer)) from exc


class oom_diagnostics:
    """Context manager wrapping device execution: an OOM escapes with
    the full diagnostic, everything else re-raises untouched. Shared by
    TrainStep and DistTrainStep."""

    def __init__(self, model=None, optimizer=None):
        self.model = model
        self.optimizer = optimizer

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None and isinstance(exc, Exception):
            _wrap_oom(exc, self.model, self.optimizer)
        return False


__all__ += ["memory_stats", "memory_allocated", "max_memory_allocated",
            "memory_reserved", "max_memory_reserved",
            "reset_max_memory_allocated", "reset_max_memory_reserved",
            "explain_oom", "oom_diagnostics"]
