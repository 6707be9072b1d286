"""Device management (reference: python/paddle/device/ + phi DeviceManager
device_manager.h:134). On the TPU stack PJRT owns devices; this module maps
the reference's Place/device-string surface onto jax.devices()."""
from __future__ import annotations

import jax


class Place:
    def __init__(self, kind, index=0):
        self.kind = kind
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return isinstance(other, Place) and (self.kind, self.index) == (other.kind, other.index)


class CPUPlace(Place):
    def __init__(self, index=0):
        super().__init__("cpu", index)


class TPUPlace(Place):
    def __init__(self, index=0):
        super().__init__("tpu", index)


class CUDAPlace(Place):
    """Accepted for API parity; maps onto the default accelerator."""

    def __init__(self, index=0):
        super().__init__("gpu", index)


class XPUPlace(Place):
    def __init__(self, index=0):
        super().__init__("xpu", index)


class CUDAPinnedPlace(Place):
    def __init__(self, index=0):
        super().__init__("cpu", index)


#: Published peaks of one chip, keyed by jax's `device_kind` — the one
#: table every MFU / bandwidth-share / memory-fit computation reads.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 16 GB of HBM at 819 GB/s). A kind without a sourced row is an error,
#: never a default: a rate divided by the wrong peak is a wrong number.
CHIP_PEAKS = {
    "TPU v5 lite": dict(bf16_flops=197e12, hbm_bandwidth=819e9,
                        hbm_bytes=16e9),
}


def chip_peaks(device_kind):
    """The `CHIP_PEAKS` row for `device_kind` (`jax.Device.device_kind`)."""
    try:
        return CHIP_PEAKS[str(device_kind)]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {str(device_kind)!r}: add "
            f"a sourced row to paddle_tpu.device.CHIP_PEAKS (known: "
            f"{sorted(CHIP_PEAKS)})") from None


_current_device = None


def set_device(device: str):
    """Reference: paddle.set_device. Accepts 'cpu', 'tpu', 'tpu:0', 'gpu:0'
    (mapped to the default accelerator)."""
    global _current_device
    _current_device = device
    return device


def get_device() -> str:
    if _current_device is not None:
        return _current_device
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"


def device_count() -> int:
    return jax.device_count()


def get_all_devices():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_distribute() -> bool:
    return True


class cuda:
    """Namespace parity for paddle.device.cuda — returns TPU stats."""

    @staticmethod
    def device_count():
        return jax.device_count()

    @staticmethod
    def max_memory_allocated(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0)

    @staticmethod
    def memory_allocated(device=None):
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("bytes_in_use", 0)

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def synchronize(device=None):
        jax.effects_barrier()


# ---------------------------------------------------------------------------
# round-3 device-surface completions (reference: python/paddle/device/
# __init__.py — streams/events, device enumeration, build introspection)
# ---------------------------------------------------------------------------


class Stream:
    """Reference: device.Stream. PJRT owns real streams; this handle keeps
    the API contract (creation, priority, synchronize via host fence) for
    code structured around stream scoping."""

    def __init__(self, device=None, priority=2):
        self.device = device
        self.priority = priority

    def synchronize(self):
        synchronize()

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        stream.synchronize()

    def __repr__(self):
        return f"Stream(device={self.device}, priority={self.priority})"


class Event:
    """Reference: device.Event — record/synchronize/query over a stream."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self.device = device
        self._recorded = False

    def record(self, stream=None):
        self._recorded = True

    def query(self):
        return True          # all prior work observable after host fence

    def synchronize(self):
        synchronize()


_current_stream = Stream()


def current_stream(device=None):
    return _current_stream


def set_stream(stream):
    global _current_stream
    prev = _current_stream
    _current_stream = stream
    return prev


class stream_guard:
    """Reference: device.stream_guard context manager."""

    def __init__(self, stream):
        self._stream = stream

    def __enter__(self):
        self._prev = set_stream(self._stream)
        return self._stream

    def __exit__(self, *exc):
        set_stream(self._prev)
        return False


def synchronize(device=None):
    """Block until all queued device work has finished: a device runs its
    programs in order, so waiting on a freshly enqueued one fences every
    dispatch issued before it."""
    import jax.numpy as _jnp
    _jnp.zeros(()).block_until_ready()


def get_cudnn_version():
    """Reference returns None when not compiled with CUDA."""
    return None


def is_compiled_with_cinn():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_custom_device(device_type=None):
    return False


class IPUPlace(Place):
    def __init__(self):
        raise NotImplementedError(
            "IPU support is not provided in the TPU build (reference "
            "gates it behind WITH_IPU)")


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return []


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


FLAGS_selected_xpus = ""   # reference exports the env-flag name
