"""paddle.device parity (ref: python/paddle/device/ (U))."""

from ..core.device import (
    set_device, get_device, get_default_device, device_count,
    is_compiled_with_cuda, is_compiled_with_tpu, synchronize, Place,
)


def get_available_device():
    import jax

    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return []


def is_compiled_with_rocm():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_cinn():
    # XLA plays CINN's role and is always present
    return True


def is_compiled_with_distribute():
    return True


class cuda:
    """paddle.device.cuda stubs (no CUDA on the TPU build)."""

    @staticmethod
    def device_count():
        return 0

    @staticmethod
    def is_available():
        return False

    @staticmethod
    def empty_cache():
        pass

    @staticmethod
    def synchronize(device=None):
        synchronize()

    @staticmethod
    def max_memory_allocated(device=None):
        return 0

    @staticmethod
    def memory_allocated(device=None):
        return 0


class tpu:
    """TPU introspection — the CUDAPlace analog."""

    @staticmethod
    def device_count():
        import jax

        return sum(1 for d in jax.devices() if d.platform == "tpu")

    @staticmethod
    def is_available():
        return tpu.device_count() > 0

    @staticmethod
    def synchronize():
        synchronize()

    @staticmethod
    def memory_stats(device=None):
        import jax

        devs = [d for d in jax.devices() if d.platform == "tpu"]
        if not devs:
            return {}
        try:
            return devs[0].memory_stats() or {}
        except Exception:
            return {}


def get_all_device_type():
    import jax

    return sorted({d.platform for d in jax.devices()})


class Stream:
    """CUDA-stream shim: XLA owns scheduling on TPU; the API exists so
    reference scripts construct/synchronize streams as no-ops."""

    def __init__(self, device=None, priority=None):
        self.device = device

    def synchronize(self):
        import jax

        jax.effects_barrier() if hasattr(jax, "effects_barrier") else None

    def wait_event(self, event):
        return None

    def wait_stream(self, stream):
        return None

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        return None

    def query(self):
        return True

    def synchronize(self):
        return None


def stream_guard(stream):
    import contextlib

    return contextlib.nullcontext()


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


class _StreamNS:
    Stream = Stream
    Event = Event
    stream_guard = staticmethod(stream_guard)
    current_stream = staticmethod(current_stream)
    set_stream = staticmethod(set_stream)


stream = _StreamNS()
