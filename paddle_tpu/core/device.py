"""Device management (ref: python/paddle/device/ (U), paddle.set_device).

On TPU there is no CUDAPlace/stream zoo to manage — XLA/PJRT owns placement —
so this is a thin veneer over jax.devices() that preserves the Paddle API.
"""

from __future__ import annotations

import warnings

import jax


class Place:
    def __init__(self, device):
        self._device = device

    @property
    def platform(self):
        return self._device.platform

    def __repr__(self):
        return f"Place({self._device})"


_CURRENT = [None]


def set_device(device: str):
    """Accepts 'tpu', 'cpu', 'tpu:0' etc. Returns the Place.  A platform
    or an index that is not there raises: asking for a TPU must never
    quietly hand back a CPU."""
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    if name in ("gpu", "cuda", "xpu"):
        # the reference's device names mean "the accelerator": map them
        # to the default platform, and say so
        mapped = _default_platform()
        warnings.warn(f"set_device({device!r}): no such platform here; "
                      f"using the default platform {mapped!r}")
        name = mapped
    devs = [d for d in jax.devices() if d.platform == name]
    if idx >= len(devs):
        have = sorted({d.platform for d in jax.devices()})
        raise ValueError(
            f"set_device({device!r}): {len(devs)} {name!r} device(s) "
            f"present (platforms here: {have})")
    _CURRENT[0] = Place(devs[idx])
    return _CURRENT[0]


def _default_platform():
    return jax.devices()[0].platform


def get_device() -> str:
    if _CURRENT[0] is None:
        d = jax.devices()[0]
        return f"{d.platform}:{d.id}"
    d = _CURRENT[0]._device
    return f"{d.platform}:{d.id}"


def get_default_device():
    return _CURRENT[0]._device if _CURRENT[0] is not None else jax.devices()[0]


def device_count() -> int:
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


def synchronize():
    # XLA is async; block on a trivial transfer to drain the stream.
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()
