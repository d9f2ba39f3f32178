"""Device places.

Mirrors the reference's Place taxonomy
(/root/reference/paddle/fluid/platform/place.h) with TPUPlace as the native
accelerator.  A Place wraps a jax.Device; everything above dispatches through
jax's own device placement, so Place is an identity + API-parity object, not a
dispatch key (XLA owns kernel selection on TPU).
"""
from __future__ import annotations

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    @property
    def jax_device(self):
        platform = self._platform()
        # by platform, not from jax.devices(): that lists the DEFAULT
        # backend only, and a TPU host still has its CPU device
        try:
            devs = jax.devices(platform)
        except RuntimeError as e:
            raise ValueError(
                f"{self!r}: jax has no {platform!r} backend here (default "
                f"backend {jax.default_backend()!r}): {e}") from None
        if self._device_id >= len(devs):
            raise ValueError(
                f"{self!r}: jax reports {len(devs)} {platform!r} "
                f"device(s) — no device with that id")
        return devs[self._device_id]

    def _platform(self) -> str:
        return self.device_type

    def __eq__(self, other):
        return (
            type(self) is type(other) and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self._device_id})"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    device_type = "tpu"


class CUDAPlace(Place):
    """GPU place. Accepted for API parity; resolves to whatever accelerator jax
    exposes (on a TPU host this is the TPU chip)."""

    device_type = "gpu"

    def _platform(self) -> str:
        return _accelerator_platform()


class CUDAPinnedPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class XPUPlace(Place):
    device_type = "tpu"


def _accelerator_platform():
    platforms = {d.platform for d in jax.devices()}
    for p in ("tpu", "gpu"):
        if p in platforms:
            return p
    return "cpu"


def default_place() -> Place:
    from . import _globals

    if _globals.DEFAULT_PLACE is not None:
        return _globals.DEFAULT_PLACE
    p = _accelerator_platform()
    if p == "tpu":
        return TPUPlace(0)
    if p == "gpu":
        return CUDAPlace(0)
    return CPUPlace()


def set_device(device: str) -> Place:
    """paddle.set_device parity: 'cpu', 'tpu', 'tpu:0', 'gpu:0', 'xpu:0'."""
    from . import _globals

    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "cpu":
        place = CPUPlace()
    elif name in ("tpu", "xpu"):
        place = TPUPlace(idx)
    elif name in ("gpu", "cuda"):
        place = CUDAPlace(idx)
    else:
        raise ValueError(f"unknown device {device!r}")
    _globals.DEFAULT_PLACE = place
    return place


def get_device() -> str:
    p = default_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"{p.device_type}:{p.get_device_id()}"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_xpu() -> bool:
    return False
