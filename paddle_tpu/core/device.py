"""Device / place abstraction over JAX devices.

Reference parity: platform/place.h:104 (``Place`` boost::variant of CUDAPlace/
XPUPlace/CPUPlace/...), platform/device_context.h DeviceContext pool, and
platform/init.cc device discovery.  TPU-native design: the whole L0a layer of
the reference collapses onto JAX's PJRT client — a ``Place`` here is a thin,
hashable handle resolving to a ``jax.Device``; there are no device contexts,
streams, or dlopen shims to manage (SURVEY.md §1 L0a "TPU mapping").
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax


class Place:
    """A device handle: ``TPUPlace(0)``, ``CPUPlace()``."""

    _platform: str = ""

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def get_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if d.platform == self._platform]
        if not devs:
            raise RuntimeError(f"No {self._platform} devices visible to JAX")
        return devs[self.device_id % len(devs)]

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    _platform = "cpu"

    def get_device(self) -> jax.Device:
        return jax.local_devices(backend="cpu")[self.device_id]


class TPUPlace(Place):
    _platform = "tpu"


class CUDAPlace(Place):
    """Accepted for API compat with reference scripts; the accelerator here
    is the TPU."""

    _platform = "tpu"


_current_place: Optional[Place] = None


def _default_place() -> Place:
    d = jax.devices()[0]
    return CPUPlace(0) if d.platform == "cpu" else TPUPlace(0)


def set_device(place) -> Place:
    """Set the default place. Accepts a Place or strings like 'tpu:0', 'cpu'."""
    global _current_place
    if isinstance(place, str):
        name, _, idx = place.partition(":")
        idx = int(idx) if idx else 0
        cls = {"cpu": CPUPlace, "tpu": TPUPlace, "gpu": CUDAPlace, "xpu": TPUPlace}.get(name)
        if cls is None:
            raise ValueError(f"Unknown device string {place!r}")
        place = cls(idx)
    _current_place = place
    jax.config.update("jax_default_device", place.get_device())
    return place


def get_device() -> Place:
    return _current_place if _current_place is not None else _default_place()


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in jax.devices())


@contextlib.contextmanager
def device_guard(place):
    """Scoped default-place override (ref: fluid.device_guard)."""
    global _current_place
    prev, prev_dev = _current_place, jax.config.jax_default_device
    try:
        set_device(place)
        yield
    finally:
        _current_place = prev
        jax.config.update("jax_default_device", prev_dev)
