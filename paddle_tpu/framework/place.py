"""Device places.

Parity target: ``/root/reference/paddle/fluid/platform/place.h`` (CPUPlace,
CUDAPlace, XPUPlace, NPUPlace, CUDAPinnedPlace) and the Python surface
``paddle.set_device`` (``/root/reference/python/paddle/device.py``).

TPU-first design: a "place" maps to a jax backend + device index.  The
framework's north star is ``paddle.set_device('tpu')`` as the only user-facing
change, so ``TPUPlace`` is first-class and ``CUDAPlace`` is accepted as an
alias that resolves to whatever accelerator jax exposes.
"""

from __future__ import annotations

import os
import threading


class Place:
    _backend = "cpu"

    def __init__(self, device_id: int = 0):
        self._device_id = int(device_id)

    def get_device_id(self) -> int:
        return self._device_id

    @property
    def backend(self) -> str:
        return self._backend

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self._backend == other._backend
            and self._device_id == other._device_id
        )

    def __hash__(self):
        return hash((self._backend, self._device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self._device_id})"

    def jax_device(self):
        import jax

        devs = jax.devices() if self._backend != "cpu" else jax.devices("cpu")
        return devs[self._device_id % len(devs)]


class CPUPlace(Place):
    _backend = "cpu"

    def __init__(self):
        super().__init__(0)


class TPUPlace(Place):
    _backend = "tpu"


class CUDAPlace(Place):
    """Accepted for API parity; resolves to the default accelerator."""

    _backend = "accel"


class CUDAPinnedPlace(CPUPlace):
    pass


class XPUPlace(TPUPlace):
    pass


class NPUPlace(TPUPlace):
    """Accepted for API parity (the fork's Ascend place); resolves to the
    default accelerator like CUDAPlace."""


_state = threading.local()


def _default_device_str() -> str:
    env = os.environ.get("PADDLE_TPU_DEVICE")
    if env:
        return env
    import jax

    # a backend that fails to initialise raises here: a chip that did not
    # come up must not read as "cpu"
    plat = jax.default_backend()
    if plat == "tpu":
        return "tpu"
    if plat in ("gpu", "cuda", "rocm"):
        return "gpu"
    return "cpu"


def set_device(device: str):
    """``paddle.set_device('tpu')`` / ``('cpu')`` / ``('tpu:0')``."""
    device = device.lower()
    if ":" in device:
        kind, idx = device.split(":", 1)
        idx = int(idx)
    else:
        kind, idx = device, 0
    if kind in ("tpu", "gpu", "cuda", "xpu", "npu", "accel"):
        place = TPUPlace(idx)
    elif kind == "cpu":
        place = CPUPlace()
    else:
        raise ValueError(f"Unknown device {device!r}")
    _state.place = place
    return place


def get_device() -> str:
    p = _get_current_place()
    if isinstance(p, CPUPlace):
        return "cpu"
    return f"tpu:{p.get_device_id()}"


def _get_current_place() -> Place:
    place = getattr(_state, "place", None)
    if place is None:
        kind = _default_device_str()
        place = CPUPlace() if kind == "cpu" else TPUPlace(0)
        _state.place = place
    return place


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"
