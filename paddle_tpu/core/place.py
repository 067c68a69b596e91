"""Places and the device context pool.

Parity target: paddle::platform::Place variant + DeviceContextPool
(reference: paddle/fluid/platform/place.h, device_context.h) and the
Python device API (python/paddle/device/__init__.py set_device:291).

TPU-native design: a Place maps onto a jax.Device. The "device context"
owns nothing stream-like — XLA/PJRT manages streams — but it is the
single point that resolves `paddle_tpu.set_device(...)` to the jax
device used for tensor placement and compilation.
"""
from __future__ import annotations

import threading

import jax


class Place:
    """Base class of device places."""

    device_type = "undefined"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    def get_device_id(self):
        return self.device_id


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    """First-class TPU place — the analog of CUDAPlace (place.h)."""

    device_type = "tpu"


class CUDAPinnedPlace(Place):  # accepted for API compat; maps to host
    device_type = "cpu"


class DeviceContext:
    """Resolves a Place to a concrete jax.Device."""

    def __init__(self, place: Place):
        self.place = place
        self._device = None

    @property
    def device(self):
        if self._device is None:
            want = self.place.device_type
            # LOCAL devices only: under multi-process SPMD, eager
            # tensors must live on this process's devices (global
            # jax.devices() includes non-addressable peers)
            devs = [d for d in jax.local_devices()
                    if d.platform == want]
            if not devs and want == "cpu":
                # the host client exists beside an accelerator default
                devs = jax.local_devices(backend="cpu")
            if not devs:
                raise RuntimeError(
                    f"{self.place!r} requested but this process has no "
                    f"{want} device (jax.local_devices(): "
                    f"{jax.local_devices()})")
            if self.place.device_id >= len(devs):
                raise RuntimeError(
                    f"{self.place!r} requested but only {len(devs)} "
                    f"{want} device(s) are local")
            self._device = devs[self.place.device_id]
        return self._device


class DeviceContextPool:
    """Singleton Place→DeviceContext map (device_context.h analog)."""

    _instance = None
    _lock = threading.Lock()

    def __init__(self):
        self._contexts = {}

    @classmethod
    def instance(cls) -> "DeviceContextPool":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def get(self, place: Place) -> DeviceContext:
        ctx = self._contexts.get(place)
        if ctx is None:
            ctx = DeviceContext(place)
            self._contexts[place] = ctx
        return ctx


_current_place = None
_place_lock = threading.Lock()


def _default_place() -> Place:
    for d in jax.devices():
        if d.platform == "tpu":
            return TPUPlace(0)
    return CPUPlace(0)


def get_device_place() -> Place:
    global _current_place
    with _place_lock:
        if _current_place is None:
            _current_place = _default_place()
        return _current_place


def set_device(device) -> Place:
    """paddle.set_device equivalent: 'tpu', 'tpu:0', 'cpu'."""
    global _current_place
    if isinstance(device, Place):
        place = device
    else:
        name, _, idx = str(device).partition(":")
        idx = int(idx) if idx else 0
        name = name.lower()
        if name in ("tpu", "gpu", "xpu", "npu", "mlu", "ipu", "cuda"):
            # any accelerator name maps to the TPU place — this IS the
            # TPU-native build; gpu aliases keep user code portable.
            place = TPUPlace(idx)
        elif name == "cpu":
            place = CPUPlace(idx)
        else:
            raise ValueError(f"Unknown device {device!r}")
    with _place_lock:
        _current_place = place
    return place


def get_device() -> str:
    p = get_device_place()
    return f"{p.device_type}:{p.device_id}"


def device_of(place: Place):
    return DeviceContextPool.instance().get(place).device


def current_device():
    return device_of(get_device_place())


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_cuda() -> bool:
    return False
