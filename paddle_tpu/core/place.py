"""Device / Place management.

TPU-native re-design of the reference's Place/Backend machinery
(`/root/reference/paddle/phi/common/place.h:58`, `phi/common/backend.h:40`) and
`paddle.set_device` (`python/paddle/device/__init__.py`).

On TPU there is no per-device context pool, stream or allocator to manage from
Python: XLA's PJRT runtime owns those. A Place is therefore identity only, and
`set_device` simply selects the JAX device new tensors land on. Device
discovery is `jax.devices()`, asked directly: a backend that fails to
initialize raises with JAX's own message rather than turning into a CPU place.
"""
from __future__ import annotations

import jax


class Place:
    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"

    # Compat shims for code written against the reference API.
    is_gpu_place = is_tpu_place
    is_custom_place = is_tpu_place


def CPUPlace():
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0):
    return Place("tpu", device_id)


# GPU-parity alias so reference-style scripts run unmodified on TPU.
CUDAPlace = TPUPlace
XPUPlace = TPUPlace


def _accelerators():
    """The TPU chips JAX reports (empty on a CPU-only backend)."""
    return tuple(d for d in jax.devices() if d.platform == "tpu")


_current_place: Place | None = None


def is_compiled_with_tpu() -> bool:
    return len(_accelerators()) > 0


# Reference-parity helpers (`paddle.is_compiled_with_cuda` etc.): the TPU build
# reports its accelerator through all of them so device-probing user code works.
is_compiled_with_cuda = is_compiled_with_tpu
is_compiled_with_xpu = is_compiled_with_tpu
is_compiled_with_custom_device = lambda _name="tpu": is_compiled_with_tpu()


def device_count() -> int:
    return len(_accelerators()) or len(jax.devices())


def set_device(device) -> Place:
    """`paddle.set_device('tpu')` equivalent. Accepts 'cpu', 'tpu', 'tpu:N',
    Place, or the reference spellings 'gpu'/'xpu' (mapped to tpu)."""
    global _current_place
    if isinstance(device, Place):
        _current_place = device
        return _current_place
    dev = device.lower()
    if ":" in dev:
        kind, _, idx = dev.partition(":")
        idx = int(idx)
    else:
        kind, idx = dev, 0
    if kind in ("tpu", "gpu", "xpu", "cuda", "npu", "mlu", "custom_device"):
        accel = _accelerators()
        if not accel:
            raise RuntimeError(
                f"set_device('{device}'): no TPU in this process (JAX "
                f"platform is {jax.devices()[0].platform!r})")
        if idx >= len(accel):
            raise ValueError(f"device index {idx} out of range")
        _current_place = Place("tpu", idx)
    elif kind == "cpu":
        _current_place = Place("cpu", 0)
    else:
        raise ValueError(f"unknown device {device!r}")
    return _current_place


def get_device() -> str:
    p = current_place()
    return f"{p.device_type}:{p.device_id}" if p.device_type != "cpu" else "cpu"


def current_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = Place("tpu", 0) if _accelerators() else Place("cpu", 0)
    return _current_place


def jax_device(place: Place | None = None):
    """The jax.Device backing a Place."""
    p = place or current_place()
    accel = _accelerators()
    if p.device_type == "tpu" and accel:
        return accel[p.device_id]
    return jax.devices("cpu")[0] if accel else jax.devices()[0]


def place_of(array) -> Place:
    """Place of a jax.Array (sharded arrays report their first device)."""
    try:
        dev = next(iter(array.devices()))
    except Exception:
        return current_place()
    if dev.platform == "cpu":
        return Place("cpu", 0)
    return Place("tpu", dev.id)
