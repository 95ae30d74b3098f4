"""Places: device selection.

Reference analogue: platform::Place variant (place.h:79). The north star
(BASELINE.json) asks for an XLAPlace alongside CPUPlace; TPUPlace is an alias
of XLAPlace bound to the TPU backend. A Place resolves to a concrete
jax.Device; the Executor uses it for jit backend selection and host->device
transfer of feeds.
"""
from __future__ import annotations

import jax


class Place:
    device_kind = "cpu"

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"

    def jax_device(self):
        # local_devices, not devices: in a multi-process (multi-host)
        # job jax.devices() lists every process's chips, and pinning
        # the single-device executor to another process's device makes
        # its outputs unfetchable from this one
        devs = jax.local_devices(backend=self.backend()) \
            if self.backend() else jax.local_devices()
        return devs[self.device_id]

    def backend(self):
        return None

    def is_cpu_place(self):
        return isinstance(self, CPUPlace)

    def is_xla_place(self):
        return isinstance(self, XLAPlace)


class CPUPlace(Place):
    device_kind = "cpu"

    def backend(self):
        return "cpu"


class XLAPlace(Place):
    """First-class accelerator place: whatever jax's default backend is."""
    device_kind = "xla"

    def backend(self):
        return None


class TPUPlace(XLAPlace):
    device_kind = "tpu"


# Compat alias: reference code says CUDAPlace; on this framework it means
# "the accelerator" (place.h:26 CUDAPlace -> XLAPlace per BASELINE.json).
CUDAPlace = XLAPlace
CUDAPinnedPlace = CPUPlace


def default_place() -> Place:
    """The place of jax's default backend. A backend that cannot be
    initialised raises here; it is never replaced by the CPU."""
    kind = jax.devices()[0].platform
    return CPUPlace() if kind == "cpu" else XLAPlace()
