"""Device abstraction for heat_tpu.

API parity with the reference device module
(/root/reference/heat/core/devices.py: ``Device`` at devices.py:17, ``cpu``
singleton at :97, ``get_device``/``sanitize_device``/``use_device`` at
:137-190), redesigned for JAX: a ``Device`` names a *platform* whose devices
form the mesh, not a single rank-local accelerator. GPU round-robin
assignment by MPI rank (reference devices.py:114-120) has no analog — the
single controller owns every device of the platform.
"""

from __future__ import annotations

import jax

from typing import Any, Optional, Union

__all__ = [
    "Device",
    "complex_mode",
    "cpu",
    "get_device",
    "sanitize_device",
    "supports_complex",
    "use_complex",
    "use_device",
    "use_x64",
]


class Device:
    """A platform on which heat_tpu arrays live.

    Parameters
    ----------
    device_type : str
        Platform name: ``'cpu'``, ``'gpu'`` or ``'tpu'``.
    device_id : int
        Principal device index (kept for reference-API parity; the mesh
        spans all devices of the platform).
    jax_platform : str
        The JAX platform string backing this device.
    """

    def __init__(self, device_type: str, device_id: int = 0, jax_platform: Optional[str] = None):
        self.__device_type = str(device_type)
        self.__device_id = int(device_id)
        self.__jax_platform = jax_platform if jax_platform is not None else str(device_type)

    @property
    def device_type(self) -> str:
        return self.__device_type

    @property
    def device_id(self) -> int:
        return self.__device_id

    @property
    def jax_platform(self) -> str:
        return self.__jax_platform

    # reference-API name (devices.py:76 exposes torch_device)
    @property
    def torch_device(self) -> str:
        return f"{self.__jax_platform}:{self.__device_id}"

    def jax_devices(self):
        """All JAX devices of this platform (the mesh population)."""
        return jax.devices(self.__jax_platform)

    def __repr__(self) -> str:
        return f"device({str(self)!r})"

    def __str__(self) -> str:
        return f"{self.__device_type}:{self.__device_id}"

    def __eq__(self, other: Any) -> bool:
        if isinstance(other, Device):
            return self.device_type == other.device_type and self.device_id == other.device_id
        if isinstance(other, str):
            try:
                other = sanitize_device(other)
                return self == other
            except (ValueError, TypeError):
                return False
        return NotImplemented

    def __hash__(self):
        return hash(str(self))


cpu = Device("cpu", 0, "cpu")
"""The standard CPU device spanning all host devices."""

# populated lazily: probing platforms initializes the XLA backend, which
# must not happen at import time or jax.distributed.initialize (multi-host
# bootstrap, communication.init_distributed) can never run afterwards
_registry = {"cpu": cpu}
_detected = False
__default_device: Optional[Device] = None


# 64-bit (x64) policy. JAX's x64 flag is global and poisons TPU traces
# (the TPU compiler has no 64-bit arithmetic and SIGABRTs on some x64-mode
# lowerings, see linalg/_lapack.py), so the framework decides it PER
# PLATFORM at first backend use instead of blanket-enabling it at import:
# CPU/GPU get full float64/int64 parity with the reference; TPU runs with
# x64 off and 64-bit dtype requests degrade to 32-bit (types.degrade64).
# ``use_x64`` overrides explicitly.
_x64_choice: "Optional[bool]" = None


def use_x64(flag: "Optional[bool]" = None) -> bool:
    """Set (or, with ``flag=None``, query) the 64-bit dtype mode.

    ``use_x64(True)`` enables real float64/int64 arrays everywhere —
    including TPU, where 64-bit arithmetic is emulated and some linalg
    lowerings are fragile (safe_svd guards the known compiler bug).
    ``use_x64(False)`` degrades every 64-bit dtype request to its 32-bit
    counterpart (the TPU default). Returns the active mode.

    A pure query resolves the platform policy first, which initializes
    the backend — in a multi-host program, call ``init_distributed``
    BEFORE querying (the same ordering every backend-touching call has).
    An explicit set is recorded without touching the backend and
    overrides the platform policy whenever it is (or was) decided."""
    global _x64_choice
    if flag is not None:
        _x64_choice = bool(flag)
        _set_x64(_x64_choice)
    else:
        _ensure_detected()  # an undecided policy would report JAX's default
    return bool(jax.config.jax_enable_x64)


def _set_x64(enable: bool) -> None:
    from . import types as _types

    # No warnings-filter games: internal code never requests a 64-bit jax
    # dtype in degrade mode (it routes through types.index_jax_type /
    # wide_jax_type), so JAX's truncation warnings stay untouched for the
    # user's own calls (ADVICE r3: a process-global filter suppressed
    # them for ALL code in the process).
    jax.config.update("jax_enable_x64", bool(enable))
    _types._DEGRADE_64 = not enable


def _apply_x64_policy(backend: str) -> None:
    if _x64_choice is None:
        _set_x64(backend in ("cpu", "gpu"))


# Complex platform policy. The reference's complex surface
# (complex_math.py:1-110) works on every device class. Mirroring the x64
# policy above, the framework decides PER PLATFORM NAME and runs in one
# of three modes (``complex_mode``):
#   "native" — cpu/gpu default: ordinary complex jax arrays.
#   "planar" — default on tpu: complex DNDarrays store split
#              real/imaginary f32 planes and the documented complex
#              surface runs as plane arithmetic (core/complex_planar.py);
#              anything outside it raises the actionable policy error.
#   "refuse" — complex creation raises.
# ``use_complex(True)`` forces native, ``use_complex("planar")`` /
# ``use_complex(False)`` force planar / refuse (also on cpu, where the
# test suite exercises the planar behavior). Whether the installed TPU
# runtime executes native complex is what chip_smoke.py's dispatch phase
# reports (PERF.md "Bring-up"); the default here is not changed by it.
_complex_choice: "Optional[object]" = None


def use_complex(flag: "Optional[object]" = None) -> bool:
    """Set (or, with ``flag=None``, query) the complex-dtype policy.

    ``True`` forces native complex arrays, ``"planar"`` forces the planar
    (split real/imaginary plane) representation, ``False`` forces
    refusal at creation time, ``"auto"`` restores platform resolution
    (native on cpu/gpu, planar on tpu). Returns whether
    NATIVE complex is active; see ``complex_mode`` for the full mode."""
    global _complex_choice
    if flag is not None:
        if flag not in (True, False, "planar", "auto"):
            raise ValueError(f"use_complex expects True/False/'planar'/'auto', got {flag!r}")
        # normalize truthy/falsy ints (1/0, np.bool_) to real booleans so
        # complex_mode's identity checks see them
        if flag == "auto":
            _complex_choice = None
        elif flag == "planar":
            _complex_choice = "planar"
        else:
            _complex_choice = bool(flag)
    return supports_complex()


def complex_mode() -> str:
    """Active complex policy: ``"native"``, ``"planar"`` or ``"refuse"``
    (see the policy note above). Resolving the policy initializes the
    backend, like every platform policy here."""
    if _complex_choice is True:
        return "native"
    if _complex_choice is False:
        return "refuse"
    if _complex_choice == "planar":
        return "planar"
    _ensure_detected()
    return "native" if jax.default_backend() in ("cpu", "gpu") else "planar"


def supports_complex() -> bool:
    """Whether NATIVE complex arrays are allowed on the default backend
    (see ``use_complex``/``complex_mode``)."""
    return complex_mode() == "native"


def _ensure_detected() -> None:
    """Probe accelerator platforms and pick the default device, once, on
    first use (NOT at import — see note on ``_registry``). Also decides
    the platform's x64 policy (see ``use_x64``)."""
    global _detected, __default_device
    if _detected:
        return
    _detected = True
    for platform in ("tpu", "gpu"):
        try:
            devs = jax.devices(platform)
        except RuntimeError:
            continue
        if devs:
            _registry[platform] = Device(platform, 0, platform)
    # default device follows the default JAX backend (TPU when present);
    # a backend that fails to initialise raises here — it is never
    # replaced by a CPU world
    _backend = jax.default_backend()
    if __default_device is None:
        __default_device = _registry[_backend]
    # the x64 policy is about the BACKEND, not the chosen default device —
    # it must apply even when use_device() pre-set the default
    _apply_x64_policy(_backend)


def __getattr__(name: str):
    """Lazy ``tpu``/``gpu`` singletons (module attributes only exist when
    the platform does — reference-API parity — but probing is deferred)."""
    if name in ("tpu", "gpu"):
        _ensure_detected()
        if name in _registry:
            return _registry[name]
        raise AttributeError(f"no {name} platform available")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def get_device() -> Device:
    """The currently globally set default device (reference: devices.py:137)."""
    _ensure_detected()
    return __default_device


def sanitize_device(device: Optional[Union[str, Device]]) -> Device:
    """Sanitize a device or device identifier (reference: devices.py:149)."""
    if device is None:
        return get_device()
    if isinstance(device, Device):
        return device
    if isinstance(device, str):
        _ensure_detected()
        name = device.strip().lower()
        if ":" in name:
            name, _, idx = name.partition(":")
            try:
                int(idx)
            except ValueError:
                raise ValueError(f"unknown device {device}")
        if name in _registry:
            return _registry[name]
        if name in ("cuda",):
            if "gpu" in _registry:
                return _registry["gpu"]
        raise ValueError(f"unknown device {device}")
    raise ValueError(f"unknown device {device}")


def use_device(device: Optional[Union[str, Device]] = None) -> None:
    """Set the globally used default device (reference: devices.py:171)."""
    global __default_device
    __default_device = sanitize_device(device)
