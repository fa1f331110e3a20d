"""Device registry & placement.

Reference parity: ``paddle.device.set_device()`` / ``Place`` over the
phi backends layer (paddle/phi/backends — device contexts, CustomDevice
plugin ABI).  On TPU the device runtime IS jax's own TPU backend
(platform ``"tpu"``, nothing else), so this layer is a thin registry that
maps paddle-style device strings ('tpu', 'tpu:0', 'cpu', 'xla') onto jax
devices and owns the session default placement.  One process drives all
chips of a host: a second process that asks for the TPU fails.  Memory
is owned by
XLA/PJRT — the reference's auto-growth allocator has no TPU analog to
reimplement, so allocator knobs are accepted and ignored (flags.py).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

from ..common.errors import InvalidArgumentError, enforce

__all__ = ["Place", "set_device", "get_device", "get_all_devices", "device_count", "is_compiled_with_tpu"]

_ALIAS = {"xla": "tpu", "gpu": "tpu", "cuda": "tpu"}  # everything accel maps to tpu


class Place:
    """A (device_type, device_id) pair, paddle.CPUPlace/CUDAPlace analog."""

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = _ALIAS.get(device_type, device_type)
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

    @property
    def jax_device(self) -> jax.Device:
        # ask for the platform by name: ``jax.devices()`` lists only the
        # default backend, and a TPU host still has its CPU
        try:
            devs = jax.devices(self.device_type)
        except RuntimeError as e:
            raise InvalidArgumentError(
                f"no {self.device_type!r} device on this machine "
                f"(jax: {e})") from None
        enforce(
            self.device_id < len(devs),
            f"device id {self.device_id} out of range for {self.device_type} "
            f"({len(devs)} present)",
        )
        return devs[self.device_id]


_state = threading.local()


def _parse(device: str) -> Place:
    device = device.lower()
    if ":" in device:
        kind, _, idx = device.partition(":")
        try:
            return Place(kind, int(idx))
        except ValueError:
            raise InvalidArgumentError(f"bad device string {device!r}")
    return Place(device, 0)


def set_device(device: str) -> Place:
    """paddle.device.set_device('tpu'|'cpu'|'xla'|'tpu:0')."""
    place = _parse(device)
    place.jax_device  # validate it exists
    _state.place = place
    return place


def get_device() -> str:
    place = getattr(_state, "place", None)
    if place is None:
        place = Place(jax.default_backend(), 0)
        _state.place = place
    return f"{place.device_type}:{place.device_id}"


def current_place() -> Place:
    get_device()
    return _state.place


def get_all_devices():
    return [f"{d.platform}:{i}" for i, d in enumerate(jax.devices())]


def device_count() -> int:
    return len(jax.devices())


def is_compiled_with_tpu() -> bool:
    try:
        return any(d.platform == "tpu" for d in jax.devices())
    except RuntimeError:
        return False
