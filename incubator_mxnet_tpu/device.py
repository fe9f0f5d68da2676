"""Device / Context system.

Capability parity with reference ``python/mxnet/context.py`` (2.x
``device.py``): ``Context(device_type, device_id)`` objects, a thread-local
default-context stack usable as a ``with`` block, and helpers ``cpu()``,
``gpu()``, ``num_gpus()``.

TPU-native redesign: a ``Context`` maps onto a concrete ``jax.Device``.
``tpu()`` is first-class (the BASELINE.json north star: ``mx.tpu()`` alongside
``mx.gpu()``); ``gpu()`` is accepted as an alias for the accelerator so that
reference scripts written against ``mx.gpu()`` run unchanged on a TPU chip.
Unlike the reference there is no per-device worker thread pool — PJRT gives
every device an async stream already (SURVEY.md §3.1 "TPU mapping").
"""

from __future__ import annotations

import threading
from typing import List, Optional


class Context:
    """A device context. Compare reference ``mxnet.context.Context``."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_stack = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in self.devstr2type:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = device_id

    # -- identity ----------------------------------------------------------
    def __eq__(self, other) -> bool:
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self) -> int:
        return hash((self.device_type, self.device_id))

    def __repr__(self) -> str:
        return f"{self.device_type}({self.device_id})"

    # -- jax binding -------------------------------------------------------
    @property
    def kind(self) -> str:
        """Normalized backend kind: 'cpu' or accelerator ('tpu')."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            return "cpu"
        return "tpu"  # gpu is an alias for the accelerator on this stack

    def jax_device(self):
        """Resolve to a concrete jax.Device (lazy; raises if id out of range)."""
        import jax

        devs = _accelerator_devices() if self.kind == "tpu" else _cpu_devices()
        if not devs:
            if self.kind == "tpu":
                raise RuntimeError(
                    "no accelerator devices visible to jax "
                    f"(jax.devices() is {jax.devices()}): "
                    f"{self} cannot be resolved")
            raise RuntimeError("no cpu devices visible to jax")
        if self.device_id >= len(devs):
            raise ValueError(
                f"device_id {self.device_id} out of range for "
                f"{self.device_type} ({len(devs)} devices)")
        return devs[self.device_id]

    # -- default-context stack --------------------------------------------
    @classmethod
    def _stack(cls) -> List["Context"]:
        if not hasattr(cls._default_stack, "stack"):
            cls._default_stack.stack = []
        return cls._default_stack.stack

    def __enter__(self) -> "Context":
        self._stack().append(self)
        return self

    def __exit__(self, *exc) -> None:
        self._stack().pop()

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = cls._stack()
        return stack[-1] if stack else cpu()


Device = Context  # 2.x rename alias


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def cpu_shared(device_id: int = 0) -> Context:
    return Context("cpu_shared", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias context for the accelerator (reference scripts use mx.gpu())."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """The north-star context: mx.tpu() (BASELINE.json)."""
    return Context("tpu", device_id)


def current_context() -> Context:
    return Context.default_ctx()


def _accelerator_devices():
    import jax

    try:
        devs = [d for d in jax.devices() if d.platform != "cpu"]
    except RuntimeError:
        return []
    return devs


def _cpu_devices():
    import jax

    try:
        return jax.devices("cpu")
    except RuntimeError:
        # cpu backend always exists in practice; be defensive anyway
        return [d for d in jax.devices() if d.platform == "cpu"]


def num_gpus() -> int:
    """Number of accelerator devices (reference ``mx.context.num_gpus``)."""
    return len(_accelerator_devices())


def num_tpus() -> int:
    return len(_accelerator_devices())


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes for the accelerator, best-effort.

    Reference ``mx.context.gpu_memory_info`` wraps cudaMemGetInfo; PJRT
    exposes per-device stats where the plugin supports them.
    """
    dev = tpu(device_id).jax_device()
    try:
        stats = dev.memory_stats()
        total = stats.get("bytes_limit", 0)
        in_use = stats.get("bytes_in_use", 0)
        return (total - in_use, total)
    except Exception:
        return (0, 0)
