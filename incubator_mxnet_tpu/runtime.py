"""Runtime feature introspection.

Capability parity with reference ``src/libinfo.cc`` + ``python/mxnet/runtime.py``
(``mx.runtime.feature_list()``, ``Features().is_enabled('CUDA')``): the build
flags become runtime-discovered properties of the jax install.
"""

from __future__ import annotations

import dataclasses
import os
from typing import List

#: the checkout's own compile cache, used where the environment names none
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    (``chip_smoke.py``, ``bench.py --config``, the ``benchmark/`` mains,
    ``tools/chaos_soak.py``) and return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at the fixed
    :data:`COMPILE_CACHE_DIR` — the directory is part of the cache key,
    so a path made from a temp name, pid or time would never hit twice.
    Called from entry points, never at package import: tests count
    ``backend_compile`` events and must compile for real."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


@dataclasses.dataclass(frozen=True)
class Feature:
    name: str
    enabled: bool

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    import jax

    feats = {}
    try:
        platforms = {d.platform for d in jax.devices()}
    except RuntimeError:
        platforms = set()
    feats["TPU"] = any(p not in ("cpu",) for p in platforms)
    feats["CPU"] = True
    feats["CUDA"] = "gpu" in platforms or "cuda" in platforms
    feats["XLA"] = True
    # compiled Pallas kernels need a real TPU backend (ops/pallas_attention);
    # on CPU the kernels still run via the Pallas interpreter
    feats["PALLAS"] = _pallas_available()
    feats["BF16"] = True
    feats["INT64_TENSOR_SIZE"] = jax.config.jax_enable_x64
    feats["DIST_KVSTORE"] = True      # jax.distributed-backed kvstore facade
    feats["SHARDED_CHECKPOINT"] = _has_module("orbax") or _has_module(
        "tensorstore")
    feats["PROFILER"] = True          # jax.profiler / XPlane
    feats["OPENCV"] = _has_module("cv2")
    feats["RECORDIO_NATIVE"] = _native_recordio_available()
    feats["AMP"] = True
    feats["SERVING"] = True           # mxtpu.serving (docs/SERVING.md)
    return feats


def _pallas_available() -> bool:
    from .ops.pallas_attention import pallas_available

    return pallas_available()


def _has_module(name: str) -> bool:
    import importlib.util

    return importlib.util.find_spec(name) is not None


def _native_recordio_available() -> bool:
    import os

    here = os.path.dirname(__file__)
    for n in ("libmxtpu_io.so",):
        if os.path.exists(os.path.join(here, "native", n)):
            return True
    return False


class Features(dict):
    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _detect().items()})

    def is_enabled(self, name: str) -> bool:
        f = self.get(name)
        return bool(f and f.enabled)


def feature_list() -> List[Feature]:
    return list(Features().values())
