"""Persistent AOT executable artifacts — the on-disk half of the
serving executor caches (ISSUE 14).

Every serving-tier executable in this repo is built the same way:
``jax.jit(...).lower(...).compile()`` — full ahead-of-time compilation
in the arXiv:1810.09868 stance. That makes the compiled artifact itself
a cacheable object: ``jax.experimental.serialize_executable`` hands back
the PJRT executable's serialized form plus its arg/result pytrees, and
deserializing it later loads a ready-to-run executable **without
touching the XLA compiler** (proven: zero ``backend_compile`` monitoring
events through deserialize + execute — the recompile watchdog stays
silent). A serving replica therefore warms from disk in deserialize
time (milliseconds per executable) instead of compile time (seconds to
minutes per bucket): the TF-Serving servable-version lifecycle
(arXiv:1605.08695) applied to the compiled artifact, not just the
weights.

The store is keyed in two layers:

* the **logical key** names what the executable is for — model,
  component (``bucket`` / ``join`` / ``decode``), bucket size, feature
  signature, dtype — and is hashed into the artifact's filename;
* the **guard fingerprint** names what the artifact is only valid
  under — jax/jaxlib versions, backend, device kind/count/topology,
  the model's parameter-spec fingerprint, donation mode — and is
  checked field-by-field at load. Any mismatch **refuses** the
  artifact (counted + logged, never deserialized into a wrong-topology
  or wrong-compiler executable) and the caller falls back to
  compile-and-repersist.

Writes are atomic (`.tmp` + fsync + rename, the PR 6 checkpoint
discipline) so a killed replica can never leave a torn artifact that a
later replica would trust.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
from typing import Any, Dict, Iterator, Optional, Tuple

logger = logging.getLogger("mxtpu.serving")

__all__ = ["ArtifactStore", "environment_fingerprint",
           "params_fingerprint"]

#: bump when the on-disk pickle layout changes — old files are refused
SCHEMA_VERSION = 2

_SUFFIX = ".mxart"


def environment_fingerprint() -> Dict[str, Any]:
    """The compiler/topology half of the guard: a serialized executable
    embeds device assignments and backend codegen, so it is only valid
    on the same jaxlib + backend + device kind + device/process count
    it was compiled for."""
    import jax
    import jaxlib

    devs = jax.devices()
    return {
        "schema": SCHEMA_VERSION,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": devs[0].device_kind if devs else "?",
        "device_count": jax.device_count(),
        "process_count": jax.process_count(),
    }


def params_fingerprint(params) -> str:
    """Structural fingerprint of a parameter list: ordered shapes +
    dtypes + the order of each device array's layout (an executable
    lowered for a row-major table is no executable for the default
    column-major one). Identifies the *program signature*, not the
    weight values — a hot weight swap keeps the fingerprint (and the
    executables); an architecture change breaks it. Callers whose
    architectures can collide on param specs disambiguate with a
    ``model_version`` tag."""
    h = hashlib.sha256()
    for p in params:
        h.update(repr(tuple(int(d) for d in p.shape)).encode())
        h.update(str(getattr(p.dtype, "name", p.dtype)).encode())
        layout = getattr(getattr(p, "format", None), "layout", None)
        h.update(repr(getattr(layout, "major_to_minor", None)).encode())
    return h.hexdigest()[:16]


def _execution_device_ids(compiled) -> list:
    """Ids of the devices ``compiled`` runs on, in assignment order —
    stored beside the artifact so a load targets the same devices."""
    import jax

    sharding = jax.tree_util.tree_leaves(compiled.output_shardings)[0]
    return [int(d.id) for d in sharding._device_assignment]


def _key_hash(logical: Dict[str, Any]) -> str:
    payload = repr(sorted((k, repr(v)) for k, v in logical.items()))
    return hashlib.sha1(payload.encode()).hexdigest()[:20]


class ArtifactStore:
    """One directory of serialized executables, ``<root>/<model>/
    <logical-key-hash>.mxart`` — shared safely by every cache in a
    process (and by independent replica processes: loads are read-only,
    saves are atomic renames)."""

    def __init__(self, root: str):
        self.root = str(root)
        self._lock = threading.Lock()

    def _model_dir(self, model: str) -> str:
        # model names come from user-facing server names; keep the path
        # component safe without being clever
        safe = "".join(c if (c.isalnum() or c in "._-") else "_"
                       for c in str(model)) or "model"
        return os.path.join(self.root, safe)

    def path_for(self, model: str, logical: Dict[str, Any]) -> str:
        return os.path.join(self._model_dir(model),
                            _key_hash(logical) + _SUFFIX)

    # -- save ---------------------------------------------------------------
    def save(self, model: str, logical: Dict[str, Any],
             guard: Dict[str, Any], compiled) -> str:
        """Serialize ``compiled`` under (model, logical) with ``guard``
        recorded for load-time verification. Atomic: a crash mid-write
        leaves at most a ``.tmp`` the next save overwrites."""
        from jax.experimental.serialize_executable import serialize

        payload = serialize(compiled)
        blob = pickle.dumps({"schema": SCHEMA_VERSION,
                             "logical": dict(logical),
                             "guard": dict(guard),
                             "devices": _execution_device_ids(compiled),
                             "artifact": payload},
                            protocol=pickle.HIGHEST_PROTOCOL)
        path = self.path_for(model, logical)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # unique scratch name: the store is shared by independent
        # replica processes (the lock only covers this one), and two
        # replicas cold-booting the same key must not interleave writes
        # into one tmp file and rename a torn blob into place
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        with self._lock:
            try:
                with open(tmp, "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        return path

    # -- load ---------------------------------------------------------------
    def load(self, model: str, logical: Dict[str, Any],
             guard: Dict[str, Any]) -> Tuple[Optional[Any], str]:
        """The executable for (model, logical), or ``(None, reason)``.

        ``reason`` is ``"absent"`` (no artifact — a plain miss),
        ``"corrupt"`` (unreadable file), or ``"refused:<field>"`` (the
        artifact exists but its recorded guard disagrees on ``<field>``
        — wrong jaxlib, wrong backend, wrong topology, wrong model
        fingerprint). A refused artifact is NEVER deserialized."""
        path = self.path_for(model, logical)
        record = self._read(path)
        if record is None:
            return None, "absent" if not os.path.exists(path) else "corrupt"
        ex, reason = self._deserialize_checked(record, logical, guard)
        if ex is None and reason.startswith("refused"):
            logger.warning(
                "artifact %s refused (%s): recompiling — a stale "
                "artifact is never loaded into a mismatched "
                "compiler/topology", path, reason)
        return ex, reason

    def load_all(self, model: str,
                 guard: Dict[str, Any]) -> Iterator[Tuple[Dict, Any]]:
        """Yield ``(logical, executable)`` for every artifact of
        ``model`` whose guard matches — the eager replica-warm-start
        scan (no need to know the feature signatures in advance).
        Refused/corrupt entries are skipped (logged), not raised."""
        d = self._model_dir(model)
        if not os.path.isdir(d):
            return
        for fn in sorted(os.listdir(d)):
            if not fn.endswith(_SUFFIX):
                continue
            record = self._read(os.path.join(d, fn))
            if record is None:
                continue
            logical = record.get("logical", {})
            ex, reason = self._deserialize_checked(record, logical, guard)
            if ex is None:
                logger.warning("artifact %s skipped (%s)",
                               os.path.join(d, fn), reason)
                continue
            yield logical, ex

    # -- internals ----------------------------------------------------------
    @staticmethod
    def _read(path: str) -> Optional[Dict]:
        try:
            with open(path, "rb") as f:
                record = pickle.load(f)
            if not isinstance(record, dict) or "artifact" not in record:
                return None
            return record
        except (OSError, pickle.UnpicklingError, EOFError, ValueError,
                AttributeError, ImportError):
            return None

    @staticmethod
    def _deserialize_checked(record: Dict, logical: Dict,
                             guard: Dict) -> Tuple[Optional[Any], str]:
        if record.get("schema") != SCHEMA_VERSION:
            return None, "refused:schema"
        if record.get("logical") != dict(logical):
            # a filename-hash collision or a hand-moved file: the
            # stored logical identity is authoritative
            return None, "refused:logical"
        stored = record.get("guard", {})
        want = dict(guard)
        for field in sorted(set(stored) | set(want)):
            if stored.get(field) != want.get(field):
                return None, f"refused:{field}"
        try:
            import jax
            from jax.experimental.serialize_executable import (
                deserialize_and_load)

            # load onto exactly the devices the executable was compiled
            # for: the default is every device of the backend, which a
            # one-device executable on a multi-device host cannot run on
            by_id = {d.id: d for d in jax.devices()}
            devices = [by_id[i] for i in record["devices"]]
            return deserialize_and_load(
                *record["artifact"], execution_devices=devices), "ok"
        except Exception as e:   # noqa: BLE001 — fall back to compile
            return None, f"corrupt:{type(e).__name__}"
