"""Model persistence: automatic blob serialization + user-managed models.

Reference: 3-mode persistence decided per-algo by
BaseAlgorithm.makePersistentModel (BaseAlgorithm.scala:96-112) —
(a) automatic Kryo blob into MODELDATA (CoreWorkflow.scala:73-79),
(b) user-managed PersistentModel.save + reflective loader
    (PersistentModel.scala:51,94; WorkflowUtils.getPersistentModel:352),
(c) Unit ⇒ retrain-on-deploy (Engine.scala:208-226).

Here (a) uses pickle (model leaves are numpy arrays — device arrays must
be pulled host-side by the algorithm before returning its model), (b) is a
`PersistentModel` subclass with save/load classmethod, (c) is a model of
`None` or a non-picklable model.

The blob of (a) is framed: a header (`_MAGIC`, a format version, the
lengths of the parts), then ONE protocol-5 pickle of the model list — the
skeleton — then the pickle's out-of-band buffers. numpy decides which
arrays go out of band (every C- or F-contiguous one, handed over as a
`PickleBuffer` with no copy); the rest stays in the skeleton. The list is
pickled once, so an object two models share is stored once and stays one
object after loading. A blob without the magic is a plain pickle, as
blobs stored before the framed format were, and loads as one. Loaded
arrays are writable and share no memory with the blob: each buffer is
copied into a `bytearray` of its own.
"""

from __future__ import annotations

import io
import os
import pickle
import struct
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np

from predictionio_tpu.core.base import PersistentModelManifest
from predictionio_tpu.controller.params import load_symbol
from predictionio_tpu.obs.registry import get_default_registry
from predictionio_tpu.utils.env import env_path

# no pickle starts with a NUL (a protocol >= 2 one starts with b"\x80")
_MAGIC = b"\x00PIOMDL\x00"
_VERSION = 1
# version, number of buffers; then one length a part, the skeleton first
_HEAD = struct.Struct("<II")

# numpy's reconstructor of an array pickled out of band, as numpy's own
# reduction names it
_NP_FROMBUFFER = np.zeros(1).__reduce_ex__(5)[0]

_SERIALIZED = get_default_registry().counter(
    "persist_serialize_total",
    "model lists serialized for MODELDATA, by path",
    labelnames=("path",),  # label-bound: literal one_pass|fallback
)


@dataclass(frozen=True)
class RetrainOnDeploy:
    """Marker stored for models that cannot/should not be serialized —
    deploy re-runs read→prepare→train (reference Engine.scala:208-226)."""

    algo_index: int


class PersistentModel:
    """User-managed persistence (reference PersistentModel.scala:51,94).

    Subclasses set PERSISTENT = True, implement `save` returning True when
    stored, and a `load(model_id, params)` classmethod."""

    PERSISTENT = True

    def save(self, model_id: str, params: Any) -> bool:
        raise NotImplementedError

    @classmethod
    def load(cls, model_id: str, params: Any) -> "PersistentModel":
        raise NotImplementedError


class LocalFileSystemPersistentModel(PersistentModel):
    """Pickle-to-PIO_FS_BASEDIR convenience base (reference
    LocalFileSystemPersistentModel.scala:40,57)."""

    @staticmethod
    def _path(model_id: str) -> str:
        base = env_path("PIO_FS_BASEDIR")
        os.makedirs(base, exist_ok=True)
        return os.path.join(base, f"pm-{model_id}.pkl")

    def save(self, model_id: str, params: Any) -> bool:
        with open(self._path(model_id), "wb") as f:
            pickle.dump(self, f)
        return True

    @classmethod
    def load(cls, model_id: str, params: Any):
        with open(cls._path(model_id), "rb") as f:
            return pickle.load(f)


def _framed(out: list[Any], attrs: Optional[dict]) -> bytes:
    """ONE protocol-5 pickle of `out`, its buffers out of band, framed
    into one exact-size bytes object in one copy."""
    buffers: list[pickle.PickleBuffer] = []
    skeleton = pickle.dumps(out, protocol=5, buffer_callback=buffers.append)
    raws = [b.raw() for b in buffers]
    sizes = [len(skeleton)] + [r.nbytes for r in raws]
    head = (_MAGIC + _HEAD.pack(_VERSION, len(raws))
            + struct.pack(f"<{len(sizes)}Q", *sizes))
    blob = b"".join([head, skeleton, *raws])
    if attrs is not None:
        attrs["bytes"] = len(blob)
        attrs["out_of_band_bytes"] = sum(sizes[1:])
        attrs["buffers"] = len(raws)
    return blob


def _discard(_buffer: pickle.PickleBuffer) -> None:
    """A buffer callback that keeps nothing: a probe pickles out of band,
    so it copies no array."""


def serialize_models(models: list[Any], attrs: Optional[dict] = None) -> bytes:
    """Pickle the per-algo model list for MODELDATA, in one pass. Only if
    that raises is each model tested alone: a non-picklable one degrades
    to a RetrainOnDeploy marker (reference mode (c)) and the list is
    pickled again. `attrs`, where given, gets `bytes`, `out_of_band_bytes`,
    `buffers` and `fallback` (0 or 1)."""
    out = [RetrainOnDeploy(algo_index=i) if m is None else m
           for i, m in enumerate(models)]
    try:
        blob = _framed(out, attrs)
        path = "one_pass"
    except Exception:
        for i, m in enumerate(out):
            if isinstance(m, (RetrainOnDeploy, PersistentModelManifest)):
                continue
            try:
                pickle.dumps(m, protocol=5, buffer_callback=_discard)
            except Exception:
                out[i] = RetrainOnDeploy(algo_index=i)
        blob = _framed(out, attrs)
        path = "fallback"
    if attrs is not None:
        attrs["fallback"] = int(path == "fallback")
    _SERIALIZED.inc(path=path)
    return blob


def _writable_frombuffer(buf: Any, *args: Any) -> np.ndarray:
    """numpy's reconstructor over the loader's own copy of the buffer: an
    array that was read-only when pickled comes back writable, as a plain
    pickle's copy does."""
    if isinstance(buf, memoryview) and isinstance(buf.obj, bytearray):
        buf = buf.obj
    return _NP_FROMBUFFER(buf, *args)


class _Unpickler(pickle.Unpickler):
    def find_class(self, module: str, name: str) -> Any:
        found = super().find_class(module, name)
        return _writable_frombuffer if found is _NP_FROMBUFFER else found


def deserialize_models(blob: bytes) -> list[Any]:
    """The model list of a blob `serialize_models` wrote, framed or — as
    blobs stored before the framed format are — one plain pickle."""
    view = memoryview(blob)
    if view[:len(_MAGIC)] != _MAGIC:
        return pickle.loads(blob)
    version, n = _HEAD.unpack_from(view, len(_MAGIC))
    if version != _VERSION:
        raise ValueError(f"model blob format {version} is not {_VERSION}")
    at = len(_MAGIC) + _HEAD.size
    sizes = struct.unpack_from(f"<{n + 1}Q", view, at)
    at += 8 * (n + 1)
    skeleton = view[at:at + sizes[0]]
    at += sizes[0]
    buffers = []
    for size in sizes[1:]:
        buffers.append(bytearray(view[at:at + size]))
        at += size
    return _Unpickler(io.BytesIO(skeleton), buffers=buffers).load()


def load_persistent_model(
    manifest: PersistentModelManifest, model_id: str, params: Any
) -> Any:
    """Reflectively re-load a user-persisted model (reference
    SparkWorkflowUtils.getPersistentModel, WorkflowUtils.scala:352)."""
    cls = load_symbol(manifest.class_name)
    loader: Optional[Any] = getattr(cls, "load", None)
    if loader is None:
        raise TypeError(f"{manifest.class_name} has no load() classmethod")
    return loader(model_id, params)
