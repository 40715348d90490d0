"""Binary object codec.

Reference: ``entities/storobj/storage_object.go:110`` (FromBinary) — a binary
envelope of header + UUID + vectors (LE float32) + named vectors + msgpack
properties, with partial-parse fast paths. We keep the same shape: msgpack
envelope with raw little-endian float32 vector payloads so vectors can be
extracted without decoding properties (``parse_single_object.go`` analogue).
"""

from __future__ import annotations

import time
import uuid as uuidlib
from typing import Any, Optional

import msgpack
import numpy as np

CODEC_VERSION = 1


class _StoredVector:
    """A vector as the envelope holds it: raw little-endian float32 bytes
    and the shape (``None`` = 1-D), not decoded until something reads it."""

    __slots__ = ("raw", "shape")

    def __init__(self, raw: bytes, shape):
        self.raw = raw
        self.shape = shape

    def array(self) -> np.ndarray:
        vec = np.frombuffer(self.raw, np.float32).copy()
        return vec.reshape(self.shape) if self.shape else vec


class StorageObject:
    """One stored object. ``vector`` and ``named_vectors`` of an object
    that came :meth:`from_bytes` stay the stored bytes until they are read
    (a search reply that does not include vectors never decodes one);
    readers and writers of the attributes see plain arrays either way."""

    _FIELDS = ("uuid", "collection", "properties", "vector", "named_vectors",
               "doc_id", "tenant", "creation_time_ms", "update_time_ms")

    def __init__(
        self,
        uuid: str,
        collection: str,
        properties: Optional[dict[str, Any]] = None,
        vector: Optional[np.ndarray] = None,
        named_vectors: Optional[dict[str, np.ndarray]] = None,
        doc_id: int = -1,
        tenant: str = "",
        creation_time_ms: int = 0,
        update_time_ms: int = 0,
    ):
        self.uuid = uuid or str(uuidlib.uuid4())
        self.collection = collection
        self.properties = {} if properties is None else properties
        # as given, or as stored until read (from_bytes): a _StoredVector /
        # the envelope's (nvecs, nvec_shapes) pair
        self._vector = vector
        self._named = {} if named_vectors is None else named_vectors
        self.doc_id = doc_id
        self.tenant = tenant
        if not (creation_time_ms and update_time_ms):
            # only an object that does not carry its times reads the clock
            now = int(time.time() * 1000)
            creation_time_ms = creation_time_ms or now
            update_time_ms = update_time_ms or now
        self.creation_time_ms = creation_time_ms
        self.update_time_ms = update_time_ms

    @property
    def vector(self) -> Optional[np.ndarray]:
        v = self._vector
        if type(v) is _StoredVector:
            v = self._vector = v.array()
        return v

    @vector.setter
    def vector(self, value) -> None:
        self._vector = value

    @property
    def named_vectors(self) -> dict[str, np.ndarray]:
        nv = self._named
        if type(nv) is tuple:
            raws, shapes = nv
            nv = self._named = {
                k: _StoredVector(raw, shapes[k]).array()
                for k, raw in raws.items()}
        return nv

    @named_vectors.setter
    def named_vectors(self, value) -> None:
        self._named = value

    def __repr__(self) -> str:
        return "StorageObject(" + ", ".join(
            f"{f}={getattr(self, f)!r}" for f in self._FIELDS) + ")"

    def __eq__(self, other):
        if other.__class__ is not self.__class__:
            return NotImplemented
        return tuple(getattr(self, f) for f in self._FIELDS) == \
            tuple(getattr(other, f) for f in self._FIELDS)

    __hash__ = None

    def to_bytes(self) -> bytes:
        vec = self._vector
        if type(vec) is _StoredVector:
            # never read since from_bytes: the stored bytes go back as is
            vec_raw, vec_shape = vec.raw, vec.shape
        elif vec is None:
            vec_raw = vec_shape = None
        else:
            arr = np.asarray(vec)
            vec_raw = np.asarray(arr, np.float32).tobytes()
            # shape for multi-vector ([T, D]) default vectors; absent/None
            # means 1-D (the overwhelmingly common case stays compact)
            vec_shape = None if arr.ndim == 1 else list(arr.shape)
        if type(self._named) is tuple:
            nvecs, nvec_shapes = self._named
        else:
            nvecs = {k: np.asarray(v, np.float32).tobytes()
                     for k, v in self._named.items()}
            nvec_shapes = {k: list(np.asarray(v).shape)
                           for k, v in self._named.items()}
        env = {
            "v": CODEC_VERSION,
            "uuid": self.uuid,
            "class": self.collection,
            "doc_id": self.doc_id,
            "tenant": self.tenant,
            "created": self.creation_time_ms,
            "updated": self.update_time_ms,
            "props": self.properties,
            "vec": vec_raw,
            "vec_shape": vec_shape,
            "nvecs": nvecs,
            "nvec_shapes": nvec_shapes,
        }
        return msgpack.packb(env, use_bin_type=True)

    @staticmethod
    def from_bytes(data: bytes) -> "StorageObject":
        env = msgpack.unpackb(data, raw=False)
        obj = StorageObject(
            uuid=env["uuid"],
            collection=env["class"],
            properties=env.get("props", {}),
            doc_id=env.get("doc_id", -1),
            tenant=env.get("tenant", ""),
            creation_time_ms=env.get("created", 0),
            update_time_ms=env.get("updated", 0),
        )
        vec = env.get("vec")
        if vec is not None:
            obj._vector = _StoredVector(vec, env.get("vec_shape"))
        nvecs = env.get("nvecs")
        if nvecs:
            obj._named = (nvecs, env.get("nvec_shapes", {}))
        return obj

    @staticmethod
    def extract_doc_id(data: bytes) -> int:
        """Partial parse: doc id only (reference parse_single_object.go)."""
        return msgpack.unpackb(data, raw=False).get("doc_id", -1)
