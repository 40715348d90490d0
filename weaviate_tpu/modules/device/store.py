"""Candidate token planes: the HBM residency of the device rerank tier.

Fused rerank gathers each candidate's token set INSIDE the search
program, so the token sets must live in HBM as doc-id-addressed planes:
``tokens [cap, T, D]`` bfloat16 + ``mask [cap, T]`` bool — ONE layout and
ONE dtype, on the host and on the device. bfloat16 because the rerank
product's operands are bfloat16 (float32 accumulation): keeping more on
the planes would double their rent for bits no program reads. ``T`` is
``max_tokens`` rounded up to a multiple of 16 (the bfloat16 sublane tile;
180 -> 192), not to a power of two.

The host copy is authoritative (writes land there first), which makes the
host fallback tier and tiering demotion free: dropping the device planes
loses nothing. The device mirror is allocated ON the device (zeros, no
upload) and fed by row: before a search the rows written since the last one
are scattered in, a bounded chunk at a time, by a program that DONATES the
planes — no whole-plane copy on the host, over the wire or in HBM. Because
a feed donates them, a reader takes the planes under ``planes()`` and keeps
it open until its program is enqueued: readers share the planes with one
another, and a feed waits until the last of them has left (many readers or
one feeder; a search-only stretch takes the lock for a counter, twice).

Mesh mode row-shards the planes along the same shard axis as every
other HBM plane (``capacity`` tracks the backend's
``device_plane_capacity`` via ``cap_fn`` so the beam's local candidate
ids index the local token block directly).

Tiering: the planes pay HBM rent like code planes do — ``nbytes`` feeds
the index's ledger total, ``drop_device``/``planes`` are the
demote/promote legs (``TieredResidency`` semantics: demotion releases
HBM, the next hot search feeds the live rows back at identical shapes so
compiled rerank programs keep hitting their cache).
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Callable, Optional

import ml_dtypes
import numpy as np

from weaviate_tpu.monitoring.tracing import TRACER

TOKEN_DTYPE = ml_dtypes.bfloat16
_TILE = 16                  # bfloat16 rows a sublane tile
_FEED_BYTES = 32 << 20      # one scatter's payload, at most
_GATE_TICK_S = 0.05         # a waiting reader looks again this often


def _token_width(n: int) -> int:
    return _TILE * max(1, -(-n // _TILE))


@functools.cache
def _scatter_rows():
    import jax

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def scatter(tokens, mask, idx, rows, mrows):
        return tokens.at[idx].set(rows), mask.at[idx].set(mrows)

    return scatter


class CandidateTokenStore:
    def __init__(self, dims: int, max_tokens: int = 8,
                 cap_fn: Optional[Callable[[], int]] = None,
                 mesh=None, initial_capacity: int = 1024):
        self.dims = dims
        self.tmax = _token_width(max_tokens)
        self.cap_fn = cap_fn
        self.mesh = mesh
        cap = self._target_capacity(initial_capacity)
        self._tokens = np.zeros((cap, self.tmax, dims), TOKEN_DTYPE)
        self._mask = np.zeros((cap, self.tmax), bool)
        self._dev: Optional[tuple] = None
        # rows whose device copy is behind the host's
        self._dirty = np.zeros(cap, bool)
        # the host planes, the dirty rows and the mirror's handle; readers
        # of the mirror only count themselves in and out under it
        self._lock = threading.RLock()
        self._gate = threading.Condition(self._lock)
        self._readers = 0

    # -- host-authoritative writes ---------------------------------------
    def _target_capacity(self, need: int) -> int:
        cap = max(1024, need)
        if self.cap_fn is not None:
            # align to the backend's device plane so ids (and, on a
            # mesh, LOCAL block offsets) index both the same way
            cap = max(cap, int(self.cap_fn()))
        if self.mesh is not None:
            from weaviate_tpu.parallel.mesh import mesh_size

            n = mesh_size(self.mesh)
            cap = ((cap + n - 1) // n) * n
        return cap

    def _ensure(self, need_rows: int, need_tokens: int) -> None:
        cap = self._target_capacity(need_rows)
        tmax = max(self.tmax, _token_width(need_tokens))
        if cap <= self._tokens.shape[0] and tmax == self.tmax:
            return
        cap = max(cap, self._tokens.shape[0])
        grown_t = np.zeros((cap, tmax, self.dims), TOKEN_DTYPE)
        grown_m = np.zeros((cap, tmax), bool)
        # the rows ever written, not the untouched (unbacked) tail
        old = self._live_rows()
        grown_t[:old, : self.tmax] = self._tokens[:old]
        grown_m[:old, : self.tmax] = self._mask[:old]
        self._tokens, self._mask, self.tmax = grown_t, grown_m, tmax
        # shape moved: the mirror is made anew and fed the live rows
        self._dev = None
        self._dirty = np.zeros(cap, bool)

    def _live_rows(self) -> int:
        live = np.flatnonzero(self._mask[:, 0])
        return int(live[-1]) + 1 if len(live) else 0

    def put(self, doc_ids: np.ndarray, token_sets) -> None:
        doc_ids = np.asarray(doc_ids, np.int64).reshape(-1)
        if len(doc_ids) == 0:
            return
        uniform = isinstance(token_sets, np.ndarray) and token_sets.ndim == 3
        if not uniform:
            token_sets = [np.atleast_2d(np.asarray(t)) for t in token_sets]
        width = token_sets.shape[1] if uniform \
            else max(t.shape[0] for t in token_sets)
        with self._lock:
            self._ensure(int(doc_ids.max()) + 1, width)
            if uniform:
                # [m, T, D] block (bulk loads): one vectorized write
                self._tokens[doc_ids, :width] = token_sets
                self._tokens[doc_ids, width:] = 0
                self._mask[doc_ids, :width] = True
                self._mask[doc_ids, width:] = False
            else:
                for d, t in zip(doc_ids.tolist(), token_sets):
                    n = t.shape[0]
                    self._tokens[d, :n] = t
                    self._tokens[d, n:] = 0
                    self._mask[d, :n] = True
                    self._mask[d, n:] = False
            self._dirty[doc_ids] = True

    def delete(self, doc_ids: np.ndarray) -> None:
        with self._lock:
            ids = np.asarray(doc_ids, np.int64).reshape(-1)
            ids = ids[ids < self._tokens.shape[0]]
            self._mask[ids] = False
            self._dirty[ids] = True

    # -- reads ------------------------------------------------------------
    def host_planes(self) -> tuple[np.ndarray, np.ndarray]:
        """(tokens, mask) host arrays — the fallback tier's scoring
        source and the mirror's feed."""
        return self._tokens, self._mask

    @contextlib.contextmanager
    def planes(self, min_rows: int = 0):
        """``with store.planes(cap) as (tokens, mask):`` the device planes,
        up to date, for ONE dispatch. Leave the block once the program
        that reads them is enqueued (not finished): a feed donates these
        buffers, and a reference taken outside the block may name a buffer
        that is gone. Readers do not exclude one another. One that finds
        the mirror behind (rows written, no mirror yet, a plane to grow)
        waits until no reader holds the planes, feeds them and goes on as
        a reader; those arriving meanwhile wait with it. ``min_rows``: the
        caller's candidate-id space (e.g. the adjacency mirror's row
        count) — the plane must cover it or a clipped gather would read
        the wrong row's tokens."""
        with self._gate:
            while self._behind(min_rows):
                if not self._readers:
                    # graftlint: allow[blocking-under-lock] reason=the feed donates the planes, so it must shut readers out; it enqueues its scatters and waits for none
                    self._sync(min_rows)
                    break
                # the readers ahead leave once their programs are enqueued
                self._gate.wait(timeout=_GATE_TICK_S)
            dev = self._dev
            self._readers += 1
        try:
            yield dev
        finally:
            with self._gate:
                self._readers -= 1
                if not self._readers:
                    self._gate.notify_all()

    def _behind(self, min_rows: int) -> bool:
        return self._dev is None or self._dirty.any() or \
            self._target_capacity(max(1, min_rows)) > self._tokens.shape[0]

    def _sync(self, min_rows: int = 0) -> None:
        """Bring the mirror up to date; the caller holds the lock and no
        reader holds the planes. A mirror that is missing (first hot
        touch, a demotion, a shape change) is allocated on the device and
        every live row counts as behind; the rows behind are then
        scattered in, ``_FEED_BYTES`` at a time, each scatter donating the
        planes (mesh scatters stay sharded: the output takes the
        sharding the plane was placed with)."""
        import jax.numpy as jnp

        # the backend plane may have grown since the last write — track
        # it so beam candidate ids never index past the token plane
        self._ensure(max(1, min_rows), self.tmax)
        if self._dev is None:
            place = {}
            if self.mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P

                from weaviate_tpu.parallel.mesh import SHARD_AXIS

                place = {"tokens": NamedSharding(
                    self.mesh, P(SHARD_AXIS, None, None)),
                    "mask": NamedSharding(self.mesh, P(SHARD_AXIS, None))}
            self._dev = (
                jnp.zeros(self._tokens.shape, TOKEN_DTYPE,
                          device=place.get("tokens")),
                jnp.zeros(self._mask.shape, bool,
                          device=place.get("mask")))
            self._dirty[:] = self._mask.any(axis=1)
        idx = np.flatnonzero(self._dirty).astype(np.int32)
        if len(idx) == 0:
            return
        row_bytes = self.tmax * self.dims * self._tokens.itemsize
        with TRACER.child("mv.tokens_sync", dirty_rows=len(idx),
                          bytes=len(idx) * row_bytes):
            self._dirty[idx] = False
            # a scatter that fails has eaten the planes it was given
            planes, self._dev = self._dev, None
            step = 1 << max(3, (_FEED_BYTES // row_bytes).bit_length() - 1)
            for lo in range(0, len(idx), step):
                part = idx[lo:lo + step]
                # a power-of-two bucket a program; the pad repeats the
                # last row, which a scatter may write twice
                bucket = 1 << max(3, (len(part) - 1).bit_length())
                part = np.pad(part, (0, bucket - len(part)), mode="edge")
                planes = _scatter_rows()(
                    *planes, part, self._tokens[part], self._mask[part])
            self._dev = planes

    # -- tiered residency -------------------------------------------------
    @property
    def device_resident(self) -> bool:
        return self._dev is not None

    @property
    def nbytes(self) -> int:
        """HBM rent of the mirrored planes (0 while demoted)."""
        if self._dev is None:
            return 0
        return sum(a.nbytes for a in self._dev)

    @property
    def host_bytes(self) -> int:
        return self._tokens.nbytes + self._mask.nbytes

    def drop_device(self) -> int:
        """Release the planes from HBM (warm demotion); the host copy is
        authoritative, so nothing is lost. Returns bytes released."""
        with self._lock:
            freed = self.nbytes
            self._dev = None
            self._dirty[:] = False
            return freed

    # -- checkpoint -------------------------------------------------------
    def save(self, path: str) -> None:
        """Persist the written rows of the host planes as an atomic sidecar
        next to the owning index's checkpoint — a restored index must
        rerank against the SAME token sets it checkpointed, never empty
        masks. bfloat16 travels as its 16 bits."""
        import os

        tmp = path + ".rrtok.tmp.npz"
        with self._lock:
            n = self._live_rows()
            np.savez_compressed(tmp, tokens=self._tokens[:n].view(np.uint16),
                                mask=self._mask[:n])
        os.replace(tmp, path + ".rrtok.npz")

    def load(self, path: str) -> bool:
        """Restore the host planes from the sidecar; False when absent
        or corrupt (the caller treats the whole checkpoint as missing —
        half a checkpoint is no checkpoint)."""
        import os

        p = path + ".rrtok.npz"
        if not os.path.exists(p):
            return False
        try:
            with np.load(p) as z:
                tokens = z["tokens"]
                mask = z["mask"]
        except (OSError, ValueError, KeyError):
            return False
        if tokens.ndim != 3 or tokens.shape[2] != self.dims \
                or tokens.dtype != np.uint16 \
                or mask.shape != tokens.shape[:2]:
            return False
        if len(tokens):
            # put() marks the saved width's slots and clears the rest of a
            # wider plane's row; the saved mask then says which are tokens
            self.put(np.arange(len(tokens)), tokens.view(TOKEN_DTYPE))
            with self._lock:
                self._mask[: len(mask), : mask.shape[1]] = mask
        return True
