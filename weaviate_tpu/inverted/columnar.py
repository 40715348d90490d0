"""Columnar filterable-property index: vectorized predicate -> allow mask.

Reference: ``adapters/repos/db/inverted/searcher.go`` builds roaring-bitmap
AllowLists from LSM roaringset buckets (``roaringset/``, 5.6k LoC of
serialized bitmap layers). The TPU-native equivalent keeps per-property
COLUMNS instead of per-doc dicts:

- numeric values  -> a dense doc-id-aligned float64 column (NaN = absent);
  a range clause is ONE numpy comparison over the column (SIMD), no
  gather/scatter. Extra values of multi-valued docs go to a small overflow
  (id, value) pair of arrays.
- discrete values (strings/bools) -> a term dictionary value -> id-array
  (sorted, deduped lazily). Equal is one dict hit; Like/ordering ops scan
  the *vocabulary* (tiny) and union the matching id arrays.
- geo points -> (doc_id, lat, lon) columns; WithinGeoRange is a vectorized
  haversine.
- presence / multi-valuedness / liveness -> dense bool bitmaps.

Every leaf evaluates to the dense bool mask the TPU kernels consume as
``allow_mask`` (``helpers/allow_list.go`` analogue). Deletions flip the live
bitmap; doc ids are never reused (shard counter is monotonic), so stale
column entries of dead docs are masked out, not purged.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import numpy as np


class _DenseBool:
    """Growable doc-id-aligned bitmap."""

    __slots__ = ("_arr",)

    def __init__(self, cap: int = 1024):
        self._arr = np.zeros(cap, bool)

    def _ensure(self, doc_id: int) -> None:
        if doc_id >= len(self._arr):
            n = len(self._arr)
            while n <= doc_id:
                n *= 2
            grown = np.zeros(n, bool)
            grown[: len(self._arr)] = self._arr
            self._arr = grown

    def set(self, doc_id: int, value: bool = True) -> None:
        self._ensure(doc_id)
        self._arr[doc_id] = value

    def set_many(self, doc_ids) -> None:
        """``set(d, True)`` for every id of a non-empty sequence."""
        ids = np.asarray(doc_ids)
        self._ensure(int(ids.max()))
        self._arr[ids] = True

    def get(self, doc_id: int) -> bool:
        return doc_id < len(self._arr) and bool(self._arr[doc_id])

    def mask(self, space: int) -> np.ndarray:
        m = np.zeros(space, bool)
        n = min(space, len(self._arr))
        m[:n] = self._arr[:n]
        return m


class _IdColumn:
    """Append-only doc-id array with amortized growth + lazy sort/dedup."""

    __slots__ = ("_arr", "_n", "_sorted")

    def __init__(self):
        self._arr = np.empty(16, np.int64)
        self._n = 0
        self._sorted = True

    def append(self, doc_id: int) -> None:
        if self._n == len(self._arr):
            grown = np.empty(len(self._arr) * 2, np.int64)
            grown[: self._n] = self._arr
            self._arr = grown
        if self._sorted and self._n and doc_id < self._arr[self._n - 1]:
            self._sorted = False
        self._arr[self._n] = doc_id
        self._n += 1

    def ids(self) -> np.ndarray:
        """Sorted unique view (dedup keeps re-added docs single)."""
        if not self._sorted:
            u = np.unique(self._arr[: self._n])
            self._arr = u
            self._n = len(u)
            self._sorted = True
        return self._arr[: self._n]

    def __len__(self) -> int:
        return self._n


class _DenseNum:
    """Doc-id-aligned float64 column; NaN marks 'no value'. Multi-valued
    docs park extra values in the overflow arrays (rare path)."""

    __slots__ = ("_vals", "_of_ids", "_of_vals", "_of_n")

    def __init__(self, cap: int = 1024):
        self._vals = np.full(cap, np.nan)
        self._of_ids = np.empty(8, np.int64)
        self._of_vals = np.empty(8, np.float64)
        self._of_n = 0

    def append(self, doc_id: int, val: float) -> None:
        if doc_id >= len(self._vals):
            n = len(self._vals)
            while n <= doc_id:
                n *= 2
            grown = np.full(n, np.nan)
            grown[: len(self._vals)] = self._vals
            self._vals = grown
        if math.isnan(self._vals[doc_id]):
            self._vals[doc_id] = val
            return
        if self._of_n == len(self._of_ids):
            ni = np.empty(self._of_n * 2, np.int64)
            nv = np.empty(self._of_n * 2, np.float64)
            ni[: self._of_n] = self._of_ids
            nv[: self._of_n] = self._of_vals
            self._of_ids, self._of_vals = ni, nv
        self._of_ids[self._of_n] = doc_id
        self._of_vals[self._of_n] = val
        self._of_n += 1

    def compare_mask(self, op, space: int) -> np.ndarray:
        """op: ufunc-style callable on an array -> bool array. NaN always
        compares False, so absent docs never match."""
        m = np.zeros(space, bool)
        n = min(space, len(self._vals))
        with np.errstate(invalid="ignore"):
            m[:n] = op(self._vals[:n])
            if self._of_n:
                ids = self._of_ids[: self._of_n]
                sel = op(self._of_vals[: self._of_n])
                ids = ids[sel & (ids < space)]
                m[ids] = True
        return m


class _GeoColumn:
    __slots__ = ("_ids", "_lat", "_lon", "_n")

    def __init__(self):
        self._ids = np.empty(16, np.int64)
        self._lat = np.empty(16, np.float64)
        self._lon = np.empty(16, np.float64)
        self._n = 0

    def append(self, doc_id: int, lat: float, lon: float) -> None:
        if self._n == len(self._ids):
            self._ids = np.concatenate([self._ids, np.empty_like(self._ids)])
            self._lat = np.concatenate([self._lat, np.empty_like(self._lat)])
            self._lon = np.concatenate([self._lon, np.empty_like(self._lon)])
        self._ids[self._n] = doc_id
        self._lat[self._n] = lat
        self._lon[self._n] = lon
        self._n += 1

    def view(self):
        return (self._ids[: self._n], self._lat[: self._n],
                self._lon[: self._n])


class PropColumn:
    """All column families for one property."""

    __slots__ = ("num", "terms", "geo", "present", "multi")

    def __init__(self):
        self.num = _DenseNum()
        self.terms: dict[Any, _IdColumn] = {}
        self.geo = _GeoColumn()
        self.present = _DenseBool()
        self.multi = _DenseBool()  # docs that carried >= 2 values

    def add_value(self, doc_id: int, v: Any) -> None:
        if isinstance(v, (str, bool)):
            idc = self.terms.get(v)
            if idc is None:
                idc = self.terms[v] = _IdColumn()
            idc.append(doc_id)
        elif isinstance(v, (int, float)):
            self.num.append(doc_id, float(v))
        elif isinstance(v, dict) and "latitude" in v and "longitude" in v:
            self.geo.append(doc_id, float(v["latitude"]),
                            float(v["longitude"]))
        # other types (nested objects/refs) are not filterable columns


class ColumnarProps:
    """The per-shard filter engine: prop -> PropColumn + a live bitmap."""

    def __init__(self):
        self.props: dict[str, PropColumn] = {}
        self._live = _DenseBool()
        self._watermark = 0

    # -- maintenance ------------------------------------------------------
    def add(self, doc_id: int, properties: dict[str, Any]) -> None:
        self.mark_live((doc_id,))
        for prop, val in properties.items():
            if val is not None:
                self.add_many(prop, (doc_id,), (val,))

    def mark_live(self, doc_ids) -> None:
        """The docs of a write batch (a non-empty sequence) are live,
        whether or not they carry a filterable property."""
        self._live.set_many(doc_ids)
        self._watermark = max(self._watermark, max(doc_ids) + 1)

    def add_many(self, prop: str, doc_ids, values) -> None:
        """One property's column of a write batch: ``values[i]`` (a scalar
        or a list, never ``None``) is doc ``doc_ids[i]``'s value."""
        col = self.props.get(prop)
        if col is None:
            col = self.props[prop] = PropColumn()
        col.present.set_many(doc_ids)
        multi = []
        add_value = col.add_value
        for doc_id, val in zip(doc_ids, values):
            if isinstance(val, list):
                if len(val) > 1:
                    multi.append(doc_id)
                for v in val:
                    add_value(doc_id, v)
            else:
                add_value(doc_id, val)
        if multi:
            col.multi.set_many(multi)

    def delete(self, doc_id: int) -> None:
        self._live.set(doc_id, False)

    def live_mask(self, space: int) -> np.ndarray:
        return self._live.mask(space)

    # -- leaf evaluation --------------------------------------------------
    def _mask_from_ids(self, ids: np.ndarray, space: int) -> np.ndarray:
        m = np.zeros(space, bool)
        if len(ids):
            ids = ids[(ids >= 0) & (ids < space)]
            m[ids] = True
        m &= self.live_mask(space)
        return m

    def eval_leaf(self, op: str, prop: str, fv: Any,
                  space: int) -> Optional[np.ndarray]:
        """Vectorized leaf eval; None = unsupported operator.

        Semantics mirror the reference searcher: NotEqual only matches docs
        that HAVE the property; list values match if any element matches.
        """
        col = self.props.get(prop)
        if op == "IsNull":
            live = self.live_mask(space)
            has = (col.present.mask(space) & live
                   if col is not None else np.zeros(space, bool))
            return (live & ~has) if fv else has
        if col is None:
            return np.zeros(space, bool)

        if op == "Equal":
            return self._equal_mask(col, fv, space)
        if op == "NotEqual":
            # single-valued docs: present with a different value; docs with
            # >= 2 values always carry some value != fv (the [fv, fv]
            # duplicate-list edge is accepted)
            m = (col.present.mask(space) & self.live_mask(space)
                 & ~self._equal_mask(col, fv, space))
            return m | (col.multi.mask(space) & self.live_mask(space))
        if op in ("GreaterThan", "GreaterThanEqual", "LessThan",
                  "LessThanEqual"):
            return self._range_mask(col, op, fv, space)
        if op == "Like":
            from weaviate_tpu.inverted.filters import like_to_regex

            rx = like_to_regex(str(fv))
            m = np.zeros(space, bool)
            for val, idc in col.terms.items():
                if isinstance(val, str) and rx.match(val) is not None:
                    m |= self._mask_from_ids(idc.ids(), space)
            return m
        if op == "ContainsAny":
            wanted = fv if isinstance(fv, list) else [fv]
            m = np.zeros(space, bool)
            for w in wanted:
                m |= self._equal_mask(col, w, space)
            return m
        if op == "ContainsAll":
            wanted = fv if isinstance(fv, list) else [fv]
            if not wanted:
                return np.zeros(space, bool)
            m = self._equal_mask(col, wanted[0], space)
            for w in wanted[1:]:
                m &= self._equal_mask(col, w, space)
            return m
        if op == "WithinGeoRange":
            ids, lat, lon = col.geo.view()
            if len(ids) == 0:
                return np.zeros(space, bool)
            lat0 = float(fv["latitude"])
            lon0 = float(fv["longitude"])
            maxd = float(fv["distance"])
            d = _haversine_m(lat0, lon0, lat, lon)
            return self._mask_from_ids(ids[d <= maxd], space)
        return None

    def _equal_mask(self, col: PropColumn, fv: Any, space: int) -> np.ndarray:
        if isinstance(fv, (int, float)) and not isinstance(fv, bool):
            ref = float(fv)
            m = col.num.compare_mask(lambda v: v == ref, space)
            return m & self.live_mask(space)
        idc = col.terms.get(fv)
        if idc is None:
            return np.zeros(space, bool)
        return self._mask_from_ids(idc.ids(), space)

    def _range_mask(self, col: PropColumn, op: str, fv: Any,
                    space: int) -> np.ndarray:
        if isinstance(fv, (int, float)) and not isinstance(fv, bool):
            ref = float(fv)
            cmp = {
                "GreaterThan": lambda v: v > ref,
                "GreaterThanEqual": lambda v: v >= ref,
                "LessThan": lambda v: v < ref,
                "LessThanEqual": lambda v: v <= ref,
            }[op]
            return col.num.compare_mask(cmp, space) & self.live_mask(space)
        # non-numeric ordering (date/text): compare each DISTINCT value once
        m = np.zeros(space, bool)
        for val, idc in col.terms.items():
            if type(val) is not type(fv):
                continue
            if ((op == "GreaterThan" and val > fv)
                    or (op == "GreaterThanEqual" and val >= fv)
                    or (op == "LessThan" and val < fv)
                    or (op == "LessThanEqual" and val <= fv)):
                m |= self._mask_from_ids(idc.ids(), space)
        return m


def _haversine_m(lat0: float, lon0: float, lat: np.ndarray,
                 lon: np.ndarray) -> np.ndarray:
    """Vectorized haversine in meters (reference ``geo_spatial.go``)."""
    r = 6371088.0
    p0 = np.radians(lat0)
    p1 = np.radians(lat)
    dp = np.radians(lat - lat0)
    dl = np.radians(lon - lon0)
    a = np.sin(dp / 2.0) ** 2 + np.cos(p0) * np.cos(p1) * np.sin(dl / 2.0) ** 2
    return 2.0 * r * np.arcsin(np.sqrt(np.clip(a, 0.0, 1.0)))
