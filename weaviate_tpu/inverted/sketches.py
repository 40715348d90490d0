"""Per-property selectivity sketches: row count, NDV, numeric min/max.

The cost-based planner (``weaviate_tpu/query/planner``) needs a cheap,
always-available answer to "what fraction of the corpus survives this
filter?" *before* materializing any allow mask. The reference gets this
from LSM segment metadata (per-segment key counts feeding the pre/post
filter switch); here every inverted index — RAM or segmented — maintains a
:class:`SketchRegistry` inline with its write path and persists it with the
segment flush / shard snapshot.

Sketch contents per property:

- ``rows``    — live docs carrying the property (exact, counter).
- ``NDV``     — distinct-value estimate via a KMV (k-minimum-values)
  sketch over 64-bit value hashes. Add-only: deletes decrement ``rows``
  but never shrink the KMV — NDV is an upper-ish bound, which is the safe
  direction for ``Equal`` selectivity (over-estimating distincts
  under-estimates selectivity, and the planner treats low selectivity
  conservatively).
- ``min/max`` — running numeric bounds (add-only, same caveat).

Estimation (:func:`estimate_selectivity`) walks the Filter AST with
textbook independence assumptions: And = product, Or =
inclusion-exclusion, Equal = (rows/N)/NDV, ranges = uniform interpolation
over [min, max]. These are *estimates* — the planner's plan types are all
recall-safe regardless, so a bad estimate costs latency, never
correctness.
"""

from __future__ import annotations

import hashlib
import heapq
import struct
from typing import Any, Mapping, Optional

from weaviate_tpu.inverted.filters import Filter

# KMV width: 256 hashes ≈ 6% NDV standard error — plenty for plan choice,
# 2 KB per property.
_KMV_K = 256
_HASH_SPACE = float(1 << 64)

# fallback selectivity when a property has no sketch (never observed a
# value): assume moderately selective rather than 1.0 so an unknown
# predicate still prefers a filtered plan over an unfiltered walk
_UNKNOWN_SELECTIVITY = 0.33


def _hash64(value: Any) -> int:
    """Stable 64-bit hash of a filterable scalar (str/num/bool)."""
    if isinstance(value, bool):
        raw = b"b1" if value else b"b0"
    elif isinstance(value, (int, float)):
        # ints and their float twins hash identically (5 == 5.0 in filters)
        raw = b"n" + struct.pack("<d", float(value))
    elif isinstance(value, str):
        raw = b"s" + value.encode("utf-8", "surrogatepass")
    else:
        raw = b"o" + repr(value).encode("utf-8", "backslashreplace")
    return int.from_bytes(hashlib.blake2b(raw, digest_size=8).digest(),
                          "little")


class PropertySketch:
    """Selectivity sketch for one property (see module doc)."""

    __slots__ = ("rows", "vmin", "vmax", "_kmv", "_kmv_set", "_exact")

    def __init__(self) -> None:
        self.rows = 0
        self.vmin: Optional[float] = None
        self.vmax: Optional[float] = None
        # max-heap (negated) of the K smallest hashes + membership set;
        # while len < K the set doubles as an exact distinct count
        self._kmv: list[int] = []
        self._kmv_set: set[int] = set()
        self._exact = True

    # -- writes -----------------------------------------------------------
    def add(self, value: Any) -> None:
        """Record one doc's value (scalar or list) for this property."""
        self.add_many((value,))

    def add_many(self, values) -> None:
        """Record the values of a write batch's docs, one a doc, in order."""
        self.rows += len(values)
        kmv, kmv_set = self._kmv, self._kmv_set
        for value in values:
            for v in (value if isinstance(value, list) else (value,)):
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    f = float(v)
                    if self.vmin is None or f < self.vmin:
                        self.vmin = f
                    if self.vmax is None or f > self.vmax:
                        self.vmax = f
                h = _hash64(v)
                if h in kmv_set:
                    continue
                if len(kmv) < _KMV_K:
                    heapq.heappush(kmv, -h)
                    kmv_set.add(h)
                elif h < -kmv[0]:
                    kmv_set.discard(-heapq.heappushpop(kmv, -h))
                    kmv_set.add(h)
                    self._exact = False
                else:
                    self._exact = False

    def remove(self) -> None:
        """One doc carrying the property was deleted (value-agnostic: the
        KMV is add-only, only ``rows`` shrinks)."""
        if self.rows > 0:
            self.rows -= 1

    # -- reads ------------------------------------------------------------
    def ndv(self) -> int:
        """Distinct-value estimate (exact while under the KMV width)."""
        n = len(self._kmv)
        if n == 0:
            return 0
        if self._exact or n < _KMV_K:
            return n
        kth = float(-self._kmv[0])  # largest of the K smallest
        if kth <= 0.0:
            return n
        return max(n, int((_KMV_K - 1) * _HASH_SPACE / kth))

    def to_dict(self) -> dict:
        return {
            "rows": self.rows,
            "min": self.vmin,
            "max": self.vmax,
            "kmv": sorted(-h for h in self._kmv),
            "exact": self._exact,
        }

    @staticmethod
    def from_dict(d: dict) -> "PropertySketch":
        sk = PropertySketch()
        sk.rows = int(d.get("rows", 0))
        sk.vmin = d.get("min")
        sk.vmax = d.get("max")
        for h in d.get("kmv", []):
            heapq.heappush(sk._kmv, -int(h))
            sk._kmv_set.add(int(h))
        sk._exact = bool(d.get("exact", True))
        return sk

    def summary(self) -> dict:
        """Small human-readable form for stats()/debug endpoints."""
        return {"rows": self.rows, "ndv": self.ndv(),
                "min": self.vmin, "max": self.vmax}


class SketchRegistry:
    """All property sketches of one shard's inverted index."""

    __slots__ = ("props",)

    def __init__(self) -> None:
        self.props: dict[str, PropertySketch] = {}

    def add(self, prop: str, value: Any) -> None:
        self.add_many(prop, (value,))

    def add_many(self, prop: str, values) -> None:
        sk = self.props.get(prop)
        if sk is None:
            sk = self.props[prop] = PropertySketch()
        sk.add_many(values)

    def remove(self, prop: str) -> None:
        sk = self.props.get(prop)
        if sk is not None:
            sk.remove()

    def to_dict(self) -> dict:
        return {p: sk.to_dict() for p, sk in self.props.items()}

    @staticmethod
    def from_dict(d: dict) -> "SketchRegistry":
        reg = SketchRegistry()
        for p, rec in (d or {}).items():
            reg.props[p] = PropertySketch.from_dict(rec)
        return reg

    def summary(self) -> dict:
        return {p: sk.summary() for p, sk in sorted(self.props.items())}


# -- estimation ------------------------------------------------------------

def _range_fraction(sk: PropertySketch, op: str, value: float) -> float:
    """Fraction of [min, max] selected by a comparison, assuming a uniform
    value distribution (the classic System-R interpolation)."""
    lo, hi = sk.vmin, sk.vmax
    if lo is None or hi is None:
        return _UNKNOWN_SELECTIVITY
    if hi <= lo:  # single-point domain
        hit = ((op in ("GreaterThanEqual", "LessThanEqual") and value == lo)
               or (op.startswith("Greater") and lo > value)
               or (op.startswith("Less") and lo < value))
        return 1.0 if hit else 0.0
    span = hi - lo
    if op in ("GreaterThan", "GreaterThanEqual"):
        frac = (hi - value) / span
    else:
        frac = (value - lo) / span
    return min(1.0, max(0.0, frac))


def _leaf_selectivity(flt: Filter,
                      sketches: Mapping[str, PropertySketch]) -> float:
    prop = flt.path[-1] if flt.path else None
    sk = sketches.get(prop) if prop is not None else None
    if sk is None or sk.rows == 0:
        # IsNull(True) over an absent property selects everything
        if flt.operator == "IsNull":
            return 1.0 if flt.value in (True, None) else 0.0
        return _UNKNOWN_SELECTIVITY
    op = flt.operator
    ndv = max(1, sk.ndv())
    if op == "Equal":
        return 1.0 / ndv
    if op == "NotEqual":
        return 1.0 - 1.0 / ndv
    if op in ("GreaterThan", "GreaterThanEqual",
              "LessThan", "LessThanEqual"):
        if isinstance(flt.value, (int, float)) \
                and not isinstance(flt.value, bool):
            return _range_fraction(sk, op, float(flt.value))
        # lexical comparison: no distribution info, fall back
        return _UNKNOWN_SELECTIVITY
    if op == "Like":
        pat = flt.value if isinstance(flt.value, str) else ""
        if "*" not in pat and "?" not in pat:
            return 1.0 / ndv  # no wildcard == Equal
        return max(1.0 / ndv, 0.05)
    if op == "ContainsAny":
        vals = flt.value if isinstance(flt.value, list) else [flt.value]
        miss = (1.0 - 1.0 / ndv) ** max(1, len(vals))
        return 1.0 - miss
    if op == "ContainsAll":
        vals = flt.value if isinstance(flt.value, list) else [flt.value]
        # first value Equal-like, each extra value halves (positively
        # correlated values co-occur far above independence)
        return (1.0 / ndv) * (0.5 ** (max(1, len(vals)) - 1))
    return _UNKNOWN_SELECTIVITY  # WithinGeoRange + anything unforeseen


def estimate_selectivity(flt: Filter,
                         sketches: Mapping[str, PropertySketch],
                         doc_count: int) -> float:
    """Estimated fraction of live docs passing ``flt`` — pure, in [0, 1].

    The row fraction (docs carrying the property at all) scales every
    positive leaf; negative leaves (NotEqual / IsNull True) additionally
    select docs *without* the property.
    """
    op = flt.operator
    if op == "And":
        s = 1.0
        for o in flt.operands:
            s *= estimate_selectivity(o, sketches, doc_count)
        return s
    if op == "Or":
        miss = 1.0
        for o in flt.operands:
            miss *= 1.0 - estimate_selectivity(o, sketches, doc_count)
        return 1.0 - miss
    if op == "Not":
        return 1.0 - estimate_selectivity(flt.operands[0], sketches,
                                          doc_count)

    prop = flt.path[-1] if flt.path else None
    sk = sketches.get(prop) if prop is not None else None
    n = max(1, doc_count)
    row_frac = min(1.0, sk.rows / n) if sk is not None else 0.0
    if op == "IsNull":
        want_null = flt.value in (True, None)
        return (1.0 - row_frac) if want_null else row_frac
    if sk is None or sk.rows == 0:
        return _UNKNOWN_SELECTIVITY
    # every non-null leaf (including NotEqual — reference semantics keep
    # absent docs out of NotEqual results) scales by the row fraction
    return min(1.0, _leaf_selectivity(flt, sketches) * row_frac)
