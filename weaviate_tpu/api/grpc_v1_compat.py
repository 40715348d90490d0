"""weaviate.v1 gRPC service: the reference's public wire contract.

Reference: ``adapters/handlers/grpc/v1/service.go`` — stock weaviate
clients speak ``/weaviate.v1.Weaviate/...`` with the messages in
``grpc/proto/v1/*.proto``. This adapter translates that contract onto the
same Explorer/Collection machinery the native ``weaviate_tpu.v1`` plane
uses (which remains the TPU-first surface: its Search carries a BATCH of
query vectors per RPC). Served alongside it on the same port.

Covered: Search (near_vector/bm25/hybrid/near_text, filters, metadata,
properties, sort, group_by, autocut), BatchObjects, BatchDelete,
TenantsGet, Aggregate (count/int/number/text/boolean, group_by), and the
bidirectional BatchStream (start -> started, data -> acks/results,
stop -> shutdown; reference ``grpc/v1/batch/start.go:35``).
"""

from __future__ import annotations

import json
import struct
import time
import uuid as uuidlib
from typing import Any, Optional

import grpc
import numpy as np

from weaviate_tpu.api.proto import weaviate_v1_compat_pb2 as wv
from weaviate_tpu.core.db import DB
from weaviate_tpu.inverted.filters import Filter
from weaviate_tpu.query import Explorer, HybridParams, QueryParams
from weaviate_tpu.storage.objects import StorageObject

SERVICE_V1 = "weaviate.v1.Weaviate"

_OP_NAMES = {
    wv.Filters.OPERATOR_EQUAL: "Equal",
    wv.Filters.OPERATOR_NOT_EQUAL: "NotEqual",
    wv.Filters.OPERATOR_GREATER_THAN: "GreaterThan",
    wv.Filters.OPERATOR_GREATER_THAN_EQUAL: "GreaterThanEqual",
    wv.Filters.OPERATOR_LESS_THAN: "LessThan",
    wv.Filters.OPERATOR_LESS_THAN_EQUAL: "LessThanEqual",
    wv.Filters.OPERATOR_AND: "And",
    wv.Filters.OPERATOR_OR: "Or",
    wv.Filters.OPERATOR_WITHIN_GEO_RANGE: "WithinGeoRange",
    wv.Filters.OPERATOR_LIKE: "Like",
    wv.Filters.OPERATOR_IS_NULL: "IsNull",
    wv.Filters.OPERATOR_CONTAINS_ANY: "ContainsAny",
    wv.Filters.OPERATOR_CONTAINS_ALL: "ContainsAll",
    wv.Filters.OPERATOR_NOT: "Not",
}


# -- request decoding --------------------------------------------------------

def filter_from_pb(f: wv.Filters) -> Filter:
    op = _OP_NAMES.get(f.operator)
    if op is None:
        raise ValueError(f"unsupported filter operator {f.operator}")
    if op in ("And", "Or", "Not"):
        return Filter(operator=op,
                      operands=[filter_from_pb(x) for x in f.filters])
    which = f.WhichOneof("test_value")
    value: Any = None
    if which == "value_text":
        value = f.value_text
    elif which == "value_int":
        value = int(f.value_int)
    elif which == "value_boolean":
        value = f.value_boolean
    elif which == "value_number":
        value = f.value_number
    elif which == "value_text_array":
        value = list(f.value_text_array.values)
    elif which == "value_int_array":
        value = [int(v) for v in f.value_int_array.values]
    elif which == "value_boolean_array":
        value = list(f.value_boolean_array.values)
    elif which == "value_number_array":
        value = list(f.value_number_array.values)
    elif which == "value_geo":
        value = {"latitude": f.value_geo.latitude,
                 "longitude": f.value_geo.longitude,
                 "distance": f.value_geo.distance}
    path: list[str] = []
    if f.target.WhichOneof("target") == "property":
        path = [f.target.property]
    elif f.on:
        path = list(f.on)
    return Filter(operator=op, path=path or None, value=value)


def _vec_from_bytes(raw: bytes) -> np.ndarray:
    return np.frombuffer(raw, "<f4").astype(np.float32)


def _decode_vectors_entry(v: wv.Vectors) -> np.ndarray:
    if v.type == wv.Vectors.VECTOR_TYPE_MULTI_FP32:
        # wire layout (reference byteops.Fp32SliceOfSlicesFromBytes): a
        # little-endian uint16 row dimension, then row-major f32 tokens
        raw = v.vector_bytes
        if len(raw) < 2:
            raise ValueError("multi-vector payload too short")
        dim = int(np.frombuffer(raw[:2], "<u2")[0])
        if dim == 0:
            raise ValueError("multi-vector dimension cannot be 0")
        return np.frombuffer(raw[2:], "<f4").astype(
            np.float32).reshape(-1, dim)
    return _vec_from_bytes(v.vector_bytes)


def vector_from_near(nv: wv.NearVector) -> np.ndarray:
    if nv.vectors:
        return _decode_vectors_entry(nv.vectors[0])
    if nv.vector_bytes:
        return _vec_from_bytes(nv.vector_bytes)
    return np.asarray(list(nv.vector), np.float32)


# search-operator pb -> QueryParams translation, shared by Search and
# the search-scoped Aggregate so the two planes can never drift
def apply_hybrid(params: QueryParams, h) -> None:
    vec = None
    if h.vectors:
        vec = _vec_from_bytes(h.vectors[0].vector_bytes)
    elif h.vector_bytes:
        vec = _vec_from_bytes(h.vector_bytes)
    elif h.vector:
        vec = np.asarray(list(h.vector), np.float32)
    if h.targets.target_vectors:
        params.target_vector = h.targets.target_vectors[0]
    elif h.target_vectors:
        params.target_vector = h.target_vectors[0]
    operator, min_match = "Or", 0
    if h.HasField("bm25_search_operator"):
        so = h.bm25_search_operator
        if so.operator == wv.SearchOperatorOptions.OPERATOR_AND:
            operator = "And"
        if so.HasField("minimum_or_tokens_match"):
            min_match = int(so.minimum_or_tokens_match)
    params.hybrid = HybridParams(
        query=h.query or None,
        vector=vec,
        # plain proto3 float: the reference uses it as sent, so an
        # absent field means 0.0 = pure keyword (no 0.75 coercion —
        # stock clients always set alpha explicitly)
        alpha=float(h.alpha),
        fusion=("rankedFusion"
                if h.fusion_type == wv.Hybrid.FUSION_TYPE_RANKED
                else "relativeScoreFusion"),
        properties=list(h.properties) or None,
        operator=operator,
        minimum_match=min_match,
    )


# reference CombinationMethod enum (base_search.proto); UNSPECIFIED
# keeps the reference's minimum default
_COMBINATION = {0: "minimum", 1: "sum", 2: "minimum", 3: "average",
                4: "relativeScore", 5: "manualWeights"}


def _apply_targets(params: QueryParams, targets, shared, per_target) -> bool:
    """Translate a pb ``Targets`` block (+ optional per-target vectors)
    into the QueryParams multi-target fields. Returns False when the
    request is single-target so callers keep the legacy field mapping.
    ValueError surfaces as INVALID_ARGUMENT at the servicer boundary."""
    tv = list(targets.target_vectors)
    if per_target is None and len(tv) <= 1:
        return False
    vecs: dict[str, np.ndarray] = dict(per_target or {})
    for t in tv:
        if t not in vecs:
            if shared is None:
                raise ValueError(
                    f"no query vector provided for target {t!r}")
            vecs[t] = shared
    if not vecs:
        return False
    combination = _COMBINATION.get(int(targets.combination))
    if combination is None:
        raise ValueError(
            f"unknown combination method {int(targets.combination)}")
    weights = {w.target: float(w.weight)
               for w in targets.weights_for_targets}
    if weights and int(targets.combination) == 0:
        combination = "manualWeights"
    params.targets = vecs
    params.target_combination = combination
    params.target_weights = weights or None
    return True


def apply_near_vector(params: QueryParams, nv) -> None:
    per_target: Optional[dict[str, np.ndarray]] = None
    if nv.vector_for_targets:
        per_target = {}
        for vt in nv.vector_for_targets:
            if vt.vectors:
                per_target[vt.name] = _decode_vectors_entry(vt.vectors[0])
            elif vt.vector_bytes:
                per_target[vt.name] = _vec_from_bytes(vt.vector_bytes)
            else:
                raise ValueError(
                    f"vector_for_targets entry {vt.name!r} carries no "
                    "vector")
    shared = None
    if nv.vectors or nv.vector_bytes or nv.vector:
        shared = vector_from_near(nv)
    if _apply_targets(params, nv.targets, shared, per_target):
        if nv.HasField("distance"):
            params.max_distance = float(nv.distance)
        return
    params.near_vector = vector_from_near(nv)
    if nv.targets.target_vectors:
        params.target_vector = nv.targets.target_vectors[0]
    elif nv.target_vectors:
        params.target_vector = nv.target_vectors[0]
    if nv.HasField("distance"):
        params.max_distance = float(nv.distance)


def apply_near_text(params: QueryParams, nt) -> None:
    params.near_text = " ".join(nt.query)
    if nt.HasField("distance"):
        params.max_distance = float(nt.distance)
    if nt.HasField("move_to"):
        params.near_text_move_to = {
            "concepts": list(nt.move_to.concepts),
            "objects": list(nt.move_to.uuids),
            "force": float(nt.move_to.force)}
    if nt.HasField("move_away"):
        params.near_text_move_away = {
            "concepts": list(nt.move_away.concepts),
            "objects": list(nt.move_away.uuids),
            "force": float(nt.move_away.force)}


def _struct_value(v) -> Any:
    kind = v.WhichOneof("kind")
    if kind == "number_value":
        # stays float: 10.0 collapsing to int would make auto-schema
        # infer INT for a number property (the reference infers number
        # from Struct numbers) and corrupt later 10.5 writes
        return v.number_value
    if kind == "string_value":
        return v.string_value
    if kind == "bool_value":
        return v.bool_value
    if kind == "struct_value":
        return {k: _struct_value(x) for k, x in v.struct_value.fields.items()}
    if kind == "list_value":
        return [_struct_value(x) for x in v.list_value.values]
    return None


def object_from_pb(bo: wv.BatchObject) -> StorageObject:
    props: dict[str, Any] = {
        k: _struct_value(v)
        for k, v in bo.properties.non_ref_properties.fields.items()
    }
    for ap in bo.properties.number_array_properties:
        props[ap.prop_name] = (
            np.frombuffer(ap.values_bytes, "<f8").tolist()
            if ap.values_bytes else list(ap.values))
    for ap in bo.properties.int_array_properties:
        props[ap.prop_name] = [int(x) for x in ap.values]
    for ap in bo.properties.text_array_properties:
        props[ap.prop_name] = list(ap.values)
    for ap in bo.properties.boolean_array_properties:
        props[ap.prop_name] = list(ap.values)
    for name in bo.properties.empty_list_props:
        props[name] = []
    vector = None
    named: dict[str, np.ndarray] = {}
    if bo.vector_bytes:
        vector = _vec_from_bytes(bo.vector_bytes)
    elif bo.vector:
        vector = np.asarray(list(bo.vector), np.float32)
    for v in bo.vectors:
        arr = _decode_vectors_entry(v)
        if v.name:
            named[v.name] = arr
        else:
            vector = arr
    return StorageObject(
        uuid=bo.uuid or str(uuidlib.uuid4()),
        collection=bo.collection,
        tenant=bo.tenant,
        properties=props,
        vector=vector,
        named_vectors=named,
    )


# -- reply encoding ----------------------------------------------------------

def _value_to_pb(out: wv.Value, value: Any) -> None:
    if value is None:
        out.null_value = 0
    elif isinstance(value, bool):
        out.bool_value = value
    elif isinstance(value, int):
        out.int_value = value
    elif isinstance(value, float):
        out.number_value = value
    elif isinstance(value, str):
        out.text_value = value
    elif isinstance(value, dict):
        if "latitude" in value and "longitude" in value:
            out.geo_value.latitude = float(value["latitude"])
            out.geo_value.longitude = float(value["longitude"])
        else:
            for k, v in value.items():
                _value_to_pb(out.object_value.fields[k], v)
    elif isinstance(value, (list, tuple, np.ndarray)):
        vals = list(value)
        if not vals:
            out.list_value.text_values.SetInParent()
        elif all(isinstance(x, bool) for x in vals):
            out.list_value.bool_values.values.extend(vals)
        elif all(isinstance(x, int) for x in vals):
            out.list_value.int_values.values = struct.pack(
                f"<{len(vals)}q", *vals)
        elif all(isinstance(x, (int, float)) for x in vals):
            out.list_value.number_values.values = struct.pack(
                f"<{len(vals)}d", *[float(x) for x in vals])
        elif all(isinstance(x, str) for x in vals):
            out.list_value.text_values.values.extend(vals)
        elif all(isinstance(x, dict) for x in vals):
            for x in vals:
                p = out.list_value.object_values.values.add()
                for k, v in x.items():
                    _value_to_pb(p.fields[k], v)


def _fill_result(sr: wv.SearchResult, obj: StorageObject,
                 distance: Optional[float], score: Optional[float],
                 md_req: Optional[wv.MetadataRequest],
                 props_req: Optional[wv.PropertiesRequest]) -> None:
    md = sr.metadata
    if md_req is None or md_req.uuid:
        md.id = obj.uuid
    if md_req is not None:
        if md_req.creation_time_unix:
            md.creation_time_unix = obj.creation_time_ms
            md.creation_time_unix_present = True
        if md_req.last_update_time_unix:
            md.last_update_time_unix = obj.update_time_ms
            md.last_update_time_unix_present = True
        if md_req.vector and obj.vector is not None:
            md.vector_bytes = np.asarray(
                obj.vector, "<f4").tobytes()
        for nm in md_req.vectors:
            v = obj.named_vectors.get(nm)
            if v is not None:
                ent = md.vectors.add()
                ent.name = nm
                ent.vector_bytes = np.asarray(v, "<f4").tobytes()
                ent.type = wv.Vectors.VECTOR_TYPE_SINGLE_FP32
    if distance is not None and (md_req is None or md_req.distance):
        md.distance = distance
        md.distance_present = True
    if score is not None and (md_req is None or md_req.score):
        md.score = score
        md.score_present = True

    wanted = None
    if props_req is not None and not props_req.return_all_nonref_properties:
        wanted = set(props_req.non_ref_properties)
    for k, v in obj.properties.items():
        if wanted is not None and k not in wanted:
            continue
        _value_to_pb(sr.properties.non_ref_props.fields[k], v)
    sr.properties.target_collection = obj.collection


class WeaviateV1Service:
    """The weaviate.v1 service handlers (registered as generic handlers)."""

    def __init__(self, db: DB, auth=None, rbac=None, qos=None):
        self.db = db
        self.explorer = Explorer(db)
        self.auth = auth
        self.rbac = rbac
        # same admission controller as the native plane (GrpcAPI passes
        # its own down); stand-alone use shares the DB's controller
        self.qos = qos if qos is not None else db.qos

    # -- auth (same identity machinery as the native plane) ----------------
    def _identity(self, context):
        if self.auth is None:
            return None, ()
        from weaviate_tpu.api.rest import AuthError

        md = dict(context.invocation_metadata() or [])
        try:
            return self.auth.identity_for(md.get("authorization", ""))
        except AuthError as e:
            context.abort(grpc.StatusCode.UNAUTHENTICATED, str(e))

    def _check(self, context, principal, groups, action: str, resource: str):
        if self.rbac is None:
            return
        from weaviate_tpu.auth.rbac import Forbidden

        try:
            self.rbac.authorize(principal, action, resource, groups=groups)
        except Forbidden as e:
            context.abort(grpc.StatusCode.PERMISSION_DENIED, str(e))

    def _gate(self, context, action: str, resource: str):
        principal, groups = self._identity(context)
        self._check(context, principal, groups, action, resource)

    def _authz_objects(self, context, principal, groups, objects) -> None:
        """Per-object create/update authz, mirroring the native plane
        (upsert of an existing uuid needs update_data, and resources are
        collection-scoped)."""
        if self.rbac is None:
            return
        for bo in objects:
            act = "create_data"
            try:
                if bo.uuid and self.db.has_collection(bo.collection) and \
                        self.db.get_collection(bo.collection).exists(
                            bo.uuid, bo.tenant):
                    act = "update_data"
            except (KeyError, ValueError, RuntimeError):
                pass
            self._check(context, principal, groups, act,
                        f"collections/{bo.collection}")

    # -- Search ------------------------------------------------------------
    def search(self, req: wv.SearchRequest, context) -> wv.SearchReply:
        t0 = time.perf_counter()
        self._gate(context, "read_data", f"collections/{req.collection}")
        flt = (filter_from_pb(req.filters)
               if req.HasField("filters") else None)
        md_req = req.metadata if req.HasField("metadata") else None
        props_req = req.properties if req.HasField("properties") else None

        params = QueryParams(
            collection=req.collection, tenant=req.tenant,
            limit=int(req.limit) or 10, offset=int(req.offset),
            filters=flt, autocut=int(req.autocut),
            # proto3 string can't carry absent-vs-empty: empty = no
            # cursor, like the reference's gRPC parse
            after=req.after or None,
        )
        if req.sort_by:
            params.sort = [
                (".".join(s.path), "asc" if s.ascending else "desc")
                for s in req.sort_by if s.path
            ]
        if req.HasField("group_by") and req.group_by.path:
            from weaviate_tpu.query.groupby import GroupByParams

            params.group_by = GroupByParams(
                property=req.group_by.path[0],
                groups=int(req.group_by.number_of_groups) or 5,
                objects_per_group=int(req.group_by.objects_per_group) or 10,
            )
        if req.HasField("hybrid_search"):
            apply_hybrid(params, req.hybrid_search)
        elif req.HasField("near_vector"):
            apply_near_vector(params, req.near_vector)
        elif req.HasField("near_text"):
            apply_near_text(params, req.near_text)
        elif req.HasField("bm25_search"):
            params.bm25_query = req.bm25_search.query
            params.bm25_properties = list(req.bm25_search.properties) or None
            if req.bm25_search.HasField("search_operator"):
                so = req.bm25_search.search_operator
                if so.operator == \
                        wv.SearchOperatorOptions.OPERATOR_AND:
                    params.bm25_operator = "And"
                if so.HasField("minimum_or_tokens_match"):
                    params.bm25_minimum_match = int(
                        so.minimum_or_tokens_match)

        out = self.explorer.get(params)
        reply = wv.SearchReply()
        keyword = params.hybrid is not None or params.bm25_query
        if out.groups:
            for g in out.groups:
                gr = reply.group_by_results.add()
                gr.name = str(g.value)
                gr.number_of_objects = len(g.objects)
                gr.min_distance = g.min_score
                gr.max_distance = g.max_score
                for obj, s in g.objects:
                    dist = None if keyword else s
                    score = s if keyword else None
                    _fill_result(gr.objects.add(), obj, dist, score,
                                 md_req, props_req)
        else:
            for hit in out.hits:
                _fill_result(reply.results.add(), hit.object, hit.distance,
                             hit.score, md_req, props_req)
        reply.took = time.perf_counter() - t0
        return reply

    # -- BatchObjects ------------------------------------------------------
    def _coerce_schema_ints(self, obj: StorageObject) -> None:
        """protobuf Struct has no integer kind — clients send ints as
        number_value. The reference resolves the type from the SCHEMA:
        a number targeting an INT property coerces to int; unknown/new
        props stay float (auto-schema infers number, like the reference)."""
        if not self.db.has_collection(obj.collection):
            return
        cfg = self.db.get_collection(obj.collection).config
        for name, val in list(obj.properties.items()):
            p = cfg.property(name)
            if p is None:
                continue
            dt = p.data_type.value
            if dt == "int" and isinstance(val, float) and val.is_integer():
                obj.properties[name] = int(val)
            elif dt == "int[]" and isinstance(val, list):
                obj.properties[name] = [
                    int(x) if isinstance(x, float) and x.is_integer()
                    else x for x in val]

    def _insert(self, objects) -> list[tuple[int, str]]:
        """Insert BatchObjects; returns (index, error) pairs."""
        from weaviate_tpu.api.grpc_server import insert_grouped

        errors: list[tuple[int, str]] = []
        decoded: list[tuple[int, StorageObject]] = []
        for i, bo in enumerate(objects):
            try:
                obj = object_from_pb(bo)
                self._coerce_schema_ints(obj)
                decoded.append((i, obj))
            except (ValueError, KeyError) as e:
                errors.append((i, str(e)))
        errors.extend(insert_grouped(self.db, decoded))
        return errors

    def batch_objects(self, req: wv.BatchObjectsRequest,
                      context) -> wv.BatchObjectsReply:
        t0 = time.perf_counter()
        principal, groups = self._identity(context)
        self._authz_objects(context, principal, groups, req.objects)
        reply = wv.BatchObjectsReply()
        for i, msg in self._insert(req.objects):
            err = reply.errors.add()
            err.index = i
            err.error = msg
        reply.took = time.perf_counter() - t0
        return reply

    def batch_references(self, req: wv.BatchReferencesRequest,
                         context) -> wv.BatchReferencesReply:
        """Reference ``grpc/v1/batch references`` handler: each entry names
        (from_collection, from_uuid, property) and the target uuid; errors
        report per index like BatchObjects."""
        t0 = time.perf_counter()
        principal, groups = self._identity(context)
        # authorize EVERY entry before applying ANY (batch_objects order):
        # a mid-loop PERMISSION_DENIED abort after partial writes would
        # leave the client unable to tell what landed
        for ref in req.references:
            self._check(context, principal, groups, "update_data",
                        f"collections/{ref.from_collection}")
        reply = wv.BatchReferencesReply()
        for i, ref in enumerate(req.references):
            try:
                col = self.db.get_collection(ref.from_collection)
                target_cls = ref.to_collection or ""
                beacon = ("weaviate://localhost/"
                          + (f"{target_cls}/" if target_cls else "")
                          + ref.to_uuid)
                col.add_reference(ref.from_uuid, ref.name, beacon,
                                  tenant=ref.tenant)
            except (KeyError, ValueError) as e:
                err = reply.errors.add()
                err.index = i
                err.error = str(e)
        reply.took = time.perf_counter() - t0
        return reply

    # -- BatchStream (bidi) ------------------------------------------------
    def batch_stream(self, request_iterator, context):
        """start -> Started; each Data -> Acks then Results; stop ->
        Shutdown (reference grpc/v1/batch/start.go:35 state machine)."""
        principal, groups = self._identity(context)
        for msg in request_iterator:
            which = msg.WhichOneof("message")
            if which == "start":
                reply = wv.BatchStreamReply()
                reply.started.SetInParent()
                yield reply
            elif which == "data":
                objs = list(msg.data.objects.values)
                self._authz_objects(context, principal, groups, objs)
                ack = wv.BatchStreamReply()
                ack.acks.uuids.extend(o.uuid for o in objs)
                yield ack
                errors = dict(self._insert(objs))
                res = wv.BatchStreamReply()
                for i, o in enumerate(objs):
                    if i in errors:
                        e = res.results.errors.add()
                        e.error = errors[i]
                        e.uuid = o.uuid
                    else:
                        s = res.results.successes.add()
                        s.uuid = o.uuid
                yield res
            elif which == "stop":
                reply = wv.BatchStreamReply()
                reply.shutdown.SetInParent()
                yield reply
                return

    # -- BatchDelete -------------------------------------------------------
    def batch_delete(self, req: wv.BatchDeleteRequest,
                     context) -> wv.BatchDeleteReply:
        t0 = time.perf_counter()
        self._gate(context, "delete_data", f"collections/{req.collection}")
        col = self.db.get_collection(req.collection)
        if not req.HasField("filters"):
            raise ValueError("BatchDelete requires filters (the reference "
                             "refuses unfiltered deletes the same way)")
        flt = filter_from_pb(req.filters)
        tenant = req.tenant if req.HasField("tenant") else ""
        reply = wv.BatchDeleteReply()
        # reference semantics (shard_write_batch_delete.go:105): dry run
        # walks the same per-object path with the delete skipped and
        # Err=nil, so matches == successful either way; verbose returns
        # one BatchDeleteObject per matched uuid with the uuid encoded as
        # the big-endian INTEGER bytes of the hex form, leading zeros
        # stripped (batch_delete.go:82 big.Int.Bytes)
        # the reference caps the WHOLE operation at QueryMaximumResults
        # (db/batch.go fetches matching ids capped, deletes only those;
        # clients loop until matches < cap) — so matches, successful and
        # the verbose list always agree, one filter scan total
        cap_n = 10_000
        matched = [o.uuid for o in col.filter_search(
            flt, limit=cap_n, tenant=tenant)]
        if not req.dry_run and matched:
            col.delete(matched, tenant=tenant)
        reply.matches = len(matched)
        reply.successful = len(matched)
        reply.failed = 0
        if req.verbose:
            for u in matched:
                bo = reply.objects.add()
                bo.uuid = bytes.fromhex(u.replace("-", "")).lstrip(b"\x00")
                bo.successful = True
                # the reference always sets Error (pointer to "") on
                # success — "empty string means no error" per the proto
                bo.error = ""
        reply.took = time.perf_counter() - t0
        return reply

    # -- TenantsGet --------------------------------------------------------
    def tenants_get(self, req: wv.TenantsGetRequest,
                    context) -> wv.TenantsGetReply:
        t0 = time.perf_counter()
        self._gate(context, "read_tenants", f"collections/{req.collection}")
        col = self.db.get_collection(req.collection)
        want = (set(req.names.values)
                if req.WhichOneof("params") == "names" else None)
        reply = wv.TenantsGetReply()
        status_map = {
            "HOT": wv.TENANT_ACTIVITY_STATUS_HOT,
            "COLD": wv.TENANT_ACTIVITY_STATUS_COLD,
            "FROZEN": wv.TENANT_ACTIVITY_STATUS_FROZEN,
        }
        for name, status in sorted(col.tenants().items()):
            if want is not None and name not in want:
                continue
            t = reply.tenants.add()
            t.name = name
            t.activity_status = status_map.get(
                status, wv.TENANT_ACTIVITY_STATUS_HOT)
        reply.took = time.perf_counter() - t0
        return reply

    # -- Aggregate ---------------------------------------------------------
    def aggregate(self, req: wv.AggregateRequest,
                  context) -> wv.AggregateReply:
        t0 = time.perf_counter()
        self._gate(context, "read_data", f"collections/{req.collection}")
        col = self.db.get_collection(req.collection)
        flt = filter_from_pb(req.filters) if req.HasField("filters") else None
        kind_of = {"int": "numeric", "number": "numeric", "text": "text",
                   "boolean": "boolean"}
        props = {
            a.property: kind_of.get(a.WhichOneof("aggregation"), "auto")
            for a in req.aggregations
        }
        group_by = (req.group_by.property
                    if req.HasField("group_by") else None)
        search = req.WhichOneof("search")
        if search is not None:
            # search-scoped aggregation (reference aggregate.proto
            # oneof search + object_limit): aggregate the top hits
            from weaviate_tpu.query.aggregator import (
                DISTANCE_AGG_CAP as _DISTANCE_AGG_CAP,
                aggregate_objects,
            )

            params = QueryParams(collection=req.collection,
                                 tenant=req.tenant, filters=flt)
            if search == "near_vector":
                apply_near_vector(params, req.near_vector)
            elif search == "hybrid":
                apply_hybrid(params, req.hybrid)
            else:  # near_text — vectorized by the collection's module
                apply_near_text(params, req.near_text)
            if not req.HasField("object_limit") \
                    and params.max_distance is None:
                raise ValueError(
                    "Aggregate with a search needs object_limit or a "
                    "distance bound")
            params.limit = (int(req.object_limit)
                            if req.HasField("object_limit")
                            else _DISTANCE_AGG_CAP)
            hits = self.explorer.get(params).hits
            if not req.HasField("object_limit") \
                    and len(hits) >= _DISTANCE_AGG_CAP:
                raise ValueError(
                    f"distance-bounded Aggregate matched >= "
                    f"{_DISTANCE_AGG_CAP} objects; set object_limit")
            result = aggregate_objects(
                [h.object for h in hits], props, group_by)
        else:
            result = col.aggregate(properties=props or None, flt=flt,
                                   tenant=req.tenant, group_by=group_by)
        reply = wv.AggregateReply()

        def fill_aggs(aggs_pb, stats: dict):
            for a in req.aggregations:
                st = stats.get(a.property)
                if st is None:
                    continue
                out = aggs_pb.aggregations.add()
                out.property = a.property
                kind = a.WhichOneof("aggregation")
                if kind == "int":
                    out.int.count = st.get("count", 0)
                    for f in ("mean", "median"):
                        if st.get(f) is not None:
                            setattr(out.int, f, float(st[f]))
                    for f, src in (("maximum", "max"), ("minimum", "min"),
                                   ("sum", "sum")):
                        if st.get(src) is not None:
                            setattr(out.int, f, int(st[src]))
                elif kind == "number":
                    out.number.count = st.get("count", 0)
                    for f, src in (("mean", "mean"), ("median", "median"),
                                   ("maximum", "max"), ("minimum", "min"),
                                   ("sum", "sum")):
                        if st.get(src) is not None:
                            setattr(out.number, f, float(st[src]))
                elif kind == "text":
                    out.text.count = st.get("count", 0)
                    for item in st.get("topOccurrences", []):
                        to = out.text.top_occurences.items.add()
                        to.value = str(item["value"])
                        to.occurs = int(item["occurs"])
                elif kind == "boolean":
                    out.boolean.count = st.get("count", 0)
                    if st.get("totalTrue") is not None:
                        out.boolean.total_true = int(st["totalTrue"])
                    if st.get("totalFalse") is not None:
                        out.boolean.total_false = int(st["totalFalse"])

        if group_by:
            for g in result.get("groups", []):
                grp = reply.grouped_results.groups.add()
                grp.objects_count = g.get("meta", {}).get("count", 0)
                grp.grouped_by.path.append(group_by)
                val = g.get("groupedBy", {}).get("value")
                if isinstance(val, bool):
                    grp.grouped_by.boolean = val
                elif isinstance(val, int):
                    grp.grouped_by.int = val
                elif isinstance(val, float):
                    grp.grouped_by.number = val
                else:
                    grp.grouped_by.text = str(val)
                fill_aggs(grp.aggregations, g.get("properties", {}))
        else:
            reply.single_result.objects_count = result.get(
                "meta", {}).get("count", 0)
            fill_aggs(reply.single_result.aggregations,
                      result.get("properties", {}))
        reply.took = time.perf_counter() - t0
        return reply

    # -- registration ------------------------------------------------------
    def generic_handler(self):
        from weaviate_tpu.api.grpc_server import (
            qos_admit,
            traced_unary_handler,
        )
        from weaviate_tpu.cluster.resilience import DeadlineExceeded
        from weaviate_tpu.serving.context import request_scope
        from weaviate_tpu.tiering import ColdStartPending

        def unary(name, fn, req_cls):
            def run(request, context):
                # same admission + end-to-end deadline as the native
                # plane (shared qos_admit); tenant rides most requests
                ticket, ctx = qos_admit(
                    self.qos, name, context,
                    tenant=getattr(request, "tenant", ""))
                try:
                    with ticket, request_scope(ctx):
                        return fn(request, context)
                except DeadlineExceeded as e:
                    context.abort(grpc.StatusCode.DEADLINE_EXCEEDED,
                                  str(e))
                except KeyError as e:
                    context.abort(grpc.StatusCode.NOT_FOUND, str(e))
                except (ValueError, TypeError) as e:
                    context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
                except ColdStartPending as e:
                    # tiering cold-start shed (subclasses RuntimeError):
                    # UNAVAILABLE + retry-after, same as the native plane
                    context.set_trailing_metadata(
                        (("retry-after", str(int(e.retry_after))),))
                    context.abort(grpc.StatusCode.UNAVAILABLE, str(e))
                except RuntimeError as e:
                    context.abort(grpc.StatusCode.FAILED_PRECONDITION,
                                  str(e))
            # ingress span, attributes and the grpc.send child shared
            # with the native plane (the two planes must not drift)
            return traced_unary_handler(name, run, req_cls,
                                        plane="v1_compat")

        # BatchStream stays un-admitted: it is flow-controlled per Data
        # message by the gRPC stream itself, and a mid-stream shed would
        # strand the client's protocol state machine
        stream = grpc.stream_stream_rpc_method_handler(
            self.batch_stream,
            request_deserializer=wv.BatchStreamRequest.FromString,
            response_serializer=lambda m: m.SerializeToString())

        return grpc.method_handlers_generic_handler(SERVICE_V1, {
            "Search": unary("Search", self.search, wv.SearchRequest),
            "BatchObjects": unary("BatchObjects", self.batch_objects,
                                  wv.BatchObjectsRequest),
            "BatchReferences": unary("BatchReferences",
                                     self.batch_references,
                                     wv.BatchReferencesRequest),
            "BatchDelete": unary("BatchDelete", self.batch_delete,
                                 wv.BatchDeleteRequest),
            "TenantsGet": unary("TenantsGet", self.tenants_get,
                                wv.TenantsGetRequest),
            "Aggregate": unary("Aggregate", self.aggregate,
                               wv.AggregateRequest),
            "BatchStream": stream,
        })
