"""gRPC data plane server.

Reference: ``adapters/handlers/grpc/v1/service.go`` (Search :271,
BatchObjects :221, BatchDelete, TenantsGet, Aggregate). The service is
registered through ``grpc.method_handlers_generic_handler`` with
protoc-generated messages — the image has no grpc codegen plugin, so the
stub layer is explicit (and tiny).

TPU-first deviation from the reference: ``SearchRequest.near_vectors`` is a
batch — all query vectors in one RPC are answered by ONE batched device
call, the design SURVEY.md §7 calls out as the amortization lever for the
host↔device round-trip.
"""

from __future__ import annotations

import json
import threading
import time
from concurrent import futures
from typing import Optional

import grpc
import numpy as np

from weaviate_tpu.api.graphql import where_to_filter
from weaviate_tpu.api.proto import pb
from weaviate_tpu.cluster.resilience import Deadline, DeadlineExceeded
from weaviate_tpu.core.db import DB
from weaviate_tpu.monitoring.tracing import TRACER, annotate
from weaviate_tpu.query import Explorer, HybridParams, QueryParams
from weaviate_tpu.serving.context import RequestContext, request_scope
from weaviate_tpu.serving.qos import QosRejected
from weaviate_tpu.tiering import ColdStartPending

SERVICE = "weaviate_tpu.v1.WeaviateTpu"
# a BatchObjects of 100 ColBERT passages is ~4 MB (100 x ~76 tokens x 128-d
# float32), over gRPC's own 4 MiB default; the reference's default
# (usecases/config DefaultGRPCMaxMsgSize)
MAX_MESSAGE_BYTES = 104858000

# admission lane per RPC (mirrors the REST endpoint->lane map): search
# and aggregation are interactive, bulk mutation rides the batch lane
RPC_LANES = {
    "Search": "interactive", "Aggregate": "interactive",
    "BatchObjects": "batch", "BatchReferences": "batch",
    "BatchDelete": "batch", "TenantsGet": "background",
}


def qos_admit(qos, name: str, context, tenant: str = ""):
    """Shared gRPC-plane admission: mint the end-to-end Deadline from the
    client's gRPC deadline (clamped to the server default), acquire a QoS
    ticket, and map shed/expiry onto RESOURCE_EXHAUSTED (with a
    ``retry-after`` trailer) / DEADLINE_EXCEEDED. Returns
    ``(ticket, request_scope_ctx)``; both planes use it so they can't
    drift."""
    from weaviate_tpu.utils.runtime_config import SERVING_DEFAULT_TIMEOUT_S

    if not qos.enabled():  # serving_qos=off: no deadline, no admission
        return qos.acquire(), None
    # the client's gRPC deadline IS the budget when given (capped like
    # REST's X-Request-Timeout at 600s — a longer client deadline must
    # not be silently truncated to the server default); the default
    # applies only to clients that sent none. grpc-python reports "no
    # deadline" as ~2^63 ns remaining, not None, hence the sanity bound.
    remaining = context.time_remaining()
    if remaining is not None and remaining < 1e9:
        budget = min(max(0.0, remaining), 600.0)
    else:
        budget = SERVING_DEFAULT_TIMEOUT_S.get()
    deadline = Deadline(budget, op=f"grpc.{name}")
    lane = RPC_LANES.get(name, "background")
    from weaviate_tpu.monitoring import tracing

    try:
        # same qos.queue span as the REST plane: a shed or queued-past-
        # deadline request exits it with ERROR before the abort below
        with tracing.TRACER.span("qos.queue", lane=lane,
                                 tenant=tenant) as qspan:
            ticket = qos.acquire(lane, tenant=tenant, deadline=deadline)
            qspan.set(queue_wait_ms=round(ticket.queue_wait * 1000, 3))
    except QosRejected as e:
        context.set_trailing_metadata(
            (("retry-after", str(int(e.retry_after))),))
        context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, str(e))
    except DeadlineExceeded as e:
        context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(e))
    ctx = RequestContext(deadline=deadline, lane=lane, tenant=tenant,
                         queue_wait_s=ticket.queue_wait,
                         trace=tracing.current_span())
    return ticket, ctx


# what the worker thread of the call in hand knows of its own ingress: when
# gRPC handed the call to the pool (``_run_stamped``), and the box that the
# reply's serializer fills for the call's ``grpc.send`` span
_ingress = threading.local()


class ArrivalStampingPool(futures.ThreadPoolExecutor):
    """The pool gRPC hands every call to. ``submit`` runs on gRPC's polling
    thread as the call arrives; the stamp travels to the worker thread that
    takes the call, where ``traced_unary_handler`` turns it into
    ``pool_wait_ms`` and, when the call is retired, ``resident_ms``."""

    def submit(self, fn, /, *args, **kwargs):
        return super().submit(_run_stamped, time.perf_counter_ns(), fn,
                              *args, **kwargs)


def _run_stamped(arrived_ns: int, fn, *args, **kwargs):
    _ingress.arrived_ns = arrived_ns
    return fn(*args, **kwargs)


def traced_unary_handler(name: str, run, req_cls, **root_attrs):
    """The unary-unary handler of both gRPC planes: ``run(request,
    context)`` inside the ingress root span ``grpc.<name>`` (handler entry
    to handler return; the traceparent rides invocation metadata, same W3C
    format as the REST header), with what happened to the call before the
    handler as attributes of the root — ``pool_wait_ms`` (arrival at the
    pool -> handler start: the hand-off to a worker, the wait for the
    request message and its decode), ``decode_ms`` and ``request_bytes``
    (``FromString``, which gRPC runs on its one polling thread) — and what
    happens after it as ONE late child, ``grpc.send``: from the root's end
    until gRPC retires the call (the reply serialized on the worker,
    handed to the transport, the polling thread's turn), recorded from
    that polling thread by ``context.add_callback``. Its ``resident_ms`` =
    arrival at the pool -> retirement is the call's whole residency on the
    server's clock; ``serialize_ms`` and ``reply_bytes`` are absent where
    the call ended without a reply (abort, cancellation)."""
    span_name = f"grpc.{name}"

    def decode(data: bytes):
        t0 = time.perf_counter_ns()
        request = req_cls.FromString(data)
        return request, (time.perf_counter_ns() - t0) / 1e6, len(data)

    def handler(decoded, context):
        started_ns = time.perf_counter_ns()
        request, decode_ms, request_bytes = decoded
        arrived_ns = getattr(_ingress, "arrived_ns", started_ns)
        md = dict(context.invocation_metadata() or [])
        root = TRACER.ingress(
            span_name, traceparent=md.get("traceparent", ""), rpc=name,
            pool_wait_ms=round((started_ns - arrived_ns) / 1e6, 3),
            decode_ms=round(decode_ms, 3), request_bytes=request_bytes,
            **root_attrs)
        _ingress.sent = sent = {} if root.sampled else None
        if sent is not None:
            def retired():
                # on gRPC's polling thread, after the client has its reply
                now_ns, now_unix = time.perf_counter_ns(), time.time_ns()
                TRACER.record(
                    "grpc.send", root.end_ns or now_unix, now_unix,
                    parent=root,
                    resident_ms=round((now_ns - arrived_ns) / 1e6, 3),
                    **sent)

            context.add_callback(retired)
        with root:
            return run(request, context)

    def serialize(reply) -> bytes:
        # after the root closed, on the same worker thread
        sent = getattr(_ingress, "sent", None)
        if sent is None:
            return reply.SerializeToString()
        t0 = time.perf_counter_ns()
        data = reply.SerializeToString()
        sent["serialize_ms"] = round((time.perf_counter_ns() - t0) / 1e6, 3)
        sent["reply_bytes"] = len(data)
        return data

    return grpc.unary_unary_rpc_method_handler(
        handler, request_deserializer=decode, response_serializer=serialize)


def insert_grouped(db: DB, items) -> list[tuple[int, str]]:
    """Shared batch-insert tail for both gRPC planes: group decoded objects
    by (collection, tenant), run auto-schema, put_batch; returns
    (index, error) pairs. ``items``: [(index, StorageObject)]."""
    errors: list[tuple[int, str]] = []
    groups: dict[tuple[str, str], list] = {}
    for i, obj in items:
        groups.setdefault((obj.collection, obj.tenant), []).append((i, obj))
    for (cls, tenant), group in groups.items():
        try:
            from weaviate_tpu.schema.auto_schema import ensure_schema

            with TRACER.child("schema.ensure"):
                ensure_schema(db, cls, [o.properties for _, o in group])
            col = db.get_collection(cls)
            col.put_batch([o for _, o in group], tenant=tenant)
        except (KeyError, ValueError, RuntimeError) as e:
            errors.extend((i, str(e)) for i, _ in group)
    return errors


def _np_from_vec(v: pb.Vector) -> np.ndarray:
    """[D] from ``values``, or the [T, D] token set of ``token_bytes``."""
    if v.token_dims:
        if len(v.token_bytes) % (4 * v.token_dims):
            raise ValueError(
                f"token_bytes of {len(v.token_bytes)} bytes is no whole "
                f"number of float32 rows of {v.token_dims}")
        return np.frombuffer(v.token_bytes, "<f4").reshape(-1, v.token_dims)
    return np.asarray(v.values, np.float32)


def _vec_to_pb(out: pb.Vector, vec) -> None:
    vec = np.asarray(vec, np.float32)
    if vec.ndim == 2:
        out.token_bytes = vec.astype("<f4", copy=False).tobytes()
        out.token_dims = vec.shape[1]
    else:
        out.values.extend(vec.tolist())


# authz action + resource for each RPC (mirrors the REST layer's mapping)
_RPC_AUTHZ = {
    "Search": ("read_data", lambda r: f"collections/{r.collection}"),
    "BatchObjects": ("create_data",
                     lambda r: None),  # per-object check in handler
    "BatchDelete": ("delete_data", lambda r: f"collections/{r.collection}"),
    "TenantsGet": ("read_tenants", lambda r: f"collections/{r.collection}"),
    "Aggregate": ("read_data", lambda r: f"collections/{r.collection}"),
}


class GrpcAPI:
    def __init__(self, db: DB, max_workers: Optional[int] = None,
                 auth=None, rbac=None, qos=None):
        """``auth``: rest.AuthConfig (API keys); ``rbac``: RBACController.
        Both None = open access, matching the REST defaults — the reference
        gates its gRPC plane with the same composer chain as REST.
        ``qos``: AdmissionController; defaults to the DB-shared one so the
        worker pool below and the REST plane answer to one ceiling."""
        self.db = db
        self.explorer = Explorer(db)
        self.max_workers = max_workers
        self.auth = auth
        self.rbac = rbac
        self.qos = qos if qos is not None else db.qos
        self._server: Optional[grpc.Server] = None

    # -- auth --------------------------------------------------------------
    def _principal(self, context) -> tuple[Optional[str], list[str]]:
        """(principal, groups) — groups flow to RBAC like the REST plane."""
        if self.auth is None:
            return None, []
        from weaviate_tpu.api.rest import AuthError

        md = dict(context.invocation_metadata() or [])
        try:
            return self.auth.identity_for(md.get("authorization", ""))
        except AuthError as e:
            context.abort(grpc.StatusCode.UNAUTHENTICATED, str(e))

    def _authz(self, context, principal, action, resource, groups=()):
        if self.rbac is None:
            return
        from weaviate_tpu.auth.rbac import Forbidden

        try:
            self.rbac.authorize(principal, action, resource or "*",
                                groups=groups)
        except Forbidden as e:
            context.abort(grpc.StatusCode.PERMISSION_DENIED, str(e))

    # -- rpc implementations ----------------------------------------------
    def _wrap(self, name, fn):
        action, resource_fn = _RPC_AUTHZ[name]

        def run(request, context):
            principal, groups = self._principal(context)
            if name == "BatchObjects":
                if self.rbac is not None:
                    for bo in request.objects:
                        # upsert semantics: existing uuids need update_data
                        act = "create_data"
                        try:
                            if bo.uuid and self.db.has_collection(
                                    bo.collection) and \
                                    self.db.get_collection(
                                        bo.collection).exists(
                                        bo.uuid, bo.tenant):
                                act = "update_data"
                        except (KeyError, ValueError, RuntimeError):
                            pass
                        self._authz(context, principal, act,
                                    f"collections/{bo.collection}",
                                    groups=groups)
            else:
                self._authz(context, principal, action,
                            resource_fn(request), groups=groups)
            ticket, ctx = qos_admit(self.qos, name, context,
                                    tenant=getattr(request, "tenant", ""))
            try:
                with ticket, request_scope(ctx):
                    return fn(request)
            except DeadlineExceeded as e:
                context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, str(e))
            except KeyError as e:
                context.abort(grpc.StatusCode.NOT_FOUND, str(e))
            except (ValueError, TypeError) as e:
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, str(e))
            except ColdStartPending as e:
                # tiering cold-start shed (must precede the RuntimeError
                # catch it subclasses): UNAVAILABLE + retry-after trailer,
                # the gRPC analogue of REST's 503
                context.set_trailing_metadata(
                    (("retry-after", str(int(e.retry_after))),))
                context.abort(grpc.StatusCode.UNAVAILABLE, str(e))
            except RuntimeError as e:
                context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        return run

    def search(self, req: pb.SearchRequest) -> pb.SearchReply:
        t0 = time.perf_counter()
        col = self.db.get_collection(req.collection)
        flt = where_to_filter(json.loads(req.where_json)) if req.where_json else None
        limit = int(req.limit) or 10
        max_dist = float(req.max_distance) if req.max_distance > 0 else None

        reply = pb.SearchReply()

        if (len(req.near_vectors) > 0 and req.bm25_query
                and not req.use_hybrid):
            raise ValueError(
                "near_vectors and bm25_query both set without use_hybrid: "
                "ambiguous request (set use_hybrid for fusion)")
        if len(req.near_vectors) > 1 and req.rerank_query:
            # the rerank path serves ONE query per request (the explorer
            # pipeline); silently answering only near_vectors[0] would
            # drop the rest without a trace
            raise ValueError(
                "rerank_query supports a single near_vector per request; "
                "send one request per query vector")

        if any(v.token_dims for v in req.near_vectors):
            if len(req.near_vectors) > 1 or req.use_hybrid:
                raise ValueError(
                    "a token set (token_bytes) is one late-interaction "
                    "query: send one near_vector a request, without hybrid")
            v = req.near_vectors[0]
            # the ingress root is the span under way here
            annotate(query_tokens=len(v.token_bytes) // (4 * v.token_dims))

        if (len(req.near_vectors) > 1 and not req.use_hybrid
                and not req.bm25_query):
            # the TPU fast path: all query vectors in one device batch
            from weaviate_tpu.query.autocut import autocut as autocut_fn

            queries = np.stack([_np_from_vec(v) for v in req.near_vectors])
            rows = col.vector_search_batch(
                queries, k=limit + int(req.offset),
                target=req.target_vector, flt=flt, tenant=req.tenant,
                max_distance=max_dist,
            )
            with TRACER.child("grpc.encode") as span:
                hits = 0
                for row in rows:
                    qr = reply.results.add()
                    page = row[req.offset:]
                    if req.autocut > 0:
                        cut = autocut_fn([d for _, d in page],
                                         int(req.autocut))
                        page = page[:cut]
                    for obj, dist in page:
                        self._add_hit(qr, obj, distance=dist,
                                      include_vector=req.include_vector,
                                      target=req.target_vector)
                    hits += len(page)
                span.set(hits=hits)
            reply.took_seconds = time.perf_counter() - t0
            return reply

        params = QueryParams(
            collection=req.collection, tenant=req.tenant,
            limit=limit, offset=int(req.offset),
            filters=flt, autocut=int(req.autocut),
            max_distance=max_dist,
            target_vector=req.target_vector,
        )
        if req.rerank_query:
            from weaviate_tpu.query.explorer import RerankParams

            # "" module = collection default — a configured device
            # module rides the fused dispatch (docs/modules.md)
            params.rerank = RerankParams(
                query=req.rerank_query,
                property=req.rerank_property,
                module=req.rerank_module,
            )
        if req.use_hybrid:
            params.hybrid = HybridParams(
                query=req.bm25_query or None,
                vector=_np_from_vec(req.near_vectors[0])
                if req.near_vectors else None,
                # explicit presence: alpha=0.0 (pure keyword) is honored
                alpha=float(req.alpha) if req.HasField("alpha") else 0.75,
                # verbatim: an unknown name maps to INVALID_ARGUMENT via
                # query/fusion.validate_fusion's ValueError, never a 500
                fusion=req.fusion or "relativeScoreFusion",
                properties=list(req.bm25_properties) or None,
                operator=req.bm25_operator or "Or",
                minimum_match=int(req.bm25_minimum_match),
            )
        elif req.near_vectors:
            params.near_vector = _np_from_vec(req.near_vectors[0])
        elif req.near_text:
            params.near_text = req.near_text
        elif req.bm25_query:
            params.bm25_query = req.bm25_query
            params.bm25_properties = list(req.bm25_properties) or None
            params.bm25_operator = req.bm25_operator or "Or"
            params.bm25_minimum_match = int(req.bm25_minimum_match)

        result = self.explorer.get(params)
        with TRACER.child("grpc.encode", hits=len(result.hits)):
            qr = reply.results.add()
            for hit in result.hits:
                score = hit.score
                if "rerank_score" in hit.additional:
                    score = hit.additional["rerank_score"]
                self._add_hit(qr, hit.object, score=score,
                              distance=hit.distance,
                              include_vector=req.include_vector,
                              target=req.target_vector)
        reply.took_seconds = time.perf_counter() - t0
        return reply

    def _add_hit(self, qr, obj, score=None, distance=None,
                 include_vector=False, target=""):
        hit = qr.hits.add()
        hit.uuid = obj.uuid
        if score is not None:
            hit.score = float(score)
        if distance is not None:
            hit.distance = float(distance)
        hit.properties_json = json.dumps(obj.properties)
        if include_vector:
            vec = obj.named_vectors.get(target) if target else obj.vector
            if vec is not None:
                _vec_to_pb(hit.vector, vec)

    def batch_objects(self, req: pb.BatchObjectsRequest) -> pb.BatchObjectsReply:
        from weaviate_tpu.storage.objects import StorageObject

        t0 = time.perf_counter()
        reply = pb.BatchObjectsReply()
        groups: dict[tuple[str, str], list[tuple[int, StorageObject]]] = {}
        objs: list[Optional[StorageObject]] = []
        with TRACER.child("batch.build", objects=len(req.objects)):
            for i, bo in enumerate(req.objects):
                try:
                    obj = StorageObject(
                        uuid=bo.uuid,
                        collection=bo.collection,
                        properties=json.loads(bo.properties_json)
                        if bo.properties_json else {},
                        vector=_np_from_vec(bo.vector)
                        if bo.vector.values or bo.vector.token_dims
                        else None,
                        named_vectors={
                            k: _np_from_vec(v)
                            for k, v in bo.named_vectors.items()
                        },
                        tenant=bo.tenant,
                    )
                    objs.append(obj)
                    groups.setdefault(
                        (bo.collection, bo.tenant), []).append((i, obj))
                except (json.JSONDecodeError, ValueError) as e:
                    objs.append(None)
                    err = reply.errors.add()
                    err.index = i
                    err.message = str(e)
        decoded = [it for g in groups.values() for it in g]
        failed = insert_grouped(self.db, decoded)
        with TRACER.child("grpc.encode", objects=len(objs)):
            for i, msg in failed:
                err = reply.errors.add()
                err.index = i
                err.message = msg
                objs[i] = None
            reply.uuids.extend(
                o.uuid if o is not None else "" for o in objs)
        reply.took_seconds = time.perf_counter() - t0
        return reply

    def batch_delete(self, req: pb.BatchDeleteRequest) -> pb.BatchDeleteReply:
        col = self.db.get_collection(req.collection)
        flt = where_to_filter(json.loads(req.where_json))
        reply = pb.BatchDeleteReply()
        if req.dry_run:
            reply.matches = col.count_where(flt, tenant=req.tenant)
            reply.successful = 0
        else:
            n = col.delete_where(flt, tenant=req.tenant)
            reply.matches = n
            reply.successful = n
        return reply

    def tenants_get(self, req: pb.TenantsGetRequest) -> pb.TenantsGetReply:
        col = self.db.get_collection(req.collection)
        reply = pb.TenantsGetReply()
        for name, status in sorted(col.tenants().items()):
            t = reply.tenants.add()
            t.name = name
            t.activity_status = status
        return reply

    def aggregate(self, req: pb.AggregateRequest) -> pb.AggregateReply:
        col = self.db.get_collection(req.collection)
        flt = where_to_filter(json.loads(req.where_json)) if req.where_json else None
        agg = col.aggregate(
            {p: None for p in req.properties},
            flt=flt,
            group_by=req.group_by or None,
            tenant=req.tenant,
        )
        return pb.AggregateReply(result_json=json.dumps(agg))

    # -- service wiring ----------------------------------------------------
    def _generic_handler(self):
        rpcs = {
            "Search": (self.search, pb.SearchRequest),
            "BatchObjects": (self.batch_objects, pb.BatchObjectsRequest),
            "BatchDelete": (self.batch_delete, pb.BatchDeleteRequest),
            "TenantsGet": (self.tenants_get, pb.TenantsGetRequest),
            "Aggregate": (self.aggregate, pb.AggregateRequest),
        }
        handlers = {
            name: traced_unary_handler(name, self._wrap(name, fn), req_cls)
            for name, (fn, req_cls) in rpcs.items()
        }
        return grpc.method_handlers_generic_handler(SERVICE, handlers)

    def serve(self, host: str = "127.0.0.1", port: int = 0) -> int:
        """Start the server; returns the bound port. Raises on bind failure
        (grpc signals it by returning port 0)."""
        from weaviate_tpu.api.grpc_v1_compat import WeaviateV1Service

        # pool sized from the admission limiter (like the bounded REST
        # server): a fixed 16 would queue silently AHEAD of admission,
        # hiding exactly the backlog the QoS layer exists to shed
        workers = self.max_workers if self.max_workers is not None \
            else max(8, min(64, self.qos.limiter.max_limit))
        self._server = grpc.server(
            ArrivalStampingPool(max_workers=workers),
            options=[("grpc.max_receive_message_length", MAX_MESSAGE_BYTES)])
        # native TPU-first plane + the reference's public weaviate.v1
        # contract, one port (stock clients connect unchanged)
        compat = WeaviateV1Service(self.db, auth=self.auth, rbac=self.rbac,
                                   qos=self.qos)
        self._server.add_generic_rpc_handlers(
            (self._generic_handler(), compat.generic_handler()))
        bound = self._server.add_insecure_port(f"{host}:{port}")
        if bound == 0:
            raise RuntimeError(f"gRPC failed to bind {host}:{port}")
        self._server.start()
        return bound

    def shutdown(self, grace: float = 1.0):
        if self._server is not None:
            # graftlint: allow[blocking-call-without-deadline] reason=shutdown verb, not a request leg; stop(grace) already bounds in-flight handlers before the event fires
            self._server.stop(grace).wait()


class GrpcClient:
    """Minimal client over explicit method paths (no generated stubs)."""

    def __init__(self, address: str, api_key: Optional[str] = None):
        self.channel = grpc.insecure_channel(address)
        self._methods = {}
        self._metadata = (
            [("authorization", f"Bearer {api_key}")] if api_key else None)

    def _call(self, name: str, request, reply_cls,
              timeout: Optional[float] = None):
        """``timeout`` (seconds) becomes the call's gRPC deadline, which
        the server takes as the request's budget in place of its 30 s
        default — what a client sends with a request it knows will
        compile (the first search of a new shape)."""
        m = self._methods.get(name)
        if m is None:
            m = self.channel.unary_unary(
                f"/{SERVICE}/{name}",
                request_serializer=lambda msg: msg.SerializeToString(),
                response_deserializer=reply_cls.FromString,
            )
            self._methods[name] = m
        return m(request, metadata=self._metadata, timeout=timeout)

    def search(self, request: pb.SearchRequest,
               timeout: Optional[float] = None) -> pb.SearchReply:
        return self._call("Search", request, pb.SearchReply, timeout)

    def batch_objects(self, request: pb.BatchObjectsRequest,
                      timeout: Optional[float] = None
                      ) -> pb.BatchObjectsReply:
        return self._call("BatchObjects", request, pb.BatchObjectsReply,
                          timeout)

    def batch_delete(self, request: pb.BatchDeleteRequest) -> pb.BatchDeleteReply:
        return self._call("BatchDelete", request, pb.BatchDeleteReply)

    def tenants_get(self, request: pb.TenantsGetRequest) -> pb.TenantsGetReply:
        return self._call("TenantsGet", request, pb.TenantsGetReply)

    def aggregate(self, request: pb.AggregateRequest) -> pb.AggregateReply:
        return self._call("Aggregate", request, pb.AggregateReply)

    def close(self):
        self.channel.close()
