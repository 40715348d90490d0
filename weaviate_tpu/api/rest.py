"""REST API server (werkzeug WSGI), mirroring the reference's endpoint map.

Reference: ``adapters/handlers/rest/`` (go-swagger) — ``/v1/schema``,
``/v1/objects``, ``/v1/batch/*``, ``/v1/graphql``, ``/v1/nodes``,
``/v1/meta``, ``/v1/.well-known/*`` (``configure_api.go``, ``handlers_*.go``).
Wire shapes follow the reference's swagger models so its clients work
unchanged; go-swagger codegen is replaced by explicit werkzeug routing.
"""

from __future__ import annotations

import json
import math
import re
import threading
from typing import Any, Optional

import numpy as np
from werkzeug.exceptions import HTTPException

from weaviate_tpu.core.collection import TenantNotActive
from weaviate_tpu.monitoring.memwatch import MemoryPressure
from weaviate_tpu.storage.store import ShardClosed
from werkzeug.routing import Map, Rule
from werkzeug.wrappers import Request, Response

from weaviate_tpu.api.graphql import GraphQLExecutor, where_to_filter
from weaviate_tpu.api.schema_translate import class_from_rest, class_to_rest
from weaviate_tpu.auth.rbac import Forbidden as _Forbidden
from weaviate_tpu.cluster.resilience import Deadline, DeadlineExceeded
from weaviate_tpu.core.db import DB
from weaviate_tpu.serving.context import RequestContext, request_scope
from weaviate_tpu.serving.qos import QosRejected
from weaviate_tpu.tiering import ColdStartPending
from weaviate_tpu.storage.objects import StorageObject
from weaviate_tpu.version import __version__


class AuthConfig:
    """API-key authentication (reference ``usecases/auth/authentication/apikey``).

    ``api_keys``: {key: user}; ``anonymous_access``: allow unauthenticated
    requests (reference AUTHENTICATION_ANONYMOUS_ACCESS_ENABLED).
    """

    def __init__(self, api_keys: Optional[dict[str, str]] = None,
                 anonymous_access: bool = True, oidc=None):
        self.api_keys = api_keys or {}
        self.anonymous_access = anonymous_access
        self.oidc = oidc  # Optional[auth.oidc.OIDCConfig]
        self.dynamic_users = None  # Optional[auth.users.DynamicUserStore]

    def identity_for(self, header: str) -> tuple[Optional[str], list[str]]:
        """Transport-agnostic check of an Authorization header value.
        Returns (principal, groups) — principal None = anonymous allowed;
        raises AuthError otherwise. Shared by the REST and gRPC planes so
        the two can't diverge."""
        if header.startswith("Bearer "):
            key = header[len("Bearer "):].strip()
            user = self.api_keys.get(key)
            if user is not None:
                return user, []
            if self.dynamic_users is not None:
                dyn = self.dynamic_users.principal_for_key(key)
                if dyn is not None:
                    return dyn, []
            # JWT-shaped tokens fall through to OIDC (reference runs the
            # apikey and oidc middlewares side by side the same way)
            if self.oidc is not None and key.count(".") == 2:
                from weaviate_tpu.auth.oidc import OIDCError

                try:
                    return self.oidc.validate(key)
                except OIDCError as e:
                    raise AuthError(f"oidc: {e}") from e
            raise AuthError("invalid api key")
        if self.anonymous_access:
            return None, []
        raise AuthError(
            "anonymous access disabled: provide Authorization: Bearer <key>")

    def principal_for(self, header: str) -> Optional[str]:
        return self.identity_for(header)[0]

    def authenticate(self, request: Request) -> Optional[str]:
        """Sets request.principal_groups; returns principal name, or None
        when anonymous. Raises 401."""
        try:
            principal, groups = self.identity_for(
                request.headers.get("Authorization", ""))
            request.principal_groups = groups
            return principal
        except AuthError as e:
            _abort(401, str(e))


class AuthError(Exception):
    pass


class _ApiError(Exception):
    def __init__(self, status: int, message: str):
        self.status = status
        self.message = message


def _abort(status: int, message: str):
    raise _ApiError(status, message)


def _json_response(data: Any, status: int = 200) -> Response:
    return Response(json.dumps(data), status=status,
                    content_type="application/json")


def _obj_to_rest(obj: StorageObject, include_vector: bool = True) -> dict:
    out = {
        "class": obj.collection,
        "id": obj.uuid,
        "properties": obj.properties,
        "creationTimeUnix": obj.creation_time_ms,
        "lastUpdateTimeUnix": obj.update_time_ms,
    }
    if obj.tenant:
        out["tenant"] = obj.tenant
    if include_vector and obj.vector is not None:
        out["vector"] = np.asarray(obj.vector).tolist()
    if obj.named_vectors:
        out["vectors"] = {k: np.asarray(v).tolist()
                          for k, v in obj.named_vectors.items()}
    return out


def _obj_from_rest(d: dict) -> StorageObject:
    vec = d.get("vector")
    return StorageObject(
        uuid=d.get("id", ""),
        collection=d.get("class", ""),
        properties=d.get("properties", {}) or {},
        vector=None if vec is None else np.asarray(vec, np.float32),
        named_vectors={
            k: np.asarray(v, np.float32)
            for k, v in (d.get("vectors") or {}).items()
        },
        tenant=d.get("tenant", ""),
    )


def _consistency(request) -> str:
    cl = request.args.get("consistency_level", "QUORUM").upper()
    if cl not in ("ONE", "QUORUM", "ALL"):
        # a typo'd level must not silently downgrade a requested ALL
        _abort(422, f"invalid consistency_level {cl!r}; "
                    "expected ONE | QUORUM | ALL")
    return cl


class RestAPI:
    # endpoints that must answer even under full overload: health probes,
    # metrics scrapes, and the debug/ops plane an operator needs to SEE
    # the overload (shedding your own observability is how outages hide)
    _QOS_EXEMPT = frozenset({
        "root", "meta", "ready", "live", "metrics", "openapi",
        "oidc_discovery", "pprof_profile", "pprof_heap", "debug_traces",
        "debug_config", "debug_telemetry", "debug_cluster",
        "debug_compile", "debug_planner", "cluster_autoscale",
    })
    # endpoint -> admission lane; anything unlisted is background
    # (schema/authz/backup/replication mutations: important, not latency-
    # sensitive, and never allowed to crowd out interactive search)
    _QOS_LANES = {
        "graphql": "interactive", "graphql_batch": "interactive",
        "objects": "interactive", "object": "interactive",
        "object_by_id": "interactive", "objects_validate": "interactive",
        "object_references": "interactive",
        "object_by_id_references": "interactive",
        "batch_objects": "batch", "batch_references": "batch",
        "debug_reindex": "batch",
    }

    def __init__(self, db: DB, auth: Optional[AuthConfig] = None,
                 rbac=None, backup_root: Optional[str] = None,
                 cluster=None, qos=None):
        self.db = db
        # admission controller shared with the gRPC planes via the DB by
        # default (one ceiling for the process); pass qos= to isolate
        self.qos = qos if qos is not None else db.qos
        self.auth = auth or AuthConfig()
        self.rbac = rbac  # RBACController or None (authz disabled)
        # Optional ClusterNode: object CRUD then rides the replicated
        # data plane (2PC writes, consistency-level reads) instead of the
        # local shard, and schema mutations go through raft — REST served
        # from any cluster worker behaves like the reference's clustered
        # REST tier. Search/aggregate endpoints still answer from the
        # local replica view (every node holds its raft-replicated
        # schema; scatter-gather search stays on the ctl/cluster plane).
        self.cluster = cluster
        self.graphql = GraphQLExecutor(db, cluster=cluster)
        from weaviate_tpu.backup.handler import BackupHandler

        self.backups = BackupHandler(db)
        self.backup_root = backup_root or f"{db.root}/backups"
        self.url_map = Map([
            Rule("/", endpoint="root", methods=["GET"]),
            Rule("/v1", endpoint="root", methods=["GET"]),
            Rule("/v1/meta", endpoint="meta", methods=["GET"]),
            Rule("/v1/.well-known/openid-configuration",
                 endpoint="oidc_discovery", methods=["GET"]),
            Rule("/v1/.well-known/ready", endpoint="ready", methods=["GET"]),
            Rule("/v1/.well-known/live", endpoint="live", methods=["GET"]),
            Rule("/v1/.well-known/openapi", endpoint="openapi",
                 methods=["GET"]),
            Rule("/v1/schema", endpoint="schema", methods=["GET", "POST"]),
            Rule("/v1/aliases", endpoint="aliases",
                 methods=["GET", "POST"]),
            Rule("/v1/aliases/<alias>", endpoint="alias_one",
                 methods=["GET", "PUT", "DELETE"]),
            Rule("/v1/schema/<cls>", endpoint="schema_class",
                 methods=["GET", "PUT", "DELETE"]),
            Rule("/v1/schema/<cls>/properties", endpoint="schema_properties",
                 methods=["POST"]),
            Rule("/v1/schema/<cls>/shards", endpoint="shards",
                 methods=["GET"]),
            Rule("/v1/schema/<cls>/shards/<shard>", endpoint="shard_status",
                 methods=["PUT"]),
            Rule("/v1/schema/<cls>/tenants/<tname>", endpoint="tenant_one",
                 methods=["GET", "HEAD"]),
            Rule("/v1/schema/<cls>/tenants", endpoint="tenants",
                 methods=["GET", "POST", "PUT", "DELETE"]),
            Rule("/v1/objects", endpoint="objects", methods=["GET", "POST"]),
            Rule("/v1/objects/validate", endpoint="objects_validate",
                 methods=["POST"]),
            # uuid-only legacy routes (reference /objects/{id}): the
            # class is resolved by uuid scan across collections
            Rule("/v1/objects/<uuid>", endpoint="object_by_id",
                 methods=["GET", "HEAD", "PUT", "PATCH", "DELETE"]),
            Rule("/v1/objects/<uuid>/references/<prop>",
                 endpoint="object_by_id_references",
                 methods=["POST", "PUT", "DELETE"]),
            Rule("/v1/objects/<cls>/<uuid>", endpoint="object",
                 methods=["GET", "PUT", "PATCH", "DELETE", "HEAD"]),
            Rule("/v1/batch/objects", endpoint="batch_objects",
                 methods=["POST", "DELETE"]),
            Rule("/v1/batch/references", endpoint="batch_references",
                 methods=["POST"]),
            Rule("/v1/objects/<cls>/<uuid>/references/<prop>",
                 endpoint="object_references",
                 methods=["POST", "PUT", "DELETE"]),
            Rule("/v1/graphql", endpoint="graphql", methods=["POST"]),
            Rule("/v1/graphql/batch", endpoint="graphql_batch",
                 methods=["POST"]),
            Rule("/v1/nodes", endpoint="nodes", methods=["GET"]),
            Rule("/v1/nodes/<cls>", endpoint="nodes_class",
                 methods=["GET"]),
            Rule("/v1/cluster/statistics", endpoint="cluster_statistics",
                 methods=["GET"]),
            Rule("/v1/cluster/rebalance", endpoint="cluster_rebalance",
                 methods=["GET", "POST"]),
            Rule("/v1/cluster/drain/<node>", endpoint="cluster_drain",
                 methods=["POST"]),
            Rule("/v1/cluster/autoscale", endpoint="cluster_autoscale",
                 methods=["GET", "POST"]),
            Rule("/v1/replication/replicate", endpoint="replicate",
                 methods=["POST"]),
            Rule("/v1/replication/replicate/list",
                 endpoint="replicate_list", methods=["GET"]),
            Rule("/v1/replication/replicate/force-delete",
                 endpoint="replicate_force_delete", methods=["POST"]),
            Rule("/v1/replication/replicate/<op_id>",
                 endpoint="replicate_op", methods=["GET"]),
            Rule("/v1/replication/replicate/<op_id>/cancel",
                 endpoint="replicate_cancel", methods=["POST"]),
            Rule("/v1/replication/sharding-state",
                 endpoint="sharding_state", methods=["GET"]),
            Rule("/v1/replication/scale", endpoint="replication_scale",
                 methods=["GET"]),
            Rule("/v1/tasks", endpoint="tasks_list", methods=["GET"]),
            Rule("/metrics", endpoint="metrics", methods=["GET"]),
            # pprof-shaped profiling surface (reference serves Go pprof
            # on the metrics port; here cProfile/tracemalloc equivalents)
            Rule("/debug/pprof/profile", endpoint="pprof_profile",
                 methods=["GET"]),
            Rule("/debug/pprof/heap", endpoint="pprof_heap",
                 methods=["GET"]),
            Rule("/v1/backups/<backend>", endpoint="backup_create",
                 methods=["POST"]),
            Rule("/v1/backups/<backend>/<backup_id>",
                 endpoint="backup_status", methods=["GET"]),
            Rule("/v1/backups/<backend>/<backup_id>/restore",
                 endpoint="backup_restore", methods=["POST"]),
            Rule("/v1/authz/roles", endpoint="authz_roles",
                 methods=["GET", "POST"]),
            Rule("/v1/authz/roles/<name>", endpoint="authz_role",
                 methods=["GET", "DELETE"]),
            Rule("/v1/authz/roles/<name>/add-permissions",
                 endpoint="authz_role_add_permissions", methods=["POST"]),
            Rule("/v1/authz/roles/<name>/remove-permissions",
                 endpoint="authz_role_remove_permissions",
                 methods=["POST"]),
            Rule("/v1/authz/roles/<name>/has-permission",
                 endpoint="authz_role_has_permission", methods=["POST"]),
            Rule("/v1/authz/roles/<name>/users",
                 endpoint="authz_role_users", methods=["GET"]),
            Rule("/v1/authz/roles/<name>/user-assignments",
                 endpoint="authz_role_user_assignments", methods=["GET"]),
            Rule("/v1/authz/users/<user>/roles/<user_type>",
                 endpoint="authz_user_roles_typed", methods=["GET"]),
            Rule("/v1/authz/groups/<group_type>", endpoint="authz_groups",
                 methods=["GET"]),
            Rule("/v1/authz/groups/<gid>/assign",
                 endpoint="authz_group_assign", methods=["POST"]),
            Rule("/v1/authz/groups/<gid>/revoke",
                 endpoint="authz_group_revoke", methods=["POST"]),
            Rule("/v1/authz/groups/<gid>/roles/<group_type>",
                 endpoint="authz_group_roles", methods=["GET"]),
            Rule("/v1/authz/roles/<name>/group-assignments",
                 endpoint="authz_role_group_assignments",
                 methods=["GET"]),
            Rule("/v1/authz/users/<user>/assign", endpoint="authz_assign",
                 methods=["POST"]),
            Rule("/v1/authz/users/<user>/revoke", endpoint="authz_revoke",
                 methods=["POST"]),
            Rule("/v1/authz/users/<user>/roles", endpoint="authz_user_roles",
                 methods=["GET"]),
            # dynamic db users (reference /users/db + own-info surface)
            Rule("/v1/users/own-info", endpoint="users_own_info",
                 methods=["GET"]),
            Rule("/v1/users/db", endpoint="users_db", methods=["GET"]),
            Rule("/v1/users/db/<user_id>", endpoint="users_db_user",
                 methods=["GET", "POST", "DELETE"]),
            Rule("/v1/users/db/<user_id>/rotate-key",
                 endpoint="users_db_rotate", methods=["POST"]),
            Rule("/v1/users/db/<user_id>/activate",
                 endpoint="users_db_activate", methods=["POST"]),
            Rule("/v1/users/db/<user_id>/deactivate",
                 endpoint="users_db_deactivate", methods=["POST"]),
            # reference swagger publishes this path WITH the trailing
            # slash; accept both without a 308 redirect (POST bodies
            # don't survive redirects in some clients)
            Rule("/v1/classifications", endpoint="classifications",
                 methods=["POST"], strict_slashes=False),
            Rule("/v1/classifications/<cid>", endpoint="classification",
                 methods=["GET"]),
            # debug/ops plane (reference adapters/handlers/debug + runtime
            # config + telemetry inspection)
            Rule("/v1/debug/cluster", endpoint="debug_cluster",
                 methods=["GET"]),
            Rule("/v1/debug/traces", endpoint="debug_traces",
                 methods=["GET", "DELETE"]),
            Rule("/v1/debug/config", endpoint="debug_config",
                 methods=["GET"]),
            Rule("/v1/debug/telemetry", endpoint="debug_telemetry",
                 methods=["GET"]),
            Rule("/v1/debug/compile", endpoint="debug_compile",
                 methods=["GET"]),
            Rule("/v1/debug/planner", endpoint="debug_planner",
                 methods=["GET"]),
            Rule("/v1/debug/reindex/<cls>", endpoint="debug_reindex",
                 methods=["POST"]),
        ])
        self.telemeter = None  # attached by server.py when enabled
        # eager: a lazy per-request init would race two first requests into
        # two managers, orphaning one run's id
        from weaviate_tpu.usecases.classification import ClassificationManager

        self._classifications = ClassificationManager(db)
        # dynamic db users back the same Bearer-key auth chain static env
        # keys use (reference apikey dynamic store)
        from weaviate_tpu.auth.users import DynamicUserStore

        reserved = set(self.auth.api_keys.values())
        if rbac is not None:
            reserved |= set(getattr(rbac, "root_users", ()))
        self.users = DynamicUserStore(f"{db.root}/users.db",
                                      reserved=reserved)
        self.auth.dynamic_users = self.users
        self._server = None
        self._thread = None

    # -- WSGI --------------------------------------------------------------
    def __call__(self, environ, start_response):
        request = Request(environ)
        span = None
        try:
            adapter = self.url_map.bind_to_environ(environ)
            endpoint, args = adapter.match()
            request.principal = self.auth.authenticate(request)
            handler = getattr(self, f"on_{endpoint}")
            from weaviate_tpu.monitoring.tracing import TRACER

            # ingress span: continues an incoming W3C traceparent (and
            # its sampled flag) or mints a fresh trace under the
            # tracing_sample_rate knob; the id is echoed back in the
            # response header so clients can fetch their own trace
            span = TRACER.ingress(
                f"rest.{endpoint}",
                traceparent=request.headers.get("traceparent", ""),
                method=request.method, path=request.path)
            with span:
                response = self._dispatch_qos(request, endpoint,
                                              handler, args)
        except _Forbidden as e:
            response = _json_response(
                {"error": [{"message": str(e)}]}, 403)
        except QosRejected as e:
            # explicit load shed: the client knows WHEN to come back
            response = _json_response(
                {"error": [{"message": str(e)}]}, 429)
            response.headers["Retry-After"] = str(
                int(math.ceil(e.retry_after)))
        except DeadlineExceeded as e:
            # end-to-end budget spent (at admission, in the queue, or
            # mid-execution) — distinct from the 503 raft TimeoutError
            response = _json_response(
                {"error": [{"message": str(e)}]}, 504)
        except _ApiError as e:
            response = _json_response(
                {"error": [{"message": e.message}]}, e.status)
        except HTTPException as e:
            response = _json_response(
                {"error": [{"message": e.description}]},
                e.code or 500)
        except (KeyError, ValueError, TypeError,
                TenantNotActive, ShardClosed) as e:
            # TenantNotActive / ShardClosed: inactive tenant or a read
            # racing a freeze — client errors, retriable once activated
            response = _json_response(
                {"error": [{"message": str(e)}]}, 422)
        except MemoryPressure as e:
            # back-pressure, not failure: clients should retry later
            response = _json_response(
                {"error": [{"message": str(e)}]}, 503)
        except TimeoutError as e:
            # raft apply/forward deadline (clustered schema mutation)
            response = _json_response(
                {"error": [{"message": str(e)}]}, 503)
        except ColdStartPending as e:
            # tiering cold-start shed: the tenant's promotion is still in
            # flight past the request deadline — 503 with a Retry-After
            # sized from the promotion-latency EWMA (docs/tiering.md)
            response = _json_response(
                {"error": [{"message": str(e)}]}, 503)
            response.headers["Retry-After"] = str(
                int(math.ceil(e.retry_after)))
        except RuntimeError as e:
            # ReplicationError subclasses RuntimeError: consistency level
            # not met / replicas unreachable — a structured 503 the client
            # can retry, never a bare werkzeug 500
            from weaviate_tpu.cluster.node import ReplicationError

            status = 503 if isinstance(e, ReplicationError) else 500
            response = _json_response(
                {"error": [{"message": str(e)}]}, status)
        if span is not None and span.sampled:
            # traceparent OUT: error responses carry it too — the 429/504
            # shed is exactly the request whose trace an operator wants
            response.headers["traceparent"] = span.traceparent
        return response(environ, start_response)

    def _dispatch_qos(self, request: Request, endpoint: str, handler,
                      args: dict) -> Response:
        """Admission control + end-to-end deadline for one request.

        The deadline is minted HERE (``X-Request-Timeout`` seconds, else
        the ``serving_default_timeout_s`` knob) and installed in the
        serving request scope, so collection search, the coalescing
        dispatcher, and the cluster replica fan-out all clamp to the same
        budget — no per-layer timeout arithmetic."""
        if endpoint in self._QOS_EXEMPT or not self.qos.enabled():
            return handler(request, **args)
        lane = self._QOS_LANES.get(endpoint, "background")
        from weaviate_tpu.utils.runtime_config import (
            SERVING_DEFAULT_TIMEOUT_S,
        )

        budget = SERVING_DEFAULT_TIMEOUT_S.get()
        hdr = request.headers.get("X-Request-Timeout", "")
        if hdr:
            try:
                budget = min(float(hdr), 600.0)
            except ValueError:
                budget = None
            # nan would make the deadline never expire AND never satisfy
            # the wait math; <=0 can only mean a client bug
            if budget is None or not math.isfinite(budget) or budget <= 0:
                _abort(400, f"invalid X-Request-Timeout {hdr!r}: "
                            "expected positive seconds")
        deadline = Deadline(budget, op=f"rest.{endpoint}")
        tenant = (request.args.get("tenant", "")
                  or request.headers.get("X-Tenant", ""))
        from weaviate_tpu.monitoring import tracing

        # qos.queue: the admission wait as its own span — a shed (429) or
        # queued-past-deadline (504) exits it with ERROR status, so "where
        # did my request die" is answerable from the trace alone
        with tracing.TRACER.span("qos.queue", lane=lane,
                                 tenant=tenant) as qspan:
            ticket = self.qos.acquire(lane, tenant=tenant,
                                      deadline=deadline)
            qspan.set(queue_wait_ms=round(ticket.queue_wait * 1000, 3))
        with ticket:
            ctx = RequestContext(deadline=deadline, lane=lane,
                                 tenant=tenant,
                                 queue_wait_s=ticket.queue_wait,
                                 trace=tracing.current_span())
            with request_scope(ctx):
                return handler(request, **args)

    def _write_action(self, obj: StorageObject) -> str:
        """Puts are upserts: writing an EXISTING uuid needs update_data,
        not just create_data (else create-only principals could overwrite)."""
        try:
            if obj.uuid and obj.collection \
                    and self.db.has_collection(obj.collection) \
                    and self.db.get_collection(obj.collection).exists(
                        obj.uuid, obj.tenant):
                return "update_data"
        except (KeyError, ValueError, RuntimeError):
            pass
        return "create_data"

    def _authz(self, request: Request, action: str,
               resource: str = "*") -> None:
        """RBAC check (no-op when RBAC disabled, like the reference with
        AUTHORIZATION_ADMINLIST/RBAC off)."""
        if self.rbac is not None:
            self.rbac.authorize(getattr(request, "principal", None),
                                action, resource,
                                groups=getattr(request, "principal_groups",
                                               ()))

    def _body(self, request: Request) -> dict:
        try:
            return json.loads(request.get_data(as_text=True) or "{}")
        except json.JSONDecodeError as e:
            _abort(400, f"invalid json: {e}")

    # -- meta / health -----------------------------------------------------
    def on_meta(self, request):
        return _json_response({
            "hostname": request.host,
            "version": __version__,
            "modules": self.db.modules.list() if self.db.modules else {},
        })

    def on_openapi(self, request):
        """OpenAPI 3 spec derived from the LIVE url map (api/openapi.py)
        — the reference serves its generated swagger the same way
        (``embedded_spec.go``); here the routing table is the source of
        truth so route/spec drift is impossible. Built once: the url
        map is fixed after __init__."""
        spec = getattr(self, "_openapi_spec", None)
        if spec is None:
            from weaviate_tpu.api.openapi import build_spec

            spec = self._openapi_spec = build_spec(
                self.url_map, __version__)
        return _json_response(spec)

    def on_root(self, request):
        return _json_response({
            "links": [
                {"href": "/v1/meta", "name": "Meta information"},
                {"href": "/v1/schema", "name": "Schema"},
                {"href": "/v1/objects", "name": "Objects"},
                {"href": "/v1/graphql", "name": "GraphQL"},
                {"href": "/v1/.well-known/openapi", "name": "OpenAPI"},
            ]})

    def on_oidc_discovery(self, request):
        """OIDC discovery (reference /.well-known/openid-configuration):
        points clients at the configured issuer; 404 when OIDC is off."""
        oidc = getattr(self.auth, "oidc", None)
        if oidc is None:
            _abort(404, "OIDC is not configured")
        issuer = getattr(oidc, "issuer", "") or ""
        return _json_response({
            "href": issuer.rstrip("/") + "/.well-known/openid-configuration",
            "clientID": getattr(oidc, "client_id", "") or "",
        })

    def on_ready(self, request):
        # ``warming``: true while the shape-bucket prewarm driver is
        # compiling the serving lattice (docs/compile_cache.md) — the
        # node answers queries (they just pay the compile), so readiness
        # stays 200 and orchestrators that want compile-free first
        # queries gate on the field instead
        from weaviate_tpu.utils import prewarm

        return _json_response({"warming": prewarm.warming()})

    def on_live(self, request):
        return Response(status=200)

    # -- schema ------------------------------------------------------------
    def on_schema(self, request):
        if request.method == "GET":
            self._authz(request, "read_schema")
            return _json_response({"classes": [
                class_to_rest(self.db.get_collection(n).config)
                for n in self.db.collections()
            ]})
        self._authz(request, "create_schema")
        body = self._body(request)
        cfg = class_from_rest(body)
        try:
            if self.cluster is not None:
                self.cluster.create_collection(cfg)  # raft-replicated
            else:
                self.db.create_collection(cfg)
        except ValueError as e:
            _abort(422, str(e))
        return _json_response(class_to_rest(cfg))

    # -- aliases (reference /v1/aliases) -----------------------------------
    def on_aliases(self, request):
        if request.method == "GET":
            self._authz(request, "read_schema")
            target = request.args.get("class", "")
            return _json_response({"aliases": [
                {"alias": a, "class": t}
                for a, t in self.db.aliases(target).items()]})
        self._authz(request, "create_schema")
        body = self._body(request)
        alias, target = body.get("alias", ""), body.get("class", "")
        if not alias or not target:
            _abort(422, "alias and class are required")
        self._set_alias(alias, target)
        return _json_response({"alias": alias, "class": target})

    def _set_alias(self, alias: str, target: str) -> None:
        """Shared POST/PUT alias write with MODE-UNIFORM status codes:
        a missing target class is 404 in both single-node and cluster
        paths (the FSM flattens KeyError into ok:false, which would
        otherwise surface as 422 only when clustered)."""
        if target not in self.db.collections():
            _abort(404, f"collection {target!r} not found")
        try:
            if self.cluster is not None:
                self.cluster.set_alias(alias, target)
            else:
                self.db.set_alias(alias, target)
        except KeyError as e:
            _abort(404, str(e))
        except ValueError as e:
            _abort(422, str(e))

    def on_alias_one(self, request, alias):
        if request.method == "GET":
            self._authz(request, "read_schema")
            target = self.db.aliases().get(alias)
            if target is None:
                _abort(404, f"alias {alias!r} not found")
            return _json_response({"alias": alias, "class": target})
        if request.method == "PUT":
            # re-point the alias at a new class (reference alias update)
            self._authz(request, "update_schema")
            if alias not in self.db.aliases():
                _abort(404, f"alias {alias!r} not found")
            target = self._body(request).get("class", "")
            if not target:
                _abort(422, "class is required")
            self._set_alias(alias, target)
            return _json_response({"alias": alias, "class": target})
        self._authz(request, "delete_schema")
        if self.cluster is not None:
            self.cluster.delete_alias(alias)
        else:
            self.db.delete_alias(alias)
        return Response(status=204)

    def on_schema_class(self, request, cls):
        if request.method == "GET":
            self._authz(request, "read_schema", f"collections/{cls}")
            if not self.db.has_collection(cls):
                _abort(404, f"class {cls!r} not found")
            return _json_response(
                class_to_rest(self.db.get_collection(cls).config))
        if request.method == "PUT":
            # live class update: only mutable fields (reference
            # schema update validation + hnsw/config_update.go)
            self._authz(request, "update_schema", f"collections/{cls}")
            if not self.db.has_collection(cls):
                _abort(404, f"class {cls!r} not found")
            from weaviate_tpu.api.schema_translate import (
                update_class_from_rest,
            )

            try:
                new_cfg = update_class_from_rest(
                    self.db.get_collection(cls).config,
                    self._body(request))
                if self.cluster is not None:
                    self.cluster.update_collection(new_cfg)
                    # answer from the COMMITTED config: a follower's
                    # local FSM apply may lag the leader by a heartbeat
                    return _json_response(class_to_rest(new_cfg))
                self.db.update_collection(cls, new_cfg)
            except ValueError as e:
                _abort(422, str(e))
            return _json_response(
                class_to_rest(self.db.get_collection(cls).config))
        self._authz(request, "delete_schema", f"collections/{cls}")
        if self.cluster is not None:
            self.cluster.delete_collection(cls)
        else:
            self.db.delete_collection(cls)
        return Response(status=200)

    def on_schema_properties(self, request, cls):
        self._authz(request, "update_schema", f"collections/{cls}")
        from weaviate_tpu.api.schema_translate import property_from_rest

        body = self._body(request)
        prop = property_from_rest(body)
        try:
            if self.cluster is not None:
                r = self.cluster.apply({"op": "add_property", "class": cls,
                                        "property": body})
                if not r.get("ok"):
                    raise ValueError(r.get("error", "add_property failed"))
            else:
                self.db.add_property(cls, prop)
        except (KeyError, ValueError) as e:
            _abort(422, str(e))
        return _json_response(body)

    def on_tenants(self, request, cls):
        self._authz(request,
                    "read_tenants" if request.method == "GET"
                    else "update_tenants", f"collections/{cls}")
        col = self.db.get_collection(cls)
        if request.method == "GET":
            return _json_response([
                {"name": n, "activityStatus": s}
                for n, s in sorted(col.tenants().items())
            ])
        body = self._body(request)
        tenants = body if isinstance(body, list) else [body]
        if request.method == "POST":
            for t in tenants:
                col.add_tenant(t["name"], t.get("activityStatus", "HOT"))
        elif request.method == "PUT":
            for t in tenants:
                col.set_tenant_status(t["name"], t["activityStatus"])
        else:  # DELETE
            for t in tenants:
                name = t if isinstance(t, str) else t["name"]
                col.remove_tenant(name)
        return _json_response(tenants)

    def on_tenant_one(self, request, cls, tname):
        """GET/HEAD one tenant (reference
        /schema/{className}/tenants/{tenantName})."""
        self._authz(request, "read_tenants", f"collections/{cls}")
        col = self.db.get_collection(cls)
        status = col.tenants().get(tname)
        if status is None:
            _abort(404, f"tenant {tname!r} not found")
        if request.method == "HEAD":
            return Response(status=200)
        return _json_response({"name": tname, "activityStatus": status})

    def on_shards(self, request, cls):
        """Shard list + status (reference /schema/{className}/shards)."""
        self._authz(request, "read_schema", f"collections/{cls}")
        col = self.db.get_collection(cls)
        return _json_response(col.shard_statuses())

    def on_shard_status(self, request, cls, shard):
        """PUT status READY|READONLY (reference shards/{shardName});
        READONLY shards reject writes atomically at the batch level."""
        self._authz(request, "update_schema", f"collections/{cls}")
        col = self.db.get_collection(cls)
        body = self._body(request)
        try:
            status = col.set_shard_status(shard, body.get("status", ""))
        except KeyError as e:
            _abort(404, str(e))
        return _json_response({"status": status})

    # -- objects -----------------------------------------------------------
    def _resolve_uuid_class(self, uuid: str) -> str:
        """Class for a uuid-only legacy route (reference /objects/{id}):
        scan collections; 404 when the uuid exists nowhere."""
        for name in self.db.collections():
            col = self.db.get_collection(name)
            try:
                if col.exists(uuid):
                    return name
            except (KeyError, ValueError, TenantNotActive):
                continue
        _abort(404, f"object {uuid!r} not found")

    def on_object_by_id(self, request, uuid):
        return self.on_object(request, self._resolve_uuid_class(uuid),
                              uuid)

    def on_object_by_id_references(self, request, uuid, prop):
        return self.on_object_references(
            request, self._resolve_uuid_class(uuid), uuid, prop)

    def on_objects_validate(self, request):
        """Validate an object without writing it (reference
        /objects/validate): schema + dims checks, 200 on valid."""
        body = self._body(request)
        obj = _obj_from_rest(body)
        if not obj.collection:
            _abort(422, "class required")
        try:
            col = self.db.get_collection(obj.collection)
        except KeyError as e:
            _abort(422, str(e))
        try:
            col.validate_object(obj)
        except (KeyError, ValueError) as e:
            _abort(422, str(e))
        return Response(status=200)

    def on_objects(self, request):
        if request.method == "POST":
            body = self._body(request)
            obj = _obj_from_rest(body)
            if not obj.collection:
                _abort(422, "class required")
            self._authz(request, self._write_action(obj),
                        f"collections/{obj.collection}")
            from weaviate_tpu.schema.auto_schema import ensure_schema

            ensure_schema(self.cluster or self.db, obj.collection,
                          [obj.properties])
            col = self.db.get_collection(obj.collection)
            if self.cluster is not None:
                self.cluster.put_batch(obj.collection, [obj],
                                       tenant=obj.tenant,
                                       consistency=_consistency(request))
            else:
                col.put(obj, tenant=obj.tenant)
            return _json_response(_obj_to_rest(obj))
        cls = request.args.get("class")
        if not cls:
            _abort(422, "class query param required")
        self._authz(request, "read_data", f"collections/{cls}")
        col = self.db.get_collection(cls)
        limit = int(request.args.get("limit", 25))
        offset = int(request.args.get("offset", 0))
        tenant = request.args.get("tenant", "")
        after = request.args.get("after")  # None when absent; "" = start
        if after is not None and offset:
            _abort(422, "offset cannot combine with the after cursor")
        objs = col.objects_page(limit=limit, offset=offset, tenant=tenant,
                                after=after)
        return _json_response({
            "objects": [_obj_to_rest(o) for o in objs],
            "totalResults": col.count(tenant=tenant),
        })

    def on_object(self, request, cls, uuid):
        action = {"GET": "read_data", "HEAD": "read_data",
                  "DELETE": "delete_data"}.get(request.method, "update_data")
        self._authz(request, action, f"collections/{cls}")
        col = self.db.get_collection(cls)
        tenant = request.args.get("tenant", "")

        def _read(u):
            # clustered reads go through the finder (digest reads at the
            # requested consistency + read-repair); local otherwise
            if self.cluster is not None:
                return self.cluster.get(cls, u, tenant=tenant,
                                        consistency=_consistency(request))
            return col.get(u, tenant)

        if request.method == "HEAD":
            found = (self.cluster.exists(cls, uuid, tenant=tenant,
                                         consistency=_consistency(request))
                     if self.cluster is not None
                     else col.exists(uuid, tenant))
            return Response(status=204 if found else 404)
        if request.method == "GET":
            obj = _read(uuid)
            if obj is None:
                _abort(404, f"object {uuid} not found")
            return _json_response(_obj_to_rest(obj))
        if request.method == "DELETE":
            if self.cluster is not None:
                n = self.cluster.delete(cls, [uuid], tenant=tenant,
                                        consistency=_consistency(request))
            else:
                n = col.delete([uuid], tenant)
            return Response(status=204 if n else 404)
        body = self._body(request)
        existing = _read(uuid)
        if request.method == "PATCH":  # merge
            if existing is None:
                _abort(404, f"object {uuid} not found")
            merged = dict(existing.properties)
            merged.update(body.get("properties", {}) or {})
            body = {**body, "properties": merged}
            if "vector" not in body and existing.vector is not None:
                body["vector"] = existing.vector.tolist()
            if "vectors" not in body and existing.named_vectors:
                body["vectors"] = {k: np.asarray(v).tolist()
                                   for k, v in existing.named_vectors.items()}
        body["id"] = uuid
        body.setdefault("class", cls)
        obj = _obj_from_rest(body)
        obj.tenant = tenant or obj.tenant
        # updates can introduce new properties too (reference auto-schema
        # runs on update/merge, not only create)
        from weaviate_tpu.schema.auto_schema import ensure_schema

        ensure_schema(self.cluster or self.db, cls, [obj.properties])
        if self.cluster is not None:
            self.cluster.put_batch(cls, [obj], tenant=obj.tenant,
                                   consistency=_consistency(request))
        else:
            col.put(obj, tenant=obj.tenant)
        return _json_response(_obj_to_rest(obj))

    # -- batch -------------------------------------------------------------
    _UUID_RE = re.compile(
        r"^[0-9a-fA-F]{8}-[0-9a-fA-F]{4}-[0-9a-fA-F]{4}"
        r"-[0-9a-fA-F]{4}-[0-9a-fA-F]{12}$")

    @classmethod
    def _parse_beacon(cls_, beacon: str) -> tuple[str, str, str]:
        """weaviate://localhost[/Class]/uuid[/prop] → (class, uuid, prop).
        The uuid is detected by SHAPE, not capitalization — uppercase hex
        uuids are valid RFC 4122 and several clients emit them."""
        if not beacon.startswith("weaviate://"):
            raise ValueError(f"invalid beacon {beacon!r}")
        parts = [p for p in
                 beacon[len("weaviate://"):].split("/")[1:] if p]
        cls = uuid = prop = ""
        for p in parts:
            if cls_._UUID_RE.match(p):
                if uuid:
                    raise ValueError(f"invalid beacon {beacon!r}")
                uuid = p
            elif not uuid:
                cls = p
            else:
                prop = p
        if not uuid:
            raise ValueError(f"invalid beacon {beacon!r}")
        return cls, uuid, prop

    def on_batch_references(self, request):
        """Reference ``batch_references_add.go``: [{from, to}] where from
        is weaviate://localhost/SourceClass/uuid/refProp and to addresses
        the target object."""
        body = self._body(request)
        if not isinstance(body, list):
            _abort(422, "expected a JSON array of {from, to} references")
        refs = body
        tenant = request.args.get("tenant", "")
        results = []
        for i, r in enumerate(refs):
            try:
                src_cls, src_id, prop = self._parse_beacon(r["from"])
                if not src_cls or not prop:
                    raise ValueError(
                        "from beacon needs class and property")
                self._authz(request, "update_data",
                            f"collections/{src_cls}")
                col = self.db.get_collection(src_cls)
                col.add_reference(src_id, prop, r["to"], tenant=tenant)
                results.append({"result": {"status": "SUCCESS"}})
            except (KeyError, ValueError) as e:
                results.append({"result": {
                    "status": "FAILED",
                    "errors": {"error": [{"message": str(e)}]}}})
        return _json_response(results)

    def on_object_references(self, request, cls, uuid, prop):
        """Single-object reference mutations (reference objects API
        /references/{propertyName}: POST add, PUT replace, DELETE)."""
        self._authz(request, "update_data", f"collections/{cls}")
        body = self._body(request)
        tenant = request.args.get("tenant", "")
        try:
            col = self.db.get_collection(cls)
        except KeyError:
            _abort(404, f"class {cls!r} not found")
        # body-shape errors are 422; only a missing object/class is 404
        if request.method == "PUT":
            if not isinstance(body, list) or any(
                    "beacon" not in b for b in body):
                _abort(422, "expected a JSON array of {beacon} entries")
            beacons = [b["beacon"] for b in body]
        else:
            if not isinstance(body, dict) or "beacon" not in body:
                _abort(422, "expected a JSON object with a beacon")
        try:
            if request.method == "POST":
                col.add_reference(uuid, prop, body["beacon"],
                                  tenant=tenant)
            elif request.method == "PUT":
                col.replace_references(uuid, prop, beacons, tenant=tenant)
            else:
                col.delete_reference(uuid, prop, body["beacon"],
                                     tenant=tenant)
        except KeyError as e:
            _abort(404, str(e))
        return Response(status=204)

    def on_batch_objects(self, request):
        body = self._body(request)
        if request.method == "DELETE":
            self._authz(request, "delete_data",
                        f"collections/{body.get('match', {}).get('class', '*')}")
            # reference batch_delete.go: {match: {class, where}, output, dryRun}
            match = body.get("match", {})
            cls = match.get("class")
            if not cls:
                _abort(422, "match.class required")
            col = self.db.get_collection(cls)
            flt = where_to_filter(match.get("where", {}))
            tenant = body.get("tenant", "") or request.args.get("tenant", "")
            if body.get("dryRun"):
                matches = col.count_where(flt, tenant=tenant)
                deleted = 0
            else:
                matches = deleted = col.delete_where(flt, tenant=tenant)
            return _json_response({
                "match": match,
                "results": {"matches": matches, "successful": deleted,
                            "failed": 0},
            })
        objs_json = body if isinstance(body, list) else body.get("objects", [])
        if self.rbac is not None:
            for oj in objs_json:
                self._authz(request, self._write_action(_obj_from_rest(oj)),
                            f"collections/{oj.get('class', '*')}")
        results = []
        by_class: dict[str, list[StorageObject]] = {}
        parsed: list[tuple[int, StorageObject]] = []
        for i, oj in enumerate(objs_json):
            obj = _obj_from_rest(oj)
            parsed.append((i, obj))
            by_class.setdefault(obj.collection, []).append(obj)
        errors: dict[int, str] = {}
        for cls, group in by_class.items():
            try:
                from weaviate_tpu.schema.auto_schema import ensure_schema

                ensure_schema(self.cluster or self.db, cls,
                              [o.properties for o in group])
                col = self.db.get_collection(cls)
            except (KeyError, ValueError) as e:
                for i, o in parsed:
                    if o.collection == cls:
                        errors[i] = str(e)
                continue
            # objects in one class may span tenants; a failing tenant group
            # only marks its own objects FAILED (earlier groups persisted)
            by_tenant: dict[str, list[StorageObject]] = {}
            for o in group:
                by_tenant.setdefault(o.tenant, []).append(o)
            for tenant, tgroup in by_tenant.items():
                try:
                    if self.cluster is not None:
                        self.cluster.put_batch(
                            cls, tgroup, tenant=tenant,
                            consistency=_consistency(request))
                    else:
                        col.put_batch(tgroup, tenant=tenant)
                except (KeyError, ValueError, RuntimeError) as e:
                    failed_ids = {id(o) for o in tgroup}
                    for i, o in parsed:
                        if id(o) in failed_ids:
                            errors[i] = str(e)
        for i, obj in parsed:
            if i in errors:
                results.append({
                    "result": {"status": "FAILED",
                               "errors": {"error": [{"message": errors[i]}]}},
                    "id": obj.uuid,
                })
            else:
                results.append({**_obj_to_rest(obj, include_vector=False),
                                "result": {"status": "SUCCESS"}})
        return _json_response(results)

    # -- graphql -----------------------------------------------------------
    def _graphql_authz(self, request, query: str,
                       variables=None, operation_name=None) -> None:
        """Per-class authz for every class a query touches (scoped
        read_data grants must work); parse errors fall through to the
        executor's error shape. Shared by /graphql and /graphql/batch.
        MUST parse with the same variables/operation as execution —
        otherwise a variable-driven @include could hide a class from the
        authz walk that execution then returns. Introspection roots
        (``__schema``/``__type``) select meta fields, not classes."""
        if self.rbac is None:
            return
        from weaviate_tpu.api.graphql import GraphQLError, parse

        try:
            for root in parse(query, variables, operation_name):
                if root.name.startswith("__"):
                    continue
                for cls in root.selections:
                    self._authz(request, "read_data",
                                f"collections/{cls.name}")
        except GraphQLError:
            pass

    def on_graphql(self, request):
        body = self._body(request)
        query = body.get("query", "")
        variables = body.get("variables")
        op_name = body.get("operationName")
        self._graphql_authz(request, query, variables, op_name)
        return _json_response(
            self.graphql.execute(query, variables, op_name))

    def on_graphql_batch(self, request):
        """Batch of GraphQL queries in one request (reference
        /graphql/batch): a JSON array of {query}; one result per entry,
        errors isolated per query."""
        body = self._body(request)
        if not isinstance(body, list):
            _abort(422, "expected a JSON array of GraphQL queries")
        out = []
        for entry in body:
            if not isinstance(entry, dict):
                out.append({"errors": [{"message":
                                        "entry must be {query: ...}"}]})
                continue
            query = entry.get("query", "")
            variables = entry.get("variables")
            op_name = entry.get("operationName")
            try:
                self._graphql_authz(request, query, variables, op_name)
                out.append(self.graphql.execute(query, variables, op_name))
            except _Forbidden as e:
                out.append({"errors": [{"message": str(e)}]})
        return _json_response(out)

    def on_cluster_statistics(self, request):
        """Raft consensus statistics (reference /cluster/statistics):
        per-node state/term/commit indexes; single-node servers report
        a synchronized singleton."""
        self._authz(request, "read_cluster")
        if self.cluster is None:
            return _json_response({"statistics": [{
                "name": "node-0", "status": "HEALTHY",
                "raft": {"state": "Leader", "term": 0,
                         "commitIndex": 0, "appliedIndex": 0},
                "leaderId": "node-0", "open": True, "bootstrapped": True,
            }], "synchronized": True})
        r = self.cluster.raft
        return _json_response({"statistics": [{
            "name": self.cluster.id,
            "status": "HEALTHY",
            "raft": {"state": r.state.capitalize(),
                     "term": int(r.current_term),
                     "commitIndex": int(r.commit_index),
                     "appliedIndex": int(r.last_applied)},
            "leaderId": r.leader_id or "",
            "open": True,
            "bootstrapped": True,
        }], "synchronized": r.leader_id is not None})

    # -- replication ops (reference /v1/replication) -----------------------
    def _cluster_or_422(self):
        if self.cluster is None:
            _abort(422, "replication operations require a cluster")
        return self.cluster

    def on_replicate(self, request):
        """Start an async COPY/MOVE of one shard replica (reference
        POST /replication/replicate -> replication engine FSM)."""
        self._authz(request, "manage_cluster")
        c = self._cluster_or_422()
        b = self._body(request)
        for f in ("collection", "shard", "sourceNode", "targetNode"):
            if not b.get(f) and b.get(f) != 0:
                _abort(422, f"{f} is required")
        try:
            op_id = c.start_replication_op(
                b["collection"], int(b["shard"]), b["sourceNode"],
                b["targetNode"], kind=b.get("type", "MOVE"),
                tenant=b.get("tenant", ""))
        except KeyError as e:
            _abort(404, str(e))
        return _json_response({"id": op_id})

    def on_replicate_op(self, request, op_id):
        self._authz(request, "read_cluster")
        op = self._cluster_or_422().replication_op(op_id)
        if op is None:
            _abort(404, f"replication op {op_id!r} not found")
        return _json_response(op)

    def on_replicate_list(self, request):
        self._authz(request, "read_cluster")
        c = self._cluster_or_422()
        shard = request.args.get("shard")
        return _json_response(c.replication_ops(
            cls=request.args.get("collection", ""),
            shard=int(shard) if shard is not None else None))

    def on_replicate_cancel(self, request, op_id):
        self._authz(request, "manage_cluster")
        if not self._cluster_or_422().cancel_replication_op(op_id):
            _abort(404, f"replication op {op_id!r} not found")
        return Response(status=204)

    def on_replicate_force_delete(self, request):
        self._authz(request, "manage_cluster")
        n = self._cluster_or_422().delete_replication_ops()
        return _json_response({"deleted": n})

    def on_replication_scale(self, request):
        """Scale plan (reference GET /replication/scale): per-shard
        add/remove lists toward a desired factor; computes only."""
        self._authz(request, "read_cluster")
        c = self._cluster_or_422()
        cls = request.args.get("collection", "")
        if not cls:
            _abort(422, "collection query param required")
        if not self.db.has_collection(cls):
            _abort(404, f"class {cls!r} not found")
        try:
            factor = int(request.args.get("replicationFactor", "0"))
        except ValueError:
            _abort(422, "replicationFactor must be an integer")
        try:
            return _json_response(c.scale_plan(cls, factor))
        except ValueError as e:
            _abort(422, str(e))

    def on_sharding_state(self, request):
        self._authz(request, "read_cluster")
        c = self._cluster_or_422()
        cls = request.args.get("collection", "")
        if cls and not self.db.has_collection(cls):
            _abort(404, f"class {cls!r} not found")
        return _json_response(c.sharding_state(cls))

    def on_cluster_rebalance(self, request):
        """GET: the planner's current move list (dry run). POST: plan and
        execute a rebalance round from this node as coordinator — every
        move journaled in the raft ledger (docs/rebalance.md)."""
        c = self._cluster_or_422()
        if request.method == "GET":
            self._authz(request, "read_cluster")
            moves = c.rebalancer.plan(
                max_moves=int(request.args.get("maxMoves", 16)))
            return _json_response({"moves": [m.__dict__ for m in moves]})
        self._authz(request, "manage_cluster")
        b = self._body(request) or {}
        ids = c.rebalancer.rebalance(
            max_moves=int(b.get("maxMoves", 16)),
            wait=bool(b.get("wait", False)))
        return _json_response({"moveIds": ids})

    def on_cluster_drain(self, request, node):
        """Drain one node: migrate every replica off it (writes never
        rejected), then remove it from membership unless ?remove=false."""
        self._authz(request, "manage_cluster")
        c = self._cluster_or_422()
        if node not in c.all_nodes:
            _abort(404, f"{node!r} is not a cluster member")
        remove = request.args.get("remove", "true") != "false"

        import logging as _logging
        import threading as _threading

        def _run():
            try:
                c.rebalancer.drain(node, remove=remove)
            except Exception:
                # async surface: the failure story lives in the ledger /
                # draining mark (drain is re-runnable), but say so
                _logging.getLogger("weaviate_tpu.cluster.rebalance") \
                    .exception("async drain of %s failed", node)

        _threading.Thread(target=_run, daemon=True,
                          name=f"drain-{node}").start()
        return _json_response({"draining": node, "remove": remove},
                              status=202)

    def on_cluster_autoscale(self, request):
        """Closed-loop autoscaler control (docs/autoscale.md). GET: the
        loop's status (knob state, breach counters, cooldown, decision
        ledger). POST {"action": enable|disable|evaluate}: flip the
        hot-reloadable autoscale_enabled knob or force one leader-side
        evaluation. QoS-exempt: disarming the loop mid-incident must
        work exactly when the cluster is overloaded."""
        c = self._cluster_or_422()
        if request.method == "GET":
            self._authz(request, "read_cluster")
            return _json_response({"autoscale": c.autoscaler.status()})
        self._authz(request, "manage_cluster")
        from weaviate_tpu.utils.runtime_config import AUTOSCALE_ENABLED

        action = (self._body(request) or {}).get("action", "")
        if action == "enable":
            AUTOSCALE_ENABLED.set_override(True)
        elif action == "disable":
            AUTOSCALE_ENABLED.set_override(False)
        elif action == "evaluate":
            return _json_response(
                {"autoscale": c.autoscaler.tick(force=True)})
        else:
            _abort(422, f"unknown action {action!r}; expected "
                        "enable | disable | evaluate")
        return _json_response({"autoscale": c.autoscaler.status()})

    def on_debug_cluster(self, request):
        """Operator cluster view: membership + gossip liveness, per-node
        advertised HBM capacity, draining set, and the rebalance ledger."""
        self._authz(request, "read_cluster", "debug/cluster")
        if self.cluster is None:
            return _json_response({"node": "node-0", "nodes": {},
                                   "draining": [], "rebalance_ledger": [],
                                   "replication_ops": []})
        return _json_response(self.cluster.cluster_view())

    def on_tasks_list(self, request):
        """Distributed task table (reference /tasks; cluster/tasks.py
        FSM). Single-node servers have no task plane — empty list."""
        self._authz(request, "read_cluster")
        if self.cluster is None or getattr(self.cluster, "tasks",
                                           None) is None:
            return _json_response({"tasks": []})
        return _json_response({"tasks": self.cluster.tasks.list()})

    # -- metrics -----------------------------------------------------------
    # -- dynamic db users (reference rest/operations/users) ----------------
    def on_users_own_info(self, request):
        principal = getattr(request, "principal", None)
        if principal is None:
            _abort(401, "own-info requires authentication")
        roles = []
        if self.rbac is not None:
            roles = [{"name": r} for r in self.rbac.user_roles(principal)]
        return _json_response({
            "username": principal,
            "roles": roles,
            "groups": getattr(request, "principal_groups", []) or [],
        })

    def on_users_db(self, request):
        self._authz(request, "read_users")
        return _json_response(self.users.list())

    def on_users_db_user(self, request, user_id):
        if request.method == "POST":
            self._authz(request, "create_users")
            try:
                key = self.users.create(user_id)
            except KeyError as e:
                _abort(409, str(e.args[0]))
            except ValueError as e:
                _abort(422, str(e))
            return _json_response({"apikey": key}, 201)
        if request.method == "DELETE":
            self._authz(request, "delete_users")
            if not self.users.delete(user_id):
                _abort(404, f"user {user_id!r} not found")
            return Response(status=204)
        self._authz(request, "read_users")
        u = self.users.get(user_id)
        if u is None:
            _abort(404, f"user {user_id!r} not found")
        return _json_response(u)

    def on_users_db_rotate(self, request, user_id):
        self._authz(request, "update_users")
        try:
            return _json_response({"apikey": self.users.rotate(user_id)})
        except KeyError as e:
            _abort(404, str(e.args[0]))

    def on_users_db_activate(self, request, user_id):
        self._authz(request, "update_users")
        try:
            self.users.set_active(user_id, True)
        except KeyError as e:
            _abort(404, str(e.args[0]))
        return Response(status=200)

    def on_users_db_deactivate(self, request, user_id):
        self._authz(request, "update_users")
        try:
            self.users.set_active(user_id, False)
        except KeyError as e:
            _abort(404, str(e.args[0]))
        return Response(status=200)

    # -- classifications (reference adapters/handlers/rest classifications,
    # usecases/classification) --------------------------------------------
    def on_classifications(self, request):
        body = self._body(request)
        cls = body.get("class")
        if not cls:
            _abort(422, "class required")
        self._authz(request, "update_data", f"collections/{cls}")
        try:
            c = self._classifications.start(
                collection=cls,
                classify_properties=body.get("classifyProperties", []),
                based_on_properties=body.get("basedOnProperties", []),
                kind=body.get("type", "knn"),
                k=int((body.get("settings") or {}).get("k", 3)),
                background=request.args.get("async") == "true",
            )
        except (KeyError, ValueError) as e:
            _abort(422, str(e))
        return _json_response(c.to_dict(), 201)

    def on_classification(self, request, cid):
        self._authz(request, "read_data", "classifications")
        c = self._classifications.get(cid)
        if c is None:
            _abort(404, f"classification {cid} not found")
        return _json_response(c.to_dict())

    # -- debug/ops plane ---------------------------------------------------
    def on_debug_traces(self, request):
        from weaviate_tpu.monitoring.tracing import TRACER

        if request.method == "DELETE":
            # destroys debugging evidence: write-tier verb, not read_cluster
            self._authz(request, "manage_cluster", "debug/traces")
            TRACER.clear()
            return Response(status=204)
        self._authz(request, "read_cluster", "debug/traces")
        if request.args.get("exemplars") == "true":
            # worst-observation trace ids per histogram: the jump table
            # from a bad percentile to the trace that produced it
            from weaviate_tpu.monitoring.metrics import REGISTRY

            return _json_response({"exemplars": REGISTRY.exemplars()})
        trace_id = request.args.get("trace")
        if trace_id:
            if request.args.get("format") == "otlp":
                # OTLP-shaped JSONL of ONE trace (docs/tracing.md):
                # importable by any OTLP-tolerant tool, one span per line
                body = TRACER.export_otlp_jsonl(trace_id)
                if not body:
                    _abort(404, f"trace {trace_id!r} not found "
                                "(evicted or never sampled)")
                return Response(body,
                                content_type="application/x-ndjson")
            tree = TRACER.trace_tree(trace_id)
            if tree is None:
                _abort(404, f"trace {trace_id!r} not found "
                            "(evicted or never sampled)")
            return _json_response({
                "spans": TRACER.recent(
                    limit=int(request.args.get("limit", 200)),
                    trace_id=trace_id),
                "tree": tree,
            })
        return _json_response({
            "traces": TRACER.traces(limit=int(request.args.get("limit", 20)))
        })

    def on_debug_config(self, request):
        self._authz(request, "read_cluster", "debug/config")
        from weaviate_tpu.utils.runtime_config import RUNTIME

        return _json_response({
            "overrides_path": RUNTIME.path or None,
            "values": RUNTIME.snapshot(),
            "qos": self.qos.snapshot(),
        })

    def on_debug_telemetry(self, request):
        self._authz(request, "read_cluster", "debug/telemetry")
        if self.telemeter is None:
            return _json_response({"enabled": False})
        return _json_response({
            "enabled": self.telemeter.enabled,
            "payload": self.telemeter.build_payload("UPDATE"),
            "push_url": self.telemeter.url or None,
            "last_push_error": self.telemeter.last_push_error,
        })

    def on_debug_compile(self, request):
        """Compile-tax readiness surface (docs/compile_cache.md):
        persistent-cache hit/miss/bytes, the prewarm driver's warmed
        bucket lattice + manifest, and every program identity devtime
        has sighted with the phase its first dispatch was classified as
        — "did this node's restart pay compile seconds" is answerable
        from one GET."""
        self._authz(request, "read_cluster", "debug/compile")
        from weaviate_tpu.monitoring import devtime
        from weaviate_tpu.utils import compile_cache, prewarm

        return _json_response({
            "cache": compile_cache.stats(),
            "prewarm": prewarm.stats(),
            "devtime": {
                "identities": devtime.snapshot(),
                "phases": devtime.phase_counts(),
            },
        })

    def on_debug_planner(self, request):
        """Query-planner inspection surface (docs/planner.md): per
        collection/shard, the resident filter planes (id, version, hit
        count, HBM bytes) and the inverted index's selectivity sketches
        (per-property row count / NDV / min-max) the cost model plans
        from. An operator can answer "why did this filter take a beam"
        from this GET plus the plan's trace-span attributes.

        ``?estimate=<filter-json>&collection=<name>`` additionally runs
        the estimator against live sketches and returns per-shard
        selectivity — the same numbers plan() would consume."""
        self._authz(request, "read_cluster", "debug/planner")
        from weaviate_tpu.utils.runtime_config import (
            FILTER_PLANE_MAX,
            FILTER_PLANE_PROMOTE_HITS,
        )

        out: dict = {
            "knobs": {
                "filter_plane_promote_hits":
                    int(FILTER_PLANE_PROMOTE_HITS.get()),
                "filter_plane_max": int(FILTER_PLANE_MAX.get()),
            },
            "collections": {},
        }
        want = request.args.get("collection")
        for name, col in list(self.db._collections.items()):
            if want and name != want:
                continue
            shards = {}
            for sname, shard in list(col._shards.items()):
                inv_stats = shard.inverted.stats()
                shards[sname] = {
                    "filter_planes": shard.filter_planes.stats(),
                    "selectivity_sketches":
                        inv_stats.get("selectivity_sketches", {}),
                }
            out["collections"][name] = {"shards": shards}
        est = request.args.get("estimate")
        if est:
            import json as _json

            from weaviate_tpu.inverted.filters import Filter

            flt = Filter.from_dict(_json.loads(est))
            estimates: dict = {}
            for name, col in list(self.db._collections.items()):
                if want and name != want:
                    continue
                for sname, shard in list(col._shards.items()):
                    try:
                        estimates[f"{name}/{sname}"] = \
                            shard.inverted.estimate_selectivity(flt)
                    except Exception as e:
                        estimates[f"{name}/{sname}"] = f"error: {e}"
            out["estimates"] = estimates
        return _json_response(out)

    def on_debug_reindex(self, request, cls):
        self._authz(request, "update_schema", f"collections/{cls}")
        col = self.db.get_collection(cls)
        return _json_response({"class": cls,
                               "reindexed": col.reindex_inverted()})

    def on_metrics(self, request):
        """Prometheus text exposition (reference serves these on :2112
        without authz; same here)."""
        from weaviate_tpu.monitoring.metrics import REGISTRY

        return Response(REGISTRY.render_text(),
                        content_type="text/plain; version=0.0.4")

    def on_pprof_profile(self, request):
        """CPU profile: sample every live thread's stack for ?seconds=N
        (default 2, capped at 30) and return aggregated stack counts —
        the /debug/pprof/profile role, py-spy-shaped output (Go's
        signal-based profiler has no Python equivalent that can see other
        threads; a wall-clock stack sampler does)."""
        self._authz(request, "read_nodes")  # ops surface, not public
        import sys
        import time as _time
        import traceback

        seconds = min(float(request.args.get("seconds", 2) or 2), 30.0)
        me = __import__("threading").get_ident()
        samples: dict[str, int] = {}
        total = 0
        deadline = _time.monotonic() + seconds
        while _time.monotonic() < deadline:
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue  # the sampler's own loop is noise
                stack = "".join(traceback.format_stack(frame, limit=8))
                samples[stack] = samples.get(stack, 0) + 1
                total += 1
            _time.sleep(0.01)
        top = sorted(samples.items(), key=lambda t: -t[1])[:20]
        out = [f"# {total} stack samples over {seconds}s "
               f"(innermost frame last):\n"]
        for stack, n in top:
            out.append(f"\n=== {n} samples ===\n{stack}")
        return Response("".join(out), content_type="text/plain")

    def on_pprof_heap(self, request):
        """Heap profile via tracemalloc: top allocation sites. First call
        starts tracing; ?stop=true turns the (allocation-overhead-heavy)
        tracer back off."""
        self._authz(request, "read_nodes")  # ops surface, not public
        import tracemalloc

        if request.args.get("stop") == "true":
            if tracemalloc.is_tracing():
                tracemalloc.stop()
            return Response("tracemalloc stopped\n",
                            content_type="text/plain")
        if not tracemalloc.is_tracing():
            tracemalloc.start(10)
            return Response(
                "tracemalloc started; call again for a snapshot "
                "(?stop=true to disable)\n",
                content_type="text/plain")
        snap = tracemalloc.take_snapshot()
        stats = snap.statistics("lineno")[:50]
        from weaviate_tpu.monitoring.memwatch import MONITOR

        lines = [f"# rss={MONITOR.stats()['rss']} "
                 f"limit={MONITOR.stats()['limit']}\n"]
        lines += [f"{s.size:>12} B {s.count:>8} blocks  "
                  f"{s.traceback}\n" for s in stats]
        return Response("".join(lines), content_type="text/plain")

    # -- nodes -------------------------------------------------------------
    def on_nodes(self, request):
        self._authz(request, "read_nodes")
        return _json_response(self._nodes_dict())

    def on_nodes_class(self, request, cls):
        """Node status scoped to one collection (reference
        /nodes/{className})."""
        self._authz(request, "read_nodes")
        if not self.db.has_collection(cls):
            _abort(404, f"class {cls!r} not found")
        full = self._nodes_dict()
        for node in full["nodes"]:
            node["shards"] = [s for s in node["shards"]
                              if s.get("class") == cls]
            node["stats"] = {
                "objectCount": sum(s["objectCount"]
                                   for s in node["shards"]),
                "shardCount": len(node["shards"]),
            }
        return _json_response(full)

    def _nodes_dict(self) -> dict:
        from weaviate_tpu.parallel.runtime import device_report

        shards = []
        total = 0
        for name in self.db.collections():
            col = self.db.get_collection(name)
            for sname, s in col._shards.items():
                shards.append({
                    "name": sname, "class": name,
                    "objectCount": s.count(),
                })
                total += s.count()
        local = {
            "name": (self.cluster.id if self.cluster is not None
                     else "node-0"),
            "status": "HEALTHY",
            "version": __version__,
            "stats": {"objectCount": total, "shardCount": len(shards)},
            "shards": shards,
            "device": device_report(),
        }
        if self.cluster is None:
            return {"nodes": [local]}
        # clustered: every raft member, liveness from gossip (reference
        # /v1/nodes aggregates memberlist state the same way)
        nodes = [local]
        statuses = self.cluster.members()
        for nid in sorted(self.cluster.all_nodes):
            if nid == self.cluster.id:
                continue
            st = statuses.get(nid, "UNKNOWN")
            nodes.append({
                "name": nid,
                "status": ("HEALTHY" if st == "ALIVE"
                           else "UNHEALTHY" if st == "DEAD"
                           else "UNAVAILABLE"),
                "version": __version__,
                # zero-valued, HOMOGENEOUS stats (typed clients index
                # into every element; reference non-verbose output is
                # zero-valued the same way)
                "stats": {"objectCount": 0, "shardCount": 0},
                "shards": [],
            })
        return {"nodes": nodes}

    # -- backups -----------------------------------------------------------
    def _backend(self, name: str):
        from weaviate_tpu.backup.backends import make_backend

        try:
            return make_backend(name, f"{self.backup_root}/{name}")
        except KeyError as e:
            _abort(422, str(e))

    def on_backup_create(self, request, backend):
        self._authz(request, "manage_backups")
        from weaviate_tpu.backup.handler import BackupError

        body = self._body(request)
        if not body.get("id"):
            _abort(422, "backup id required")
        try:
            status = self.backups.create(
                self._backend(backend), body["id"],
                include=body.get("include"), exclude=body.get("exclude"),
            )
        except BackupError as e:
            _abort(422, str(e))
        return _json_response(status)

    def on_backup_status(self, request, backend, backup_id):
        self._authz(request, "manage_backups")
        try:
            return _json_response(
                self.backups.status(self._backend(backend), backup_id))
        except KeyError as e:
            _abort(404, str(e))

    def on_backup_restore(self, request, backend, backup_id):
        self._authz(request, "manage_backups")
        from weaviate_tpu.backup.handler import BackupError

        body = self._body(request)
        try:
            out = self.backups.restore(
                self._backend(backend), backup_id,
                include=body.get("include"), exclude=body.get("exclude"),
            )
        except BackupError as e:
            _abort(422, str(e))
        return _json_response(out)

    # -- authz (RBAC management) -------------------------------------------
    def _rbac_or_404(self):
        if self.rbac is None:
            _abort(404, "RBAC is not enabled")
        return self.rbac

    def on_authz_roles(self, request):
        rbac = self._rbac_or_404()
        if request.method == "GET":
            self._authz(request, "read_roles")
            return _json_response([
                {"name": r.name,
                 "permissions": [{"action": p.action, "resource": p.resource}
                                 for p in r.permissions]}
                for r in rbac.roles.values()
            ])
        self._authz(request, "manage_roles")
        body = self._body(request)
        try:
            role = rbac.upsert_role(body["name"],
                                    body.get("permissions", []))
        except ValueError as e:
            _abort(422, str(e))
        return _json_response({"name": role.name})

    def on_authz_role(self, request, name):
        rbac = self._rbac_or_404()
        if request.method == "GET":
            self._authz(request, "read_roles")
            r = rbac.roles.get(name)
            if r is None:
                _abort(404, f"role {name!r} not found")
            return _json_response({
                "name": r.name,
                "permissions": [{"action": p.action, "resource": p.resource}
                                for p in r.permissions]})
        self._authz(request, "manage_roles")
        try:
            rbac.delete_role(name)
        except ValueError as e:
            _abort(422, str(e))
        return Response(status=204)

    def on_authz_role_add_permissions(self, request, name):
        rbac = self._rbac_or_404()
        self._authz(request, "manage_roles")
        body = self._body(request)
        try:
            role = rbac.add_permissions(name, body.get("permissions", []))
        except KeyError as e:
            _abort(404, str(e))
        except ValueError as e:
            _abort(422, str(e))
        return _json_response({"name": role.name})

    def on_authz_role_remove_permissions(self, request, name):
        rbac = self._rbac_or_404()
        self._authz(request, "manage_roles")
        body = self._body(request)
        try:
            role = rbac.remove_permissions(name,
                                           body.get("permissions", []))
        except KeyError as e:
            _abort(404, str(e))
        except ValueError as e:
            _abort(422, str(e))
        return _json_response({"name": role.name})

    def on_authz_role_has_permission(self, request, name):
        rbac = self._rbac_or_404()
        self._authz(request, "read_roles")
        body = self._body(request)
        p = body.get("permission", body)
        try:
            ok = rbac.role_has_permission(
                name, p.get("action", ""), p.get("resource", "*"))
        except KeyError as e:
            _abort(404, str(e))
        return _json_response(bool(ok))

    def on_authz_role_users(self, request, name):
        rbac = self._rbac_or_404()
        self._authz(request, "read_roles")
        try:
            return _json_response(rbac.users_with_role(name))
        except KeyError as e:
            _abort(404, str(e))

    def on_authz_role_user_assignments(self, request, name):
        rbac = self._rbac_or_404()
        self._authz(request, "read_roles")
        try:
            users = rbac.users_with_role(name)
        except KeyError as e:
            _abort(404, str(e))
        return _json_response([
            {"userId": u, "userType": "db"} for u in users])

    def on_authz_user_roles_typed(self, request, user, user_type):
        # userType (db | oidc) narrows nothing here: one identity plane
        rbac = self._rbac_or_404()
        self._authz(request, "read_roles")
        return _json_response(rbac.user_roles(user))

    # -- RBAC group subjects (reference /authz/groups; OIDC groups map
    # to `group:<name>` principals in the assignment table) -------------
    def on_authz_groups(self, request, group_type):
        rbac = self._rbac_or_404()
        self._authz(request, "read_roles")
        return _json_response(sorted(
            p[len("group:"):] for p, rs in rbac.assignments.items()
            if p.startswith("group:") and rs))

    def on_authz_group_assign(self, request, gid):
        rbac = self._rbac_or_404()
        self._authz(request, "manage_roles")
        roles = self._body(request).get("roles", [])
        missing = [r for r in roles if r not in rbac.roles]
        if missing:
            _abort(404, f"roles not found: {missing}")
        for role in roles:
            rbac.assign(f"group:{gid}", role)
        return Response(status=200)

    def on_authz_group_revoke(self, request, gid):
        rbac = self._rbac_or_404()
        self._authz(request, "manage_roles")
        for role in self._body(request).get("roles", []):
            rbac.revoke(f"group:{gid}", role)
        return Response(status=200)

    def on_authz_group_roles(self, request, gid, group_type):
        rbac = self._rbac_or_404()
        self._authz(request, "read_roles")
        return _json_response(rbac.user_roles(f"group:{gid}"))

    def on_authz_role_group_assignments(self, request, name):
        rbac = self._rbac_or_404()
        self._authz(request, "read_roles")
        if name not in rbac.roles:
            _abort(404, f"role {name!r} not found")
        groups = sorted(
            p[len("group:"):] for p, rs in rbac.assignments.items()
            if p.startswith("group:") and name in rs)
        return _json_response([
            {"groupId": g, "groupType": "oidc"} for g in groups])

    def on_authz_assign(self, request, user):
        rbac = self._rbac_or_404()
        self._authz(request, "manage_roles")
        body = self._body(request)
        roles = body.get("roles", [])
        missing = [r for r in roles if r not in rbac.roles]
        if missing:  # validate all before assigning any (no partial state)
            _abort(404, f"roles not found: {missing}")
        for role in roles:
            rbac.assign(user, role)
        return Response(status=200)

    def on_authz_revoke(self, request, user):
        rbac = self._rbac_or_404()
        self._authz(request, "manage_roles")
        body = self._body(request)
        for role in body.get("roles", []):
            rbac.revoke(user, role)
        return Response(status=200)

    def on_authz_user_roles(self, request, user):
        rbac = self._rbac_or_404()
        self._authz(request, "read_roles")
        return _json_response(rbac.user_roles(user))

    # -- lifecycle ---------------------------------------------------------
    def serve(self, host: str = "127.0.0.1", port: int = 8080,
              background: bool = True, max_handlers: Optional[int] = None,
              read_timeout: Optional[float] = None):
        """Start the bounded REST server (serving/bounded.py): handler
        concurrency is capped by a fixed pool sized from the admission
        limiter's ceiling range (not thread-per-connection), and a
        per-connection read timeout unpins handlers from slow clients."""
        from weaviate_tpu.serving.bounded import BoundedThreadedWSGIServer
        from weaviate_tpu.utils.runtime_config import (
            SERVING_REST_READ_TIMEOUT_S,
        )

        if max_handlers is None:
            # enough workers to run a full limiter ceiling plus headroom
            # to keep ANSWERING sheds (a 429 needs a thread too)
            max_handlers = max(8, min(64, self.qos.limiter.max_limit))
        if read_timeout is None:
            read_timeout = SERVING_REST_READ_TIMEOUT_S.get()
        self._server = BoundedThreadedWSGIServer(
            host, port, self, max_handlers=max_handlers,
            read_timeout=read_timeout)
        if background:
            self._thread = threading.Thread(
                target=self._server.serve_forever, daemon=True)
            self._thread.start()
        else:
            self._server.serve_forever()
        return self._server

    def shutdown(self):
        if self._server is not None:
            self._server.shutdown()
            if self._thread is not None:
                self._thread.join(timeout=5)
            # releases the listen fd AND the bounded handler pool —
            # without this every serve/shutdown cycle leaks both
            self._server.server_close()
