"""Server entry point: REST + gRPC on one DB, env-var configured.

Reference: ``cmd/weaviate-server/main.go`` + the composition root
``adapters/handlers/rest/configure_api.go`` (env-driven config from
``usecases/config/environment.go``). Run as:

    python -m weaviate_tpu.server

Env vars (reference names where they exist):
  PERSISTENCE_DATA_PATH   data directory (default ./weaviate-tpu-data)
  JAX_PLATFORMS           jax's own: "tpu" makes a chip that does not come
                          up an error (unset, jax falls back to the CPU)
  JAX_COMPILATION_CACHE_DIR  jax's own: the persistent compile cache is
                          exactly this directory; unset, <checkout>/.jax_cache
  DEFAULT_HTTP_PORT       REST port (default 8080)
  GRPC_PORT               gRPC port (default 50051; empty string disables)
  AUTHENTICATION_APIKEY_ENABLED        "true" to require API keys
  AUTHENTICATION_APIKEY_ALLOWED_KEYS   comma-separated keys
  AUTHENTICATION_APIKEY_USERS          comma-separated user names (parallel)
  AUTHENTICATION_ANONYMOUS_ACCESS_ENABLED  default "true"
  AUTHORIZATION_RBAC_ENABLED           "true" to enforce RBAC
  AUTHORIZATION_RBAC_ROOT_USERS        comma-separated always-admin users
"""

from __future__ import annotations

import os
import signal
import sys
import threading

DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def config_from_env() -> dict:
    keys = [k for k in os.environ.get(
        "AUTHENTICATION_APIKEY_ALLOWED_KEYS", "").split(",") if k]
    users = [u for u in os.environ.get(
        "AUTHENTICATION_APIKEY_USERS", "").split(",") if u]
    api_keys = dict(zip(keys, users + ["user"] * (len(keys) - len(users))))
    return {
        "data_path": os.environ.get(
            "PERSISTENCE_DATA_PATH", "./weaviate-tpu-data"),
        "http_port": int(os.environ.get("DEFAULT_HTTP_PORT", "8080")),
        "grpc_port": os.environ.get("GRPC_PORT", "50051"),
        "api_keys": api_keys
        if os.environ.get("AUTHENTICATION_APIKEY_ENABLED") == "true" else {},
        "anonymous": os.environ.get(
            "AUTHENTICATION_ANONYMOUS_ACCESS_ENABLED", "true") != "false",
        "rbac_enabled": os.environ.get(
            "AUTHORIZATION_RBAC_ENABLED") == "true",
        "rbac_root_users": [
            u for u in os.environ.get(
                "AUTHORIZATION_RBAC_ROOT_USERS", "").split(",") if u],
        # OIDC (reference AUTHENTICATION_OIDC_*): zero-egress deployments
        # configure keys inline instead of discovery
        "oidc_enabled": os.environ.get(
            "AUTHENTICATION_OIDC_ENABLED") == "true",
        "oidc_issuer": os.environ.get("AUTHENTICATION_OIDC_ISSUER", ""),
        "oidc_client_id": os.environ.get("AUTHENTICATION_OIDC_CLIENT_ID", ""),
        "oidc_username_claim": os.environ.get(
            "AUTHENTICATION_OIDC_USERNAME_CLAIM", "sub"),
        "oidc_groups_claim": os.environ.get(
            "AUTHENTICATION_OIDC_GROUPS_CLAIM", "groups"),
        "oidc_jwks_file": os.environ.get("AUTHENTICATION_OIDC_JWKS_FILE", ""),
        "oidc_hs256_secret": os.environ.get(
            "AUTHENTICATION_OIDC_HS256_SECRET", ""),
    }


def main() -> int:
    from weaviate_tpu.api.grpc_server import GrpcAPI
    from weaviate_tpu.api.rest import AuthConfig, RestAPI
    from weaviate_tpu.core.db import DB

    cfg = config_from_env()
    # persistent compilation cache BEFORE anything can jit (DB open may
    # compile during checkpoint replay): restarted nodes deserialize
    # yesterday's executables instead of re-paying XLA
    # (docs/compile_cache.md). JAX_COMPILATION_CACHE_DIR, where set, is
    # used as is; otherwise the default base is one FIXED directory in
    # the checkout — the path is part of what makes a later run hit, so
    # it must not move with the data directory.
    from weaviate_tpu.utils import compile_cache

    compile_cache.configure(
        compile_cache.resolve_base_dir() or DEFAULT_COMPILE_CACHE_DIR)
    # say what this process runs on — and fail here, before the data
    # directory is touched, when the platform asked for does not come up
    from weaviate_tpu.parallel.runtime import device_report

    dev = device_report()
    print(f"devices: {dev['count']} x {dev['kind']} "
          f"(platform {dev['platform']})", file=sys.stderr)
    db = DB(cfg["data_path"])
    oidc = None
    if cfg["oidc_enabled"]:
        import json as _json

        from weaviate_tpu.auth.oidc import OIDCConfig

        jwks = None
        if cfg["oidc_jwks_file"]:
            with open(cfg["oidc_jwks_file"]) as f:
                jwks = _json.load(f)
        oidc = OIDCConfig(
            issuer=cfg["oidc_issuer"], client_id=cfg["oidc_client_id"],
            jwks=jwks,
            hs256_secret=(cfg["oidc_hs256_secret"].encode()
                          if cfg["oidc_hs256_secret"] else None),
            username_claim=cfg["oidc_username_claim"],
            groups_claim=cfg["oidc_groups_claim"],
        )
    auth = AuthConfig(api_keys=cfg["api_keys"],
                      anonymous_access=cfg["anonymous"], oidc=oidc)
    rbac = None
    if cfg["rbac_enabled"]:
        from weaviate_tpu.auth.rbac import RBACController

        rbac = RBACController(path=f"{cfg['data_path']}/rbac.json",
                              root_users=cfg["rbac_root_users"])
    # runtime-overrides hot reload + usage telemetry (reference
    # config/runtime + usecases/telemetry)
    from weaviate_tpu.monitoring.telemetry import Telemeter
    from weaviate_tpu.utils.runtime_config import RUNTIME

    RUNTIME.start()
    telemeter = Telemeter(db)
    telemeter.start()

    # boot prewarm: compile the shape-bucket lattice of every open
    # collection in the background; /v1/.well-known/ready reports
    # ``warming: true`` until it drains so orchestrators can gate
    # traffic on compile-free first queries
    from weaviate_tpu.utils import prewarm

    prewarm.prewarm_db(db, reason="boot", block=False)

    rest = RestAPI(db, auth=auth, rbac=rbac)
    rest.telemeter = telemeter
    rest_srv = rest.serve(host="0.0.0.0", port=cfg["http_port"],
                          background=True)
    print(f"REST listening on :{rest_srv.server_port}", file=sys.stderr)

    grpc_api = None
    if cfg["grpc_port"]:
        grpc_api = GrpcAPI(db, auth=auth, rbac=rbac)
        port = grpc_api.serve(host="0.0.0.0", port=int(cfg["grpc_port"]))
        print(f"gRPC listening on :{port}", file=sys.stderr)

    # the interpreter's own readings (lock wait, collector pauses): only
    # a serving process gets the thread, and only once it can serve
    from weaviate_tpu.monitoring.interp import SAMPLER

    SAMPLER.start()

    stop = threading.Event()

    def _sig(*_):
        stop.set()

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    stop.wait()

    print("shutting down", file=sys.stderr)
    SAMPLER.stop()
    rest.shutdown()
    if grpc_api is not None:
        grpc_api.shutdown()
    telemeter.stop()
    RUNTIME.stop()
    db.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
