"""What every traffic kind shares: the server child, REST and gRPC calls,
the bulk load, closed-loop clients, percentiles. Copied from ``chip_smoke.py``
where it had the piece (server child, loader, uuids); the smoke stays as it
is. The parent process never initialises a JAX backend.
"""

from __future__ import annotations

import json
import math
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request

import grpc
import numpy as np

from weaviate_tpu.api.proto import pb

SERVICE = "weaviate_tpu.v1.WeaviateTpu"     # the served gRPC plane
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOAD_THREADS = 4
LOAD_BATCH = 1000       # objects per BatchObjects (4 MiB message cap)


def say(**kw) -> None:
    """One JSON line on standard output, before the result line."""
    print(json.dumps(kw), flush=True)


def row_uuid(i: int) -> str:
    return f"{i:08x}-0000-4000-8000-{i:012x}"


def uuid_row(u: str) -> int:
    try:
        return int(u[:8], 16)
    except ValueError:
        return -1


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def percentile(values, share: float) -> float:
    """Nearest-rank percentile; ``inf`` entries (failed requests) sort last,
    so a failure counts as beyond any percentile."""
    v = np.sort(np.asarray(values, np.float64))
    if len(v) == 0:
        return math.nan
    return float(v[min(len(v) - 1, math.ceil(share * len(v)) - 1)])


# -- the server child -------------------------------------------------------

class Server:
    """``python -m <serve_module>`` with a fresh data directory, free ports
    and the platform asked for; ``out`` receives what the child writes."""

    def __init__(self, workdir: str, platform: str,
                 serve_module: str = "benchmark.serve"):
        self.out = os.path.join(workdir, "out")
        os.makedirs(self.out)
        self.http_port, self.grpc_port = free_port(), free_port()
        self.base = f"http://127.0.0.1:{self.http_port}"
        self.address = f"127.0.0.1:{self.grpc_port}"
        self.stderr_path = os.path.join(workdir, "server.stderr")
        # the program's own defaults: no WEAVIATE_TPU_* override leaks in
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("WEAVIATE_TPU_")}
        env.update(
            PERSISTENCE_DATA_PATH=os.path.join(workdir, "data"),
            DEFAULT_HTTP_PORT=str(self.http_port),
            GRPC_PORT=str(self.grpc_port),
            JAX_PLATFORMS=platform,
            BENCH_SERVE_OUT=self.out)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (REPO, env.get("PYTHONPATH", "")) if p)
        self._stderr = open(self.stderr_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", serve_module], env=env, cwd=workdir,
            stdout=self._stderr, stderr=self._stderr)

    def stderr_text(self) -> str:
        self._stderr.flush()
        with open(self.stderr_path, errors="replace") as f:
            return f.read()

    def get(self, path: str, timeout: float = 60.0):
        with urllib.request.urlopen(self.base + path, timeout=timeout) as r:
            return json.loads(r.read())

    def post(self, path: str, body: dict, timeout: float = 60.0):
        req = urllib.request.Request(
            self.base + path, data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read() or b"null")

    def wait_ready(self, deadline_s: float = 300.0) -> float:
        t0 = time.monotonic()
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise RuntimeError(
                    f"server exited with code {rc} before it was ready:\n"
                    + self.stderr_text()[-4000:])
            try:
                self.get("/v1/.well-known/ready", timeout=5)
                return time.monotonic() - t0
            except OSError:
                if time.monotonic() - t0 > deadline_s:
                    raise TimeoutError("server not ready in time") from None
                time.sleep(0.2)

    def signal_and_wait(self, sig: int, marker: str,
                        timeout: float = 120.0) -> dict:
        path = os.path.join(self.out, marker)
        self.proc.send_signal(sig)
        t0 = time.monotonic()
        while not os.path.exists(path):
            if self.proc.poll() is not None:
                raise RuntimeError("server died while tracing:\n"
                                   + self.stderr_text()[-4000:])
            if time.monotonic() - t0 > timeout:
                raise TimeoutError(f"no {marker} after {timeout:.0f}s")
            time.sleep(0.05)
        with open(path) as f:
            return json.load(f)

    def device(self) -> dict:
        return self.get("/v1/nodes")["nodes"][0]["device"]

    def object_count(self) -> int:
        return self.get("/v1/nodes")["nodes"][0]["stats"]["objectCount"]

    def compile_counters(self) -> dict:
        return self.get("/v1/debug/compile")["cache"]

    def stop(self) -> int:
        self.proc.send_signal(signal.SIGTERM)
        return self.proc.wait(timeout=120)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self._stderr.close()


def create_collection(server: Server, cfg: dict) -> None:
    col = cfg["collection"]
    server.post("/v1/schema", {
        "class": col["class"], "vectorizer": col["vectorizer"],
        "vectorIndexType": col["vectorIndexType"],
        "vectorIndexConfig": {"distance": cfg["distance"]},
        "properties": col["properties"]})


# -- gRPC -------------------------------------------------------------------

class Grpc:
    """One channel; requests may be pre-serialized bytes, so a client thread
    spends its time waiting for the server and not building messages."""

    def __init__(self, address: str):
        self.channel = grpc.insecure_channel(address)
        self._search = self.channel.unary_unary(
            f"/{SERVICE}/Search", request_serializer=_ser,
            response_deserializer=pb.SearchReply.FromString)
        self._batch = self.channel.unary_unary(
            f"/{SERVICE}/BatchObjects", request_serializer=_ser,
            response_deserializer=pb.BatchObjectsReply.FromString)

    def search(self, request, timeout: float):
        return self._search(request, timeout=timeout)

    def batch_objects(self, request, timeout: float):
        return self._batch(request, timeout=timeout)

    def close(self) -> None:
        self.channel.close()


def _ser(msg) -> bytes:
    return msg if isinstance(msg, bytes) else msg.SerializeToString()


def search_request(collection: str, k: int, vectors: np.ndarray) -> bytes:
    return pb.SearchRequest(
        collection=collection, limit=k,
        near_vectors=[pb.Vector(values=v) for v in vectors.tolist()],
    ).SerializeToString()


def batch_request(collection: str, first_row: int,
                  vectors: np.ndarray) -> bytes:
    return pb.BatchObjectsRequest(objects=[
        pb.BatchObject(
            uuid=row_uuid(first_row + j), collection=collection,
            properties_json='{"tag": "r%d"}' % (first_row + j),
            vector=pb.Vector(values=values))
        for j, values in enumerate(vectors.tolist())]).SerializeToString()


def check_batch_reply(reply, first_row: int, n: int) -> str:
    """'' when every object of the batch was acknowledged under its uuid."""
    if reply.errors:
        return f"row {first_row}: {reply.errors[0].message}"
    if list(reply.uuids) != [row_uuid(first_row + j) for j in range(n)]:
        return f"row {first_row}: acknowledged uuids differ from those sent"
    return ""


def parse_search_reply(reply) -> list[tuple[np.ndarray, np.ndarray]]:
    """(row ids, distances) for each query vector of one reply."""
    return [(np.array([uuid_row(h.uuid) for h in r.hits], np.int64),
             np.array([h.distance for h in r.hits], np.float32))
            for r in reply.results]


def load(server: Server, collection: str, corpus: np.ndarray) -> float:
    """Bulk load of rows 0..len(corpus) over gRPC: 4 threads x 1000-object
    batches, every reply checked. Returns the seconds it took."""
    starts = iter(range(0, len(corpus), LOAD_BATCH))
    lock = threading.Lock()
    failures: list[BaseException] = []

    def worker():
        client = Grpc(server.address)
        try:
            while not failures:
                with lock:
                    lo = next(starts, None)
                if lo is None:
                    return
                part = corpus[lo:lo + LOAD_BATCH]
                reply = client.batch_objects(
                    batch_request(collection, lo, part), timeout=120)
                err = check_batch_reply(reply, lo, len(part))
                if err:
                    raise RuntimeError("BatchObjects at " + err)
        except BaseException as e:  # re-raised by the caller below
            failures.append(e)
        finally:
            client.close()

    t0 = time.monotonic()
    threads = [threading.Thread(target=worker) for _ in range(LOAD_THREADS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if failures:
        raise failures[0]
    return time.monotonic() - t0


# -- closed-loop clients ----------------------------------------------------

def closed_loop(address: str, clients: list[int], seconds: float,
                next_request, call: str, parse, start_at: float | None = None,
                timeout: float = 60.0) -> list[dict]:
    """One thread per client id in ``clients``, each with its own channel:
    send, wait for the reply, send the next, until ``seconds`` have passed
    since ``start_at`` (a ``time.monotonic()`` instant, shared by generator
    processes); a request in flight at the close is waited for.
    ``next_request(client) -> (tag, request)``. Returns one record per
    request: client, tag, sent (s after the start), latency (s), error text,
    and ``answer`` = ``parse(reply)``, parsed once the window has closed."""
    if start_at is None:
        start_at = time.monotonic() + 0.2
    records: dict[int, list[dict]] = {c: [] for c in clients}
    failures: list[BaseException] = []

    def client_loop(c: int):
        client = Grpc(address)
        fn = client.search if call == "Search" else client.batch_objects
        try:
            time.sleep(max(0.0, start_at - time.monotonic()))
            while not failures:
                sent = time.monotonic()
                if sent - start_at >= seconds:
                    return
                tag, request = next_request(c)
                reply, error = None, ""
                try:
                    reply = fn(request, timeout=timeout)
                except grpc.RpcError as e:
                    error = f"{e.code().name}: {e.details()}"
                records[c].append({
                    "client": c, "tag": tag, "sent": sent - start_at,
                    "latency": time.monotonic() - sent,
                    "reply": reply, "error": error})
        except BaseException as e:  # re-raised by the caller below
            failures.append(e)
        finally:
            client.close()

    threads = [threading.Thread(target=client_loop, args=(c,))
               for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if failures:
        raise failures[0]
    out = [r for c in clients for r in records[c]]
    for r in out:
        reply = r.pop("reply")
        r["answer"] = parse(reply) if reply is not None else None
    return out
