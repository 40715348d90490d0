"""Object-store backends: S3/GCS/Azure clients, backup round-trip, offload
tier, usage reports, and backup snapshot isolation.

Reference test models: ``modules/backup-*`` client tests against emulated
endpoints and ``usecases/backup`` coordinator tests. A single in-process
HTTP emulator speaks enough of all three wire protocols (path-style S3,
GCS JSON API, Azure Blob XML listing) that signing and URL construction
are exercised end to end.
"""

import json
import re
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from weaviate_tpu.backup.backends import ObjectStoreBackend
from weaviate_tpu.backup.handler import BackupHandler
from weaviate_tpu.backup.object_store import (
    AzureClient,
    GCSClient,
    S3Client,
)
from weaviate_tpu.backup.offload import ObjectStoreOffloader, UsageReporter
from weaviate_tpu.core.db import DB
from weaviate_tpu.schema.config import (
    CollectionConfig,
    DataType,
    FlatIndexConfig,
    MultiTenancyConfig,
    Property,
)
from weaviate_tpu.storage.objects import StorageObject


class _Emulator(BaseHTTPRequestHandler):
    """dict-backed blob store speaking minimal S3 / GCS / Azure."""

    store: dict[str, bytes] = {}

    def log_message(self, *a):  # quiet
        pass

    def _send(self, code, body=b"", ctype="application/octet-stream"):
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _read_body(self) -> bytes:
        n = int(self.headers.get("Content-Length", "0") or 0)
        return self.rfile.read(n) if n else b""

    def do_PUT(self):
        path = urllib.parse.unquote(
            urllib.parse.urlparse(self.path).path).lstrip("/")
        self.store[path] = self._read_body()
        self._send(201)

    def do_POST(self):  # GCS media upload
        u = urllib.parse.urlparse(self.path)
        if u.path.startswith("/upload/storage/v1/b/"):
            bucket = u.path.split("/")[5]
            q = urllib.parse.parse_qs(u.query)
            name = q["name"][0]
            self.store[f"{bucket}/{name}"] = self._read_body()
            self._send(200, json.dumps({"name": name}).encode(),
                       "application/json")
        else:
            self._send(404)

    PAGE = 3  # tiny pages force the clients' pagination loops

    def do_DELETE(self):
        u = urllib.parse.urlparse(self.path)
        path = urllib.parse.unquote(u.path).lstrip("/")
        if path.startswith("storage/v1/b/"):  # GCS
            parts = u.path.split("/")
            path = f"{parts[4]}/{urllib.parse.unquote(parts[6])}"
        self.store.pop(path, None)
        self._send(204)

    def do_GET(self):
        u = urllib.parse.urlparse(self.path)
        q = urllib.parse.parse_qs(u.query)
        # GCS object read / list
        if u.path.startswith("/storage/v1/b/"):
            parts = u.path.split("/")
            bucket = parts[4]
            if len(parts) > 6:  # /storage/v1/b/{b}/o/{name}
                name = urllib.parse.unquote(parts[6])
                data = self.store.get(f"{bucket}/{name}")
                if data is None:
                    return self._send(404)
                return self._send(200, data)
            prefix = q.get("prefix", [""])[0]
            names = sorted(k[len(bucket) + 1:] for k in self.store
                           if k.startswith(f"{bucket}/{prefix}"))
            start = int(q.get("pageToken", ["0"])[0] or 0)
            page = names[start:start + self.PAGE]
            out = {"items": [{"name": n} for n in page]}
            if start + self.PAGE < len(names):
                out["nextPageToken"] = str(start + self.PAGE)
            return self._send(200, json.dumps(out).encode(),
                              "application/json")
        path = urllib.parse.unquote(u.path).lstrip("/")
        # Azure container list
        if "comp" in q:
            prefix = q.get("prefix", [""])[0]
            container = path
            names = sorted(k[len(container) + 1:] for k in self.store
                           if k.startswith(f"{container}/{prefix}"))
            start = int(q.get("marker", ["0"])[0] or 0)
            page = names[start:start + self.PAGE]
            marker = (f"<NextMarker>{start + self.PAGE}</NextMarker>"
                      if start + self.PAGE < len(names) else "")
            xml = "<EnumerationResults>" + "".join(
                f"<Blob><Name>{n}</Name></Blob>" for n in page) + \
                marker + "</EnumerationResults>"
            return self._send(200, xml.encode(), "application/xml")
        # S3 list
        if "list-type" in q:
            bucket = path
            prefix = q.get("prefix", [""])[0]
            keys = sorted(k[len(bucket) + 1:] for k in self.store
                          if k.startswith(f"{bucket}/{prefix}"))
            start = int(q.get("continuation-token", ["0"])[0] or 0)
            page = keys[start:start + self.PAGE]
            trunc = start + self.PAGE < len(keys)
            extra = ("<IsTruncated>true</IsTruncated>"
                     f"<NextContinuationToken>{start + self.PAGE}"
                     "</NextContinuationToken>" if trunc
                     else "<IsTruncated>false</IsTruncated>")
            xml = "<ListBucketResult>" + "".join(
                f"<Contents><Key>{k}</Key></Contents>" for k in page) + \
                extra + "</ListBucketResult>"
            return self._send(200, xml.encode(), "application/xml")
        data = self.store.get(path)
        if data is None:
            return self._send(404)
        self._send(200, data)


@pytest.fixture(scope="module")
def emulator():
    _Emulator.store = {}
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Emulator)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{srv.server_port}"
    srv.shutdown()


@pytest.fixture(autouse=True)
def _clean_store():
    _Emulator.store.clear()


def _clients(emulator):
    return [
        ("s3", S3Client("bkt", access_key="ak", secret_key="sk",
                        endpoint=emulator)),
        ("gcs", GCSClient("bkt", token="tok", endpoint=emulator)),
        ("azure", AzureClient("acct", "bkt", key="a2V5", endpoint=emulator)),
    ]


def test_put_get_list_roundtrip_all_protocols(emulator):
    for name, client in _clients(emulator):
        client.put("a/b/file1.bin", b"data-1")
        client.put("a/b/file2.bin", b"data-2")
        client.put("other/file3.bin", b"data-3")
        assert client.get("a/b/file1.bin") == b"data-1", name
        assert client.get("missing") is None, name
        keys = client.list("a/")
        assert sorted(keys) == ["a/b/file1.bin", "a/b/file2.bin"], name


def test_s3_sigv4_headers_present(emulator):
    seen = {}
    from weaviate_tpu.backup import object_store as osm

    real = osm.urllib_http

    def spy(method, url, headers, body):
        seen.update(headers)
        return real(method, url, headers, body)

    c = S3Client("bkt", access_key="AKID", secret_key="sk",
                 endpoint=emulator, http=spy)
    c.put("k", b"v")
    assert seen["Authorization"].startswith("AWS4-HMAC-SHA256 Credential=AKID/")
    assert "SignedHeaders=host;x-amz-content-sha256;x-amz-date" in \
        seen["Authorization"]
    assert re.match(r"\d{8}T\d{6}Z", seen["x-amz-date"])
    # payload hash binds the body into the signature
    import hashlib

    assert seen["x-amz-content-sha256"] == hashlib.sha256(b"v").hexdigest()


def test_azure_sharedkey_header_shape(emulator):
    seen = {}

    def spy(method, url, headers, body):
        seen.update(headers)
        from weaviate_tpu.backup.object_store import urllib_http

        return urllib_http(method, url, headers, body)

    c = AzureClient("acct", "bkt", key="a2V5", endpoint=emulator, http=spy)
    c.put("blob", b"v")
    assert seen["Authorization"].startswith("SharedKey acct:")
    assert seen["x-ms-blob-type"] == "BlockBlob"


def _db_with_data(tmp_path):
    db = DB(str(tmp_path / "db"))
    db.create_collection(CollectionConfig(
        name="Doc",
        properties=[Property(name="t", data_type=DataType.TEXT)],
        vector_config=FlatIndexConfig(distance="l2-squared",
                                      precision="fp32")))
    col = db.get_collection("Doc")
    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((40, 8)).astype(np.float32)
    col.put_batch([StorageObject(
        uuid=f"77000000-0000-0000-0000-{i:012d}", collection="Doc",
        properties={"t": f"doc {i}"}, vector=vecs[i]) for i in range(40)])
    return db, vecs


@pytest.mark.parametrize("proto", ["s3", "gcs", "azure"])
def test_backup_restore_via_object_store(tmp_path, emulator, proto):
    db, vecs = _db_with_data(tmp_path)
    client = dict(_clients(emulator))[proto]
    backend = ObjectStoreBackend(proto, client)
    h = BackupHandler(db)
    st = h.create(backend, "bk1")
    assert st["status"] == "SUCCESS", st
    assert backend.exists("bk1")
    assert backend.list_files("bk1")
    db.delete_collection("Doc")
    out = h.restore(backend, "bk1")
    assert out["classes"] == ["Doc"]
    col = db.get_collection("Doc")
    assert col.count() == 40
    hits = col.vector_search(vecs[5], k=1)
    assert hits[0][0].properties["t"] == "doc 5"
    db.close()


def test_frozen_tenant_offloads_to_object_store(tmp_path, emulator,
                                                monkeypatch):
    monkeypatch.setenv("OFFLOAD_S3_BUCKET", "bkt")
    monkeypatch.setenv("OFFLOAD_S3_ENDPOINT", emulator)
    db = DB(str(tmp_path / "db"))
    db.create_collection(CollectionConfig(
        name="MT",
        properties=[Property(name="t", data_type=DataType.TEXT)],
        vector_config=FlatIndexConfig(distance="l2-squared",
                                      precision="fp32"),
        multi_tenancy=MultiTenancyConfig(enabled=True)))
    col = db.get_collection("MT")
    col.add_tenant("acme")
    vecs = np.eye(8, dtype=np.float32)
    col.put_batch([StorageObject(
        uuid=f"88000000-0000-0000-0000-{i:012d}", collection="MT",
        properties={"t": f"doc {i}"}, vector=vecs[i], tenant="acme")
        for i in range(8)], tenant="acme")
    col.set_tenant_status("acme", "FROZEN")
    # files must live in the bucket, not the hot dir
    assert any(k.startswith("bkt/offload/MT/acme/")
               for k in _Emulator.store), list(_Emulator.store)[:5]
    import os

    assert not os.path.exists(os.path.join(col.dir, "tenant-acme"))
    col.set_tenant_status("acme", "HOT")
    hits = col.vector_search(vecs[3], k=1, tenant="acme")
    assert hits[0][0].properties["t"] == "doc 3"
    assert col.count(tenant="acme") == 8
    db.close()


def test_list_paginates_past_page_size_all_protocols(emulator):
    for name, client in _clients(emulator):
        for i in range(8):  # 8 keys > PAGE=3 → 3 pages
            client.put(f"pg/k{i:02d}", b"x")
        keys = client.list("pg/")
        assert sorted(keys) == [f"pg/k{i:02d}" for i in range(8)], name


def test_refreeze_after_compaction_clears_stale_keys(emulator):
    import os as _os
    import tempfile

    client = S3Client("bkt", access_key="a", secret_key="s",
                      endpoint=emulator)
    off = ObjectStoreOffloader(client)
    d = tempfile.mkdtemp()
    for fn in ("segment-000.db", "segment-001.db"):
        with open(_os.path.join(d, fn), "wb") as f:
            f.write(b"old")
    off.upload("C", "t1", d)
    # simulate unfreeze + compaction: the two segments merge into one
    _os.remove(_os.path.join(d, "segment-000.db"))
    _os.remove(_os.path.join(d, "segment-001.db"))
    with open(_os.path.join(d, "segment-002.db"), "wb") as f:
        f.write(b"merged")
    off.upload("C", "t1", d)
    keys = client.list("offload/C/t1/")
    assert keys == ["offload/C/t1/segment-002.db"], keys


def test_shard_created_mid_backup_inherits_pause(tmp_path):
    db = DB(str(tmp_path / "db"))
    db.create_collection(CollectionConfig(
        name="MT2",
        properties=[Property(name="t", data_type=DataType.TEXT)],
        vector_config=FlatIndexConfig(distance="l2-squared",
                                      precision="fp32"),
        multi_tenancy=MultiTenancyConfig(enabled=True)))
    col = db.get_collection("MT2")
    with col.maintenance_paused():
        col.add_tenant("late")
        shard = col._get_shard("tenant-late")
        assert shard.objects._paused > 0
        col.compact_once()  # no-op while paused
    assert shard.objects._paused == 0  # resumed on exit
    db.close()


def test_usage_reporter_writes_snapshots(tmp_path, emulator):
    db, _ = _db_with_data(tmp_path)
    rep = UsageReporter(
        db, S3Client("bkt", access_key="a", secret_key="s",
                     endpoint=emulator), node="n1")
    key = rep.report_once()
    assert key.startswith("usage/n1/")
    stored = json.loads(_Emulator.store[f"bkt/{key}"])
    assert stored["collections"]["Doc"]["objects"] == 40
    db.close()


def test_backup_pauses_compaction_during_copy(tmp_path):
    """While a collection's maintenance is paused, compaction + flush must
    not mutate the segment set (the backup walk's file list stays valid)."""
    db, _ = _db_with_data(tmp_path)
    col = db.get_collection("Doc")
    col.flush()
    shard = next(iter(col._shards.values()))
    bucket = shard.objects
    # force multiple segments, then pause
    bucket.flush_memtable()
    segs_before = list(s.path for s in bucket._segments)
    with col.maintenance_paused():
        bucket.compact()  # must be a no-op
        bucket.put(b"k-new", b"v")  # writes still land (WAL+memtable)
        bucket.flush_memtable()  # must be deferred
        assert [s.path for s in bucket._segments] == segs_before
    # after resume, maintenance may proceed
    bucket.flush_memtable()
    bucket.compact()
    assert bucket.get(b"k-new") == b"v"
    db.close()


def test_backup_includes_frozen_tenants(tmp_path, monkeypatch):
    """FROZEN tenant files live in the offload tier outside col.dir; a
    backup must carry them and restore must put them back where an
    unfreeze expects them."""
    monkeypatch.setenv("OFFLOAD_FS_PATH", str(tmp_path / "offload"))
    db = DB(str(tmp_path / "db"))
    db.create_collection(CollectionConfig(
        name="FT",
        properties=[Property(name="t", data_type=DataType.TEXT)],
        vector_config=FlatIndexConfig(distance="l2-squared",
                                      precision="fp32"),
        multi_tenancy=MultiTenancyConfig(enabled=True)))
    col = db.get_collection("FT")
    col.add_tenant("cold-co")
    vecs = np.eye(8, dtype=np.float32)
    col.put_batch([StorageObject(
        uuid=f"99000000-0000-0000-0000-{i:012d}", collection="FT",
        properties={"t": f"doc {i}"}, vector=vecs[i], tenant="cold-co")
        for i in range(8)], tenant="cold-co")
    col.set_tenant_status("cold-co", "FROZEN")

    from weaviate_tpu.backup.backends import FilesystemBackend

    backend = FilesystemBackend(str(tmp_path / "bk"))
    h = BackupHandler(db)
    st = h.create(backend, "fbk")
    assert st["status"] == "SUCCESS", st
    assert any("__frozen__" in f for f in backend.list_files("fbk"))
    db.close()

    # fresh node: different data root, same backup
    monkeypatch.setenv("OFFLOAD_FS_PATH", str(tmp_path / "offload2"))
    db2 = DB(str(tmp_path / "db2"))
    h2 = BackupHandler(db2)
    out = h2.restore(backend, "fbk")
    assert out["classes"] == ["FT"]
    col2 = db2.get_collection("FT")
    assert col2.tenants()["cold-co"] == "FROZEN"
    col2.set_tenant_status("cold-co", "HOT")
    hits = col2.vector_search(vecs[3], k=1, tenant="cold-co")
    assert hits[0][0].properties["t"] == "doc 3"
    assert col2.count(tenant="cold-co") == 8
    db2.close()


# -- the stored object's codec: vectors decoded when read (PR 32) ------------

def _eager_vectors(data: bytes):
    """(vector, named_vectors) decoded straight off the envelope, as
    ``from_bytes`` did before it left them as stored."""
    import msgpack

    env = msgpack.unpackb(data, raw=False)
    vec = env.get("vec")
    if vec is not None:
        vec = np.frombuffer(vec, np.float32).copy()
        if env.get("vec_shape"):
            vec = vec.reshape(env["vec_shape"])
    named = {k: np.frombuffer(v, np.float32).reshape(
        env["nvec_shapes"][k]).copy() for k, v in env.get("nvecs", {}).items()}
    return vec, named


def _codec_object(kind: str) -> StorageObject:
    rng = np.random.default_rng(11)
    kw = {
        "flat": dict(vector=rng.standard_normal(24).astype(np.float32)),
        "tokens": dict(vector=rng.standard_normal((5, 8)).astype(np.float32)),
        "named": dict(named_vectors={
            "title": rng.standard_normal(6).astype(np.float32),
            "toks": rng.standard_normal((3, 4)).astype(np.float32)}),
        "both": dict(vector=rng.standard_normal(4).astype(np.float32),
                     named_vectors={"a": rng.standard_normal(4).astype(
                         np.float32)}),
        "float64_in": dict(vector=rng.standard_normal(7)),
        "none": dict(),
    }[kind]
    return StorageObject(
        uuid="88000000-0000-0000-0000-000000000001", collection="Doc",
        properties={"t": "doc", "n": 3, "tags": ["a", "b"]}, doc_id=17,
        tenant="t1", creation_time_ms=1_700_000_000_000,
        update_time_ms=1_700_000_000_500, **kw)


@pytest.mark.parametrize(
    "kind", ["flat", "tokens", "named", "both", "float64_in", "none"])
def test_lazy_vectors_equal_eager_and_round_trip_bit_equal(kind):
    src = _codec_object(kind)
    data = src.to_bytes()
    # bytes -> object -> bytes is the identity, read or not read
    unread = StorageObject.from_bytes(data)
    assert unread.to_bytes() == data
    obj = StorageObject.from_bytes(data)
    want_vec, want_named = _eager_vectors(data)
    got = obj.vector
    if want_vec is None:
        assert got is None
    else:
        assert got.dtype == np.float32 and got.shape == want_vec.shape
        assert got.tobytes() == want_vec.tobytes()
        assert got.flags.writeable
        assert obj.vector is got  # decoded once, then the same array
    named = obj.named_vectors
    assert list(named) == list(want_named)
    for k, want in want_named.items():
        assert named[k].dtype == np.float32 and named[k].shape == want.shape
        assert named[k].tobytes() == want.tobytes()
    assert obj.named_vectors is named
    assert obj.to_bytes() == data  # and after the reads
    for f in ("uuid", "collection", "properties", "doc_id", "tenant",
              "creation_time_ms", "update_time_ms"):
        assert getattr(obj, f) == getattr(src, f)
    # writers of the attributes see plain attributes
    obj.vector = np.ones(3, np.float32)
    obj.named_vectors["extra"] = np.zeros(2, np.float32)
    back = StorageObject.from_bytes(obj.to_bytes())
    assert back.vector.tolist() == [1.0, 1.0, 1.0]
    assert "extra" in back.named_vectors


def test_object_never_read_for_its_vector_allocates_none(monkeypatch):
    data = _codec_object("both").to_bytes()
    made = []
    real = np.frombuffer
    monkeypatch.setattr(np, "frombuffer",
                        lambda *a, **kw: made.append(1) or real(*a, **kw))
    obj = StorageObject.from_bytes(data)
    assert (obj.uuid, obj.doc_id, obj.properties["n"]) == \
        ("88000000-0000-0000-0000-000000000001", 17, 3)
    obj.to_bytes()
    assert not made
    assert not any(isinstance(v, np.ndarray) for v in vars(obj).values())
    assert obj.vector is not None and obj.named_vectors["a"] is not None
    assert len(made) == 2


def test_object_with_its_times_does_not_read_the_clock(monkeypatch):
    import time as time_mod

    data = _codec_object("flat").to_bytes()

    def no_clock():
        raise AssertionError("from_bytes read the clock")

    monkeypatch.setattr(time_mod, "time", no_clock)
    obj = StorageObject.from_bytes(data)
    assert obj.creation_time_ms == 1_700_000_000_000
    monkeypatch.undo()
    fresh = StorageObject(uuid="", collection="Doc")
    assert fresh.uuid and fresh.creation_time_ms == fresh.update_time_ms > 0
