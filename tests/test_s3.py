"""S3 gateway e2e: full round-trip (create bucket, put/get/range
get/list/delete, multipart upload, SigV4 auth) against an in-process
cluster.  The client side signs requests with an independent SigV4
implementation (s3api.auth.sign_request_headers), standing in for the
reference's AWS-SDK-based tests (test/s3/basic) since boto3 isn't in the
image."""
import asyncio
import hashlib
import os
import xml.etree.ElementTree as ET

import aiohttp
import pytest

from seaweedfs_tpu.s3api import Identity, IdentityAccessManagement, sign_request_headers
from seaweedfs_tpu.s3api.auth import _canonical_query  # noqa: F401 (sanity import)
from seaweedfs_tpu.server.cluster import LocalCluster

ACCESS, SECRET = "AKIDEXAMPLE", "sekrit123"


def run(coro):
    return asyncio.run(coro)


async def make_cluster(tmp_path, auth=False):
    iam = None
    if auth:
        iam = IdentityAccessManagement(
            [Identity(name="admin", credentials=[(ACCESS, SECRET)], actions=["Admin"])]
        )
    cluster = LocalCluster(
        base_dir=str(tmp_path), n_volume_servers=2, with_s3=True,
        s3_kwargs=dict(iam=iam) if iam else {},
    )
    await cluster.start()
    return cluster


class S3Client:
    """Minimal signing S3 client for tests."""

    def __init__(self, endpoint: str, access: str = "", secret: str = ""):
        self.endpoint = endpoint
        self.access = access
        self.secret = secret

    async def request(self, method, path, data=b"", headers=None, query=""):
        url = f"http://{self.endpoint}{path}"
        if query:
            url += f"?{query}"
        headers = dict(headers or {})
        if self.access:
            headers = sign_request_headers(
                method, url, headers, data, self.access, self.secret
            )
        async with aiohttp.ClientSession() as s:
            async with s.request(method, url, data=data, headers=headers) as r:
                return r.status, await r.read(), r.headers.copy()  # case-insensitive


def _xml(body):
    return ET.fromstring(body)


def _strip(tag):
    return tag.split("}")[-1]


def test_s3_basic_round_trip(tmp_path):
    async def go():
        cluster = await make_cluster(tmp_path)
        c = S3Client(cluster.s3.url)
        try:
            # create bucket
            status, _, _ = await c.request("PUT", "/mybucket")
            assert status == 200
            # duplicate rejected
            status, body, _ = await c.request("PUT", "/mybucket")
            assert status == 409
            # list buckets
            status, body, _ = await c.request("GET", "/")
            assert b"mybucket" in body

            # put / get
            payload = os.urandom(300000)
            status, _, hdrs = await c.request("PUT", "/mybucket/dir/obj1.bin", payload)
            assert status == 200
            assert hdrs["ETag"] == f'"{hashlib.md5(payload).hexdigest()}"'
            status, body, hdrs = await c.request("GET", "/mybucket/dir/obj1.bin")
            assert status == 200 and body == payload
            # range get
            status, body, _ = await c.request(
                "GET", "/mybucket/dir/obj1.bin", headers={"Range": "bytes=100-199"}
            )
            assert status == 206 and body == payload[100:200]
            # head
            status, body, hdrs = await c.request("HEAD", "/mybucket/dir/obj1.bin")
            assert status == 200 and hdrs["Content-Length"] == str(len(payload))
            # missing key
            status, _, _ = await c.request("GET", "/mybucket/nope")
            assert status == 404

            # more objects for listing
            for name in ["a.txt", "dir/obj2.bin", "zed/x", "zed/y"]:
                await c.request("PUT", f"/mybucket/{name}", b"data-" + name.encode())

            # flat list
            status, body, _ = await c.request("GET", "/mybucket")
            keys = [
                e.findtext("{%s}Key" % "http://s3.amazonaws.com/doc/2006-03-01/")
                for e in _xml(body)
                if _strip(e.tag) == "Contents"
            ]
            assert keys == ["a.txt", "dir/obj1.bin", "dir/obj2.bin", "zed/x", "zed/y"]

            # delimiter list
            status, body, _ = await c.request("GET", "/mybucket", query="delimiter=%2F")
            doc = _xml(body)
            ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
            keys = [e.findtext(f"{ns}Key") for e in doc if _strip(e.tag) == "Contents"]
            cps = [
                e.findtext(f"{ns}Prefix")
                for e in doc
                if _strip(e.tag) == "CommonPrefixes"
            ]
            assert keys == ["a.txt"] and cps == ["dir/", "zed/"]

            # prefix + delimiter
            status, body, _ = await c.request(
                "GET", "/mybucket", query="prefix=dir%2F&delimiter=%2F"
            )
            doc = _xml(body)
            keys = [e.findtext(f"{ns}Key") for e in doc if _strip(e.tag) == "Contents"]
            assert keys == ["dir/obj1.bin", "dir/obj2.bin"]

            # pagination (max-keys + continuation)
            status, body, _ = await c.request(
                "GET", "/mybucket", query="list-type=2&max-keys=2"
            )
            doc = _xml(body)
            keys = [e.findtext(f"{ns}Key") for e in doc if _strip(e.tag) == "Contents"]
            token = doc.findtext(f"{ns}NextContinuationToken")
            assert keys == ["a.txt", "dir/obj1.bin"]
            assert doc.findtext(f"{ns}IsTruncated") == "true"
            status, body, _ = await c.request(
                "GET", "/mybucket",
                query=f"list-type=2&max-keys=10&continuation-token={token}",
            )
            doc = _xml(body)
            keys = [e.findtext(f"{ns}Key") for e in doc if _strip(e.tag) == "Contents"]
            assert keys == ["dir/obj2.bin", "zed/x", "zed/y"]

            # copy
            status, body, _ = await c.request(
                "PUT", "/mybucket/copy.bin",
                headers={"x-amz-copy-source": "/mybucket/dir/obj1.bin"},
            )
            assert status == 200
            status, body, _ = await c.request("GET", "/mybucket/copy.bin")
            assert body == payload

            # delete multiple
            delete_xml = (
                b"<Delete>"
                b"<Object><Key>zed/x</Key></Object>"
                b"<Object><Key>zed/y</Key></Object>"
                b"</Delete>"
            )
            status, body, _ = await c.request(
                "POST", "/mybucket", data=delete_xml, query="delete="
            )
            assert status == 200 and body.count(b"<Deleted>") == 2

            # single delete + 404 after
            status, _, _ = await c.request("DELETE", "/mybucket/a.txt")
            assert status == 204
            status, _, _ = await c.request("GET", "/mybucket/a.txt")
            assert status == 404

            # bucket not empty
            status, _, _ = await c.request("DELETE", "/mybucket")
            assert status == 409
            for k in ["dir/obj1.bin", "dir/obj2.bin", "copy.bin"]:
                await c.request("DELETE", f"/mybucket/{k}")
            status, _, _ = await c.request("DELETE", "/mybucket")
            assert status == 204
            status, _, _ = await c.request("HEAD", "/mybucket")
            assert status == 404
        finally:
            await cluster.stop()

    run(go())


def test_s3_multipart_upload(tmp_path):
    async def go():
        cluster = await make_cluster(tmp_path)
        c = S3Client(cluster.s3.url)
        ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
        try:
            await c.request("PUT", "/mp")
            status, body, _ = await c.request(
                "POST", "/mp/big/file.bin", query="uploads="
            )
            assert status == 200
            upload_id = _xml(body).findtext(f"{ns}UploadId")
            assert upload_id

            parts = [os.urandom(5 * 1024 * 1024), os.urandom(5 * 1024 * 1024), os.urandom(1234)]
            etags = []
            for i, data in enumerate(parts, start=1):
                status, _, hdrs = await c.request(
                    "PUT", "/mp/big/file.bin", data,
                    query=f"partNumber={i}&uploadId={upload_id}",
                )
                assert status == 200
                assert hdrs["ETag"] == f'"{hashlib.md5(data).hexdigest()}"'
                etags.append(hdrs["ETag"])

            # list parts
            status, body, _ = await c.request(
                "GET", "/mp/big/file.bin", query=f"uploadId={upload_id}"
            )
            doc = _xml(body)
            nums = [
                int(p.findtext(f"{ns}PartNumber"))
                for p in doc
                if _strip(p.tag) == "Part"
            ]
            assert nums == [1, 2, 3]

            complete = "<CompleteMultipartUpload>" + "".join(
                f"<Part><PartNumber>{i}</PartNumber><ETag>{e}</ETag></Part>"
                for i, e in enumerate(etags, start=1)
            ) + "</CompleteMultipartUpload>"
            status, body, _ = await c.request(
                "POST", "/mp/big/file.bin", complete.encode(),
                query=f"uploadId={upload_id}",
            )
            assert status == 200
            etag = _xml(body).findtext(f"{ns}ETag")
            want = hashlib.md5(
                b"".join(hashlib.md5(p).digest() for p in parts)
            ).hexdigest()
            assert etag == f'"{want}-3"'

            full = b"".join(parts)
            status, body, hdrs = await c.request("GET", "/mp/big/file.bin")
            assert status == 200 and body == full
            assert hdrs["ETag"] == f'"{want}-3"'
            # ranged read across part boundary
            status, body, _ = await c.request(
                "GET", "/mp/big/file.bin",
                headers={"Range": f"bytes={5 * 1024 * 1024 - 100}-{5 * 1024 * 1024 + 99}"},
            )
            assert body == full[5 * 1024 * 1024 - 100 : 5 * 1024 * 1024 + 100]

            # staging dir is gone
            status, body, _ = await c.request("GET", "/mp", query="uploads=")
            assert body.count(b"<Upload>") == 0

            # abort flow
            status, body, _ = await c.request("POST", "/mp/tmp.bin", query="uploads=")
            uid2 = _xml(body).findtext(f"{ns}UploadId")
            await c.request(
                "PUT", "/mp/tmp.bin", b"x" * 1000, query=f"partNumber=1&uploadId={uid2}"
            )
            status, _, _ = await c.request(
                "DELETE", "/mp/tmp.bin", query=f"uploadId={uid2}"
            )
            assert status == 204
            status, body, _ = await c.request(
                "GET", "/mp/tmp.bin", query=f"uploadId={uid2}"
            )
            assert status == 404
        finally:
            await cluster.stop()

    run(go())


def test_s3_sigv4_auth(tmp_path):
    async def go():
        cluster = await make_cluster(tmp_path, auth=True)
        good = S3Client(cluster.s3.url, ACCESS, SECRET)
        bad_key = S3Client(cluster.s3.url, "AKIDWRONG", SECRET)
        bad_secret = S3Client(cluster.s3.url, ACCESS, "wrong")
        anon = S3Client(cluster.s3.url)
        try:
            status, _, _ = await good.request("PUT", "/auth-bucket")
            assert status == 200
            status, _, _ = await good.request("PUT", "/auth-bucket/f", b"hello")
            assert status == 200

            status, body, _ = await anon.request("GET", "/auth-bucket/f")
            assert status == 403 and b"AccessDenied" in body
            status, body, _ = await bad_key.request("GET", "/auth-bucket/f")
            assert status == 403 and b"InvalidAccessKeyId" in body
            status, body, _ = await bad_secret.request("GET", "/auth-bucket/f")
            assert status == 403 and b"SignatureDoesNotMatch" in body

            status, body, _ = await good.request("GET", "/auth-bucket/f")
            assert status == 200 and body == b"hello"

            # signing covers the query string too
            status, body, _ = await good.request(
                "GET", "/auth-bucket", query="list-type=2&prefix=f"
            )
            assert status == 200 and b"<Key>f</Key>" in body
        finally:
            await cluster.stop()

    run(go())


def test_s3_review_regressions(tmp_path):
    """Round-2 code-review findings: prefix-delete no-op, traversal
    rejection, write-action bulk delete, dir markers, copy metadata,
    aws-chunked decode."""

    async def go():
        cluster = await make_cluster(tmp_path)
        c = S3Client(cluster.s3.url)
        ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
        try:
            await c.request("PUT", "/rb")
            await c.request("PUT", "/rb/a/b", b"B")
            await c.request("PUT", "/rb/a/c", b"C")

            # DELETE of a key matching a prefix directory must be a no-op
            status, _, _ = await c.request("DELETE", "/rb/a")
            assert status == 204
            status, body, _ = await c.request("GET", "/rb/a/b")
            assert status == 200 and body == b"B"  # subtree survived

            # path traversal rejected (raw socket: clients normalize '..'
            # before sending, attackers don't)
            for raw_path in ("/rb/../evil", "/rb/a/../c", "/rb/%2e%2e/evil"):
                reader, writer = await asyncio.open_connection(
                    cluster.s3.ip, cluster.s3.port
                )
                writer.write(
                    f"PUT {raw_path} HTTP/1.1\r\nHost: x\r\n"
                    "Content-Length: 1\r\n\r\nz".encode()
                )
                await writer.drain()
                status_line = await reader.readline()
                assert b"400" in status_line, (raw_path, status_line)
                writer.close()

            # directory marker keys
            status, _, _ = await c.request("PUT", "/rb/folder/", b"")
            assert status == 200
            status, _, _ = await c.request("PUT", "/rb/folder/inner.txt", b"in")
            assert status == 200  # prefix not shadowed by a file
            status, body, _ = await c.request("GET", "/rb/folder/inner.txt")
            assert body == b"in"
            await c.request("DELETE", "/rb/folder/inner.txt")
            status, _, _ = await c.request("DELETE", "/rb/folder/")
            assert status == 204

            # copy preserves content-type + metadata
            await c.request(
                "PUT", "/rb/src.json", b"{}",
                headers={"Content-Type": "application/json", "X-Amz-Meta-K": "v"},
            )
            await c.request(
                "PUT", "/rb/dst.json",
                headers={"x-amz-copy-source": "/rb/src.json"},
            )
            status, _, hdrs = await c.request("GET", "/rb/dst.json")
            assert hdrs["Content-Type"] == "application/json"
            assert hdrs.get("x-amz-meta-k") == "v"

            # aws-chunked framing is stripped
            payload = b"hello-chunked-world" * 100
            framed = (
                f"{len(payload):x};chunk-signature=deadbeef\r\n".encode()
                + payload
                + b"\r\n0;chunk-signature=deadbeef\r\n\r\n"
            )
            status, _, _ = await c.request(
                "PUT", "/rb/chunked.bin", framed,
                headers={
                    "x-amz-content-sha256": "STREAMING-AWS4-HMAC-SHA256-PAYLOAD",
                    "Content-Encoding": "aws-chunked",
                },
            )
            assert status == 200
            status, body, _ = await c.request("GET", "/rb/chunked.bin")
            assert body == payload
        finally:
            await cluster.stop()

    run(go())


def test_s3_readonly_identity_cannot_bulk_delete(tmp_path):
    async def go():
        iam = IdentityAccessManagement(
            [
                Identity(name="admin", credentials=[(ACCESS, SECRET)], actions=["Admin"]),
                Identity(
                    name="reader",
                    credentials=[("AKIDREAD", "readsecret")],
                    actions=["Read", "List"],
                ),
            ]
        )
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=1, with_s3=True,
            s3_kwargs=dict(iam=iam),
        )
        await cluster.start()
        admin = S3Client(cluster.s3.url, ACCESS, SECRET)
        reader = S3Client(cluster.s3.url, "AKIDREAD", "readsecret")
        try:
            await admin.request("PUT", "/guard")
            await admin.request("PUT", "/guard/keep", b"data")
            delete_xml = b"<Delete><Object><Key>keep</Key></Object></Delete>"
            status, body, _ = await reader.request(
                "POST", "/guard", data=delete_xml, query="delete="
            )
            assert status == 403
            status, body, _ = await reader.request("GET", "/guard/keep")
            assert status == 200 and body == b"data"
            # plain object delete also denied for the reader
            status, _, _ = await reader.request("DELETE", "/guard/keep")
            assert status == 403
        finally:
            await cluster.stop()

    run(go())


def test_s3_tagging_and_metadata(tmp_path):
    async def go():
        cluster = await make_cluster(tmp_path)
        c = S3Client(cluster.s3.url)
        ns = "{http://s3.amazonaws.com/doc/2006-03-01/}"
        try:
            await c.request("PUT", "/tb")
            status, _, _ = await c.request(
                "PUT", "/tb/o", b"data",
                headers={
                    "X-Amz-Tagging": "env=prod&team=storage",
                    "X-Amz-Meta-Owner": "me",
                },
            )
            assert status == 200
            status, body, _ = await c.request("GET", "/tb/o", query="tagging=")
            doc = _xml(body)
            tags = {
                t.findtext(f"{ns}Key"): t.findtext(f"{ns}Value")
                for t in doc.iter(f"{ns}Tag")
            }
            assert tags == {"env": "prod", "team": "storage"}
            status, _, hdrs = await c.request("GET", "/tb/o")
            assert hdrs.get("x-amz-meta-owner") == "me"
            # replace tags
            new = b"<Tagging><TagSet><Tag><Key>only</Key><Value>one</Value></Tag></TagSet></Tagging>"
            status, _, _ = await c.request("PUT", "/tb/o", new, query="tagging=")
            assert status == 200
            status, body, _ = await c.request("GET", "/tb/o", query="tagging=")
            assert b"only" in body and b"env" not in body
            status, _, _ = await c.request("DELETE", "/tb/o", query="tagging=")
            assert status == 204
            status, body, _ = await c.request("GET", "/tb/o", query="tagging=")
            assert b"<Tag>" not in body
        finally:
            await cluster.stop()

    run(go())


def test_s3_request_payment_and_signed_response_overrides(tmp_path):
    """GetBucketRequestPayment returns the BucketOwner payer document
    (reference s3api_bucket_handlers.go:352-360); response-* GetObject
    overrides are honored only on SIGNED requests when auth is enabled —
    AWS rejects them on anonymous reads with 400 InvalidRequest."""

    async def go():
        iam = IdentityAccessManagement(
            [
                Identity(
                    name="admin",
                    credentials=[(ACCESS, SECRET)],
                    actions=["Admin"],
                ),
                Identity(name="anonymous", actions=["Read"]),
            ]
        )
        cluster = LocalCluster(
            base_dir=str(tmp_path), n_volume_servers=1, with_s3=True,
            s3_kwargs=dict(iam=iam),
        )
        await cluster.start()
        signed = S3Client(cluster.s3.url, ACCESS, SECRET)
        anon = S3Client(cluster.s3.url)
        try:
            status, _, _ = await signed.request("PUT", "/payb")
            assert status == 200
            status, body, _ = await signed.request(
                "GET", "/payb", query="requestPayment"
            )
            assert status == 200
            assert _strip(_xml(body).tag) == "RequestPaymentConfiguration"
            payer = [c for c in _xml(body) if _strip(c.tag) == "Payer"]
            assert payer and payer[0].text == "BucketOwner"
            status, body, _ = await signed.request(
                "GET", "/no-such-bucket", query="requestPayment"
            )
            assert status == 404

            status, _, _ = await signed.request("PUT", "/payb/o.txt", b"pub")
            assert status == 200
            # the anonymous identity can read the object...
            status, body, _ = await anon.request("GET", "/payb/o.txt")
            assert status == 200 and body == b"pub"
            # ...but cannot rewrite its presentation headers
            status, body, _ = await anon.request(
                "GET", "/payb/o.txt",
                query="response-content-type=text/evil",
            )
            assert status == 400 and b"InvalidRequest" in body
            # a signed reader can
            status, _, hdrs = await signed.request(
                "GET", "/payb/o.txt",
                query="response-content-type=text/plain",
            )
            assert status == 200
            assert hdrs["Content-Type"].startswith("text/plain")
        finally:
            await cluster.stop()

    run(go())


@pytest.mark.parametrize(
    "drop_shards", [(), (0, 11)], ids=["healthy", "two_shards_dropped"]
)
def test_s3_get_rides_the_resident_ec_path(tmp_path, drop_shards):
    """End to end through the gateway and the filer: an object whose
    chunk lies on an EC volume pinned in the device cache comes back
    byte-equal, and its chunk read is admitted on the dispatcher's
    batched route under the s3 origin (ec_read_route_total
    {route="s3_batched"}) — with every shard mounted and with two
    destroyed, where the bytes can only come from a reconstruct."""
    from degraded_cluster import build_degraded_cluster
    from seaweedfs_tpu import stats
    from seaweedfs_tpu.repair import RepairConfig

    payload = os.urandom(40_000)

    async def put_object(cluster):
        client = S3Client(cluster.s3.url)
        status, _, _ = await client.request("PUT", "/resident")
        assert status == 200
        status, _, _ = await client.request(
            "PUT", "/resident/obj", data=payload
        )
        assert status == 200

    def routed(route):
        return stats.REGISTRY.get_sample_value(
            "SeaweedFS_volumeServer_ec_read_route_total", {"route": route}
        ) or 0

    async def go():
        cluster, vs, _blobs, _vid = await build_degraded_cluster(
            str(tmp_path), n_blobs=6, device_cache=True,
            drop_shards=drop_shards, with_s3=True, fill=put_object,
            # the master would rebuild the destroyed shards by itself
            master_kwargs={"ec_repair": RepairConfig(enabled=False)},
        )
        try:
            entry = cluster.filer.filer.find_entry(
                "/buckets/resident/obj"
            )
            vids = {int(c.file_id.split(",")[0]) for c in entry.chunks}
            assert vids, "the object must be chunked, not inline"
            assert all(vs.store.ec_volume_is_resident(v) for v in vids)
            batched0, native0 = routed("s3_batched"), routed("s3_native")
            status, body, _ = await S3Client(cluster.s3.url).request(
                "GET", "/resident/obj"
            )
            assert status == 200 and body == payload
            assert routed("s3_batched") - batched0 == len(entry.chunks)
            assert routed("s3_native") == native0
        finally:
            await cluster.stop()

    run(go())
