"""Plain reference for an S3 bucket namespace whose objects lie on an
erasure-coded data pool: what an object store must answer after a set
of acknowledged PUTs, and what the data pool must hold for them.

Independent of the system under test: it imports nothing of ceph_tpu.
The model is a dict; the encoding is `ec_cauchy_crc32c`'s, the
reference beside this one, taken as it is (an S3 object of this
deployment is ONE RADOS object written whole, which is what that
reference encodes).

The semantics it reproduces (AWS S3 API reference: PutObject,
GetObject, ListObjectsV2; upstream doc/radosgw/layout.rst):

- a PUT of `body` under (bucket, key) that was answered 200 makes the
  object exist: GET returns exactly `body`, `Content-Length` its
  length, `ETag` the hex md5 of `body` in double quotes (no
  multipart, no SSE); a later acknowledged PUT of the same key
  replaces it (last writer wins);
- ListObjectsV2 returns every key of the bucket exactly once, in
  ascending order of the keys' UTF-8 bytes, each with its size and
  ETag; a page holds at most `max-keys` (1,000 by default) and a
  truncated page carries the token that continues it;
- read-after-write: both hold as soon as the 200 is out;
- the data object of (bucket, key) in the data pool is named
  `<len(bucket)>_<bucket>_<key>`: the bucket's length in decimal
  digits, an underscore, the bucket, an underscore, the key — the
  length prefix makes the split unambiguous whatever characters the
  key holds (the rule `ceph_tpu/rgw/store.py` documents at
  `_data_oid`, restated here, not imported);
- its k+m shards and their crcs: `expected_shards` of the reference
  beside this one.
"""

from __future__ import annotations

import hashlib
import importlib.util
import os


def _beside(name: str):
    """The reference of that name in this directory, by its path (the
    harness and the tests load references the same way)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_references_{name}", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


expected_shards = _beside("ec_cauchy_crc32c").expected_shards


def data_object_name(bucket: str, key: str) -> str:
    return f"{len(bucket)}_{bucket}_{key}"


def etag_of(body: bytes) -> str:
    return hashlib.md5(body).hexdigest()


class BucketModel:
    """bucket -> key -> (size, md5 hex) of every acknowledged PUT."""

    def __init__(self, buckets):
        self.buckets: dict[str, dict[str, tuple[int, str]]] = {
            b: {} for b in buckets}

    def put(self, bucket: str, key: str, body: bytes) -> None:
        self.buckets[bucket][key] = (len(body), etag_of(body))

    def expected_object(self, bucket: str, key: str
                        ) -> tuple[int, str] | None:
        """(Content-Length, ETag without quotes) a GET must carry, or
        None where the key must answer 404."""
        return self.buckets[bucket].get(key)

    def expected_listing(self, bucket: str) -> list[tuple[str, int, str]]:
        """The whole bucket as ListObjectsV2 must return it over its
        pages: (key, size, etag), keys in UTF-8 byte order."""
        rows = self.buckets[bucket]
        return [(k, *rows[k])
                for k in sorted(rows, key=lambda k: k.encode("utf-8"))]


def compare_listing(want: list[tuple[str, int, str]],
                    got: list[tuple[str, int, str]]) -> dict:
    """How a listing read back differs from the model's: keys missing,
    keys that should not be there, keys seen more than once, entries
    whose size or ETag is wrong, and whether the order is S3's."""
    want_by_key = {k: (size, etag) for k, size, etag in want}
    seen: dict[str, int] = {}
    for k, _, _ in got:
        seen[k] = seen.get(k, 0) + 1
    keys = [k for k, _, _ in got]
    return {
        "missing": sum(1 for k in want_by_key if k not in seen),
        "unexpected": sum(1 for k in seen if k not in want_by_key),
        "doubled": sum(1 for n in seen.values() if n > 1),
        "wrong": sum(1 for k, size, etag in got
                     if k in want_by_key
                     and want_by_key[k] != (size, etag)),
        "misordered": int(keys != sorted(
            keys, key=lambda k: k.encode("utf-8"))),
    }
