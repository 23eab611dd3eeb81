"""Foundations tests: crc32c (vectors, combine, native-vs-sw), bufferlist.

Reference analogs: src/test/common/test_crc32c.cc (known-answer vectors,
crc combine), src/test/bufferlist.cc.
"""

import numpy as np
import pytest

from ceph_tpu.common import crc32c as C
from ceph_tpu.common import native
from ceph_tpu.common.buffer import BufferList


def test_known_answer_iscsi():
    # iSCSI CRC32C check value: crc("123456789") with init -1, final xor.
    assert C.crc32c(b"123456789", 0xFFFFFFFF) ^ 0xFFFFFFFF == 0xE3069283


def test_empty_and_zeros():
    assert C.crc32c(b"", 0x1234) == 0x1234
    z = C.crc32c(bytes(1000), 0xFFFFFFFF)
    assert C.crc32c_zeros(0xFFFFFFFF, 1000) == z


def test_combine():
    rng = np.random.default_rng(0)
    a = rng.integers(0, 256, 777, dtype=np.uint8).tobytes()
    b = rng.integers(0, 256, 1301, dtype=np.uint8).tobytes()
    whole = C.crc32c(a + b, 0xFFFFFFFF)
    got = C.crc32c_combine(C.crc32c(a, 0xFFFFFFFF), C.crc32c(b, 0), len(b))
    assert got == whole


def test_native_matches_software():
    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, 4097, dtype=np.uint8).tobytes()
    assert C.crc32c(data, 0xFFFFFFFF) == C._crc32c_sw(0xFFFFFFFF, data)
    assert C.crc32c_zeros(0xABCD1234, 999) == C._zeros_sw(0xABCD1234, 999)


def test_native_gf8_matvec_matches_numpy():
    if not native.available():
        pytest.skip("native library unavailable")
    from ceph_tpu.ec import gf
    rng = np.random.default_rng(2)
    mat = rng.integers(0, 256, (3, 8)).astype(np.uint8)
    chunks = rng.integers(0, 256, (8, 2048), dtype=np.uint8)
    got = native.gf8_matvec(mat, chunks)
    lut = gf.mul_table()
    ref = np.zeros((3, 2048), dtype=np.uint8)
    for i in range(3):
        for j in range(8):
            ref[i] ^= lut[mat[i, j]][chunks[j]]
    np.testing.assert_array_equal(got, ref)


def _native_in(tmp_path, monkeypatch):
    """A private copy of native/ (sources + Makefile, no objects) that
    ceph_tpu.common.native loads from, with its load state reset."""
    import shutil
    src = native._NATIVE_DIR
    for name in ("Makefile", "crc32c.c", "gf8.c"):
        shutil.copy(src / name, tmp_path / name)
    monkeypatch.setattr(native, "_NATIVE_DIR", tmp_path)
    monkeypatch.setattr(native, "_LIB_PATH",
                        tmp_path / "libceph_tpu_native.so")
    for attr, val in (("_lib", None), ("_tried", False),
                      ("_build_error", None)):
        monkeypatch.setattr(native, attr, val)
    return tmp_path


def test_native_is_built_from_sources_and_rebuilt_when_stale(
        tmp_path, monkeypatch):
    """First load runs make: a checkout holding only the .c files gets
    its library built, and a source newer than the library rebuilds it
    (make decides staleness, not `exists()`)."""
    import os
    d = _native_in(tmp_path, monkeypatch)
    assert native.available() and native.build_error() is None
    lib = d / "libceph_tpu_native.so"
    built = lib.stat().st_mtime_ns
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    stamp = lib.stat().st_mtime + 10
    os.utime(d / "crc32c.c", (stamp, stamp))
    assert native.available()
    assert lib.stat().st_mtime_ns != built


def test_native_build_failure_is_loud(tmp_path, monkeypatch):
    """A source that does not compile: load() warns with the
    compiler's output and build_error() keeps it — the python crc
    fallback must never be entered silently."""
    d = _native_in(tmp_path, monkeypatch)
    (d / "gf8.c").write_text("this is not C\n")
    with pytest.warns(RuntimeWarning, match="native library unavailable"):
        assert not native.available()
    assert "CalledProcessError" in native.build_error()
    assert "gf8.c" in native.build_error()


def test_bufferlist_append_substr():
    bl = BufferList()
    bl.append(b"hello ")
    bl.append(b"world")
    bl.append_zero(3)
    assert len(bl) == 14
    assert bl.to_bytes() == b"hello world\0\0\0"
    sub = bl.substr(3, 8)
    assert sub.to_bytes() == b"lo world"
    assert not bl.is_contiguous()
    bl.rebuild()
    assert bl.is_contiguous()


def test_bufferlist_crc_matches_flat():
    rng = np.random.default_rng(3)
    parts = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in (100, 1, 4096, 777)]
    bl = BufferList()
    for p in parts:
        bl.append(p)
    flat = b"".join(parts)
    assert bl.crc32c(0xFFFFFFFF) == C.crc32c(flat, 0xFFFFFFFF)
    # cached second call identical
    assert bl.crc32c(0xFFFFFFFF) == C.crc32c(flat, 0xFFFFFFFF)


def test_bufferlist_rebuild_aligned():
    bl = BufferList(b"x" * 1000)
    bl.append(b"y" * 24)
    bl.rebuild_aligned(64)
    arr = bl.to_numpy()
    assert arr.ctypes.data % 64 == 0
    assert arr.tobytes() == b"x" * 1000 + b"y" * 24


def test_substr_out_of_range():
    bl = BufferList(b"abc")
    with pytest.raises(IndexError):
        bl.substr(1, 5)
