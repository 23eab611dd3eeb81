"""Plain reference for an RBD image whose data objects lie on an
erasure-coded pool that accepts overwrites, served while one OSD is
down: the image model and the encoding are `rbd_image_ec`'s, the
reference beside this one, taken as they are (the healthy deployment
and this one differ by one dead OSD, not by what a shard must hold);
what this file adds is the DECODE a degraded cluster has to do.

Independent of the system under test: neither file imports anything
of ceph_tpu; numpy does the field arithmetic through `rbd_image_ec`'s
256 x 256 product table, built there from the polynomial.

The semantics added here (upstream doc/rados/operations/
erasure-code.rst, doc/dev/osd_internals/erasure_coding):

- degraded: the generator [I; C] is MDS, so ANY k of the k+m shards
  give the object: take the k rows of the generator that belong to
  the surviving shards, invert that k x k matrix over GF(2^8)
  (Gauss-Jordan), and the data shards are its product with the
  survivors (`decode_data`).  A shard whose holder is down is not
  written: it stays what it was when the holder died.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np


def _beside(name: str):
    """The reference of that name in this directory, by its path (the
    harness and the tests load references the same way)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_references_{name}", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_healthy = _beside("rbd_image_ec")
ImageModel = _healthy.ImageModel
expected_shards = _healthy.expected_shards
cauchy_parity_matrix = _healthy.cauchy_parity_matrix
gf_inv = _healthy.gf_inv
_MUL = _healthy._MUL


# -- the degraded half -------------------------------------------------------

def generator_matrix(k: int, m: int) -> np.ndarray:
    """The (k+m) x k systematic generator: identity over the parity
    rows of `cauchy_parity_matrix`."""
    return np.concatenate([np.eye(k, dtype=np.uint8),
                           cauchy_parity_matrix(k, m)], axis=0)


def gf_invert(mat: np.ndarray) -> np.ndarray:
    """Inverse of a square matrix over GF(2^8) by Gauss-Jordan."""
    n = mat.shape[0]
    a = np.concatenate([mat.astype(np.uint8),
                        np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        pivot = next((r for r in range(col, n) if a[r, col]), None)
        if pivot is None:
            raise ValueError("matrix is singular over GF(2^8)")
        if pivot != col:
            a[[col, pivot]] = a[[pivot, col]]
        a[col] = _MUL[gf_inv(int(a[col, col]))][a[col]]
        for r in range(n):
            if r != col and a[r, col]:
                a[r] ^= _MUL[int(a[r, col])][a[col]]
    return a[:, n:]


def decode_data(survivors: dict, k: int, m: int) -> np.ndarray:
    """The k data shards from ANY k surviving shards
    {shard index: bytes of that shard}: rows of the generator for the
    survivors, inverted, times the survivors."""
    if len(survivors) != k:
        raise ValueError(f"need exactly k={k} survivors, "
                         f"got {len(survivors)}")
    order = sorted(survivors)
    inv = gf_invert(generator_matrix(k, m)[order])
    rows = [np.asarray(survivors[s], dtype=np.uint8) for s in order]
    data = np.zeros((k, rows[0].size), dtype=np.uint8)
    for i in range(k):
        for j in range(k):
            if inv[i, j]:
                data[i] ^= _MUL[int(inv[i, j])][rows[j]]
    return data


def object_from_shards(survivors: dict, k: int, m: int,
                       stripe_unit: int, size: int) -> np.ndarray:
    """The object's first `size` bytes from any k of its shards."""
    data = decode_data(survivors, k, m)
    return data.reshape(k, -1, stripe_unit).transpose(1, 0, 2) \
        .reshape(-1)[:size]
