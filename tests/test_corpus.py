"""Encode-bytes corpus non-regression.

Re-expresses reference src/test/erasure-code/
ceph_erasure_code_non_regression.cc: archived encodings pin every
plugin's parity bytes, so a kernel or table change can never silently
change what's on disk (which would brick every object written by an
older build).

The corpus (tests/corpus/encode_corpus.json) stores sha256 digests of
every chunk for a deterministic payload per (plugin, profile).
Regenerate ONLY for a deliberate, documented format break:

    python tests/test_corpus.py --regenerate
"""

import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry

CORPUS = Path(__file__).parent / "corpus" / "encode_corpus.json"
PAYLOAD_LEN = 4096

CASES = [
    ("jerasure", {"k": "4", "m": "2", "technique": "reed_sol_van"}),
    ("jerasure", {"k": "4", "m": "2", "technique": "cauchy_good"}),
    ("jerasure", {"k": "6", "m": "3", "technique": "reed_sol_van"}),
    ("jerasure", {"k": "4", "m": "2", "technique": "liberation",
                  "w": "7"}),
    ("jerasure", {"k": "4", "m": "2", "technique": "blaum_roth",
                  "w": "6"}),
    ("jerasure", {"k": "4", "m": "2", "technique": "liber8tion"}),
    ("isa", {"k": "4", "m": "2"}),
    ("jax", {"k": "4", "m": "2", "technique": "cauchy"}),
    ("jax", {"k": "2", "m": "1", "technique": "cauchy"}),
    ("lrc", {"k": "4", "m": "2", "l": "3"}),
    ("shec", {"k": "4", "m": "3", "c": "2"}),
    ("clay", {"k": "4", "m": "2"}),
    ("example", {}),
]


def _case_id(plugin: str, profile: dict) -> str:
    return plugin + "/" + ",".join(f"{k}={v}"
                                   for k, v in sorted(profile.items()))


def _payload() -> bytes:
    rng = np.random.default_rng(0xC0FFEE)
    return rng.integers(0, 256, PAYLOAD_LEN, dtype=np.uint8).tobytes()


def _encode_digests(plugin: str, profile: dict) -> dict:
    reg = ErasureCodePluginRegistry.instance()
    codec = reg.factory(plugin, dict(profile))
    data = _payload()
    want = codec.get_chunk_size(len(data)) * codec.get_data_chunk_count()
    padded = np.frombuffer(data.ljust(want, b"\x00"), dtype=np.uint8)
    chunks = codec.encode(set(range(codec.get_chunk_count())), padded)
    return {str(s): hashlib.sha256(
        np.asarray(c).tobytes()).hexdigest()
        for s, c in sorted(chunks.items())}


def regenerate() -> None:
    corpus = {_case_id(p, prof): _encode_digests(p, prof)
              for p, prof in CASES}
    CORPUS.parent.mkdir(parents=True, exist_ok=True)
    CORPUS.write_text(json.dumps(corpus, indent=1, sort_keys=True))
    print(f"wrote {len(corpus)} cases to {CORPUS}")


@pytest.mark.parametrize("plugin,profile", CASES,
                         ids=[_case_id(p, prof) for p, prof in CASES])
def test_encode_bytes_pinned(plugin, profile):
    assert CORPUS.exists(), \
        "corpus missing — run python tests/test_corpus.py --regenerate"
    corpus = json.loads(CORPUS.read_text())
    cid = _case_id(plugin, profile)
    assert cid in corpus, f"case {cid} not in corpus — regenerate"
    got = _encode_digests(plugin, profile)
    assert got == corpus[cid], (
        f"ENCODING CHANGED for {cid}: parity bytes no longer match the "
        f"pinned corpus. If this is intentional (format break), document "
        f"it and regenerate; otherwise the kernel change corrupts every "
        f"existing object.")


if __name__ == "__main__":
    # standalone run: the corpus is pinned from the CPU platform
    import jax
    jax.config.update("jax_platforms", "cpu")
    if "--regenerate" in sys.argv:
        regenerate()
    else:
        print(__doc__)
