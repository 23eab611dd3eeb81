#!/usr/bin/env python3
"""The yardstick checked against known numbers, on the CPU, in
seconds, with no chip and no cluster:

    python3 benchmark/selfcheck.py

- the trace reduction on the recorded trace in testdata/;
- the percentile and rate arithmetic on fixed lists;
- the roofline's work for k8m3 and k2m1 at 4 MiB and 4 KiB;
- the plain reference against published test vectors;
- BENCHMARK.json against the files it names and the readers' own
  declarations;
- that a cell, a configuration, a traffic mix and a per-layer metric
  are found when added as new files and entries, nothing edited.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)
    print(f"ok   {what}")


def near(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-300)


def check_stats() -> None:
    from stats import percentile_nearest_rank, rate_per_s
    xs = [float(i) for i in range(1, 101)]
    check(percentile_nearest_rank(xs, 0.95) == 95.0,
          "p95 of 1..100 is the 95th value")
    check(percentile_nearest_rank(xs[:20], 0.95) == 19.0,
          "p95 of 1..20 is the 19th value (nearest rank, ceil)")
    check(percentile_nearest_rank([3.0, 1.0, 2.0], 0.5) == 2.0,
          "median of an unsorted list")
    check(percentile_nearest_rank([7.0], 0.95) == 7.0,
          "one sample is every percentile")
    check(near(rate_per_s(590 * 4194304, 51.0) / 1e6, 48.52234039215686),
          "590 x 4 MiB in 51 s is 48.52 MB/s (10^6 bytes)")


def check_roofline() -> None:
    from roofline import chunk_bytes, encode_work, roofline_seconds
    kind = "TPU v5 lite"
    cases = [
        # k, m, object bytes -> chunk, bytes moved, ops, bound
        (8, 3, 4 << 20, 524288, 11 * 524288 + 44, 3072 * 524288, "hbm"),
        (2, 1, 4 << 20, 2097152, 3 * 2097152 + 12, 256 * 2097152, "hbm"),
        (8, 3, 4096, 4096, 11 * 4096 + 44, 3072 * 4096, "hbm"),
        (2, 1, 4096, 4096, 3 * 4096 + 12, 256 * 4096, "hbm"),
    ]
    for k, m, size, chunk, moved, ops, bound in cases:
        c = chunk_bytes(size, k, 4096)
        w = encode_work(k, m, c)
        t, b = roofline_seconds(w, kind)
        check(c == chunk and w["bytes_in"] + w["bytes_out"] == moved
              and w["ops"] == ops and b == bound
              and near(t, max(moved / 819e9, ops / 393e12)),
              f"roofline work k{k}m{m} at {size} B: {moved} B moved, "
              f"{ops} ops, {b}-bound, {t * 1e6:.3f} us")
    try:
        roofline_seconds(encode_work(8, 3, 4096), "TPU v9")
    except KeyError:
        check(True, "a device kind without published peaks is an error")
    else:
        check(False, "a device kind without published peaks is an error")


def check_reference() -> None:
    import numpy as np
    ref = _load("references", "ec_cauchy_crc32c")
    check(ref.crc32c_ceph(b"123456789") == 0xE3069283 ^ 0xFFFFFFFF,
          "crc32c('123456789') is the standard 0xe3069283 before "
          "ceph's missing final inversion")
    t = ref.gf_mul_table()
    check(t[2][0x80] == 0x1D and t[0x53][ref.gf_inv(0x53)] == 1
          and t[3][7] == 9,
          "GF(2^8) products under the polynomial 0x11d")
    check(ref.cauchy_parity_matrix(2, 1).tolist()
          == [[ref.gf_inv(2), ref.gf_inv(3)]],
          "k2m1 Cauchy row is [1/(2^0), 1/(2^1)]")
    data = bytes(range(256)) * 40                      # 10240 B
    shards, crcs = ref.expected_shards(data, 2, 1, 4096)
    check(shards.shape == (3, 8192) and len(crcs) == 3,
          "10240 B on k2m1/4096 pads to 2 stripes: 3 shards of 8192 B")
    raw = np.frombuffer(data, dtype=np.uint8)
    check(np.array_equal(shards[0][:4096], raw[:4096])
          and np.array_equal(shards[1][:4096], raw[4096:8192])
          and np.array_equal(shards[0][4096:6144], raw[8192:])
          and not shards[1][4096:].any(),
          "striping: chunk c of stripe i is bytes [i*k*su + c*su, +su)")
    c = ref.cauchy_parity_matrix(2, 1)[0]
    col = 5
    check(int(shards[2][col]) == int(t[c[0]][shards[0][col]]
                                     ^ t[c[1]][shards[1][col]]),
          "parity byte = sum over GF(2^8) of coefficient x data byte")


def check_trace() -> None:
    import trace_reduce as tr
    check(tr.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30,
          "union of overlapping and nested intervals")
    check(tr.gaps_ns([(0, 10), (5, 20), (30, 40)], 0, 50) == [10, 10],
          "gaps between intervals inside a span")
    fam = tr.load_families()
    check(all(tr.family_of(n, fam) == "fused_encode" for n in (
              "jit__hier_acc_core(123)", "jit__hier_lsub_core",
              "jit__combine_run", "jit_gf_encode_with_crc_pallas_w32"))
          and tr.family_of("jit_squeeze", fam) == "other",
          "kernel families by the name patterns of the data file")
    path = os.path.join(HERE, "testdata", "trace_rows.json")
    with open(path) as f:
        recorded = json.load(f)
    got = tr.reduce_rows(recorded["rows"], recorded["window_s"])
    want = recorded["expect"]
    check(got["planes"] == want["planes"]
          and near(got["busy_s"], want["busy_s"])
          and near(got["families"]["fused_encode"]["seconds"],
                   want["fused_seconds"])
          and got["families"]["fused_encode"]["launches"]
          == want["fused_launches"],
          f"recorded trace reduces to busy {want['busy_s']} s, "
          f"{want['fused_launches']} fused launches in "
          f"{want['fused_seconds']} s")


def _load(kind: str, name: str):
    import run
    return run.load_module(kind, name)


def check_manifest() -> None:
    import run
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    declared = {}
    for reader in run.metric_readers():
        declared.update(reader.METRICS)
    listed = {m["name"]: {k: v for k, v in m.items()
                          if k not in ("name", "workloads")}
              for m in manifest["per_layer"]}
    check(listed == declared,
          "BENCHMARK.json per_layer equals what the readers in "
          "benchmark/metrics declare")
    import deploy
    for cfg in manifest["configs"]:
        with open(os.path.join(ROOT, cfg["file"])) as f:
            spec = json.load(f)
        check(sorted(spec["reduced"]) == sorted(cfg["reduced"])
              and spec["source"] == cfg["source"],
              f"{cfg['file']} states the source and cuts that "
              f"BENCHMARK.json lists for {cfg['name']}")
        _load("references", spec["reference"])
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for cell in manifest["workloads"]:
        traffic = deploy.load_json("traffic", cell["traffic"])
        gen = _load("generators", traffic["generator"])
        check(set(gen.END_TO_END) <= e2e,
              f"cell {cell['name']}: generator "
              f"{traffic['generator']} reports known end-to-end metrics")


def check_discovery() -> None:
    """A throwaway cell, configuration, traffic mix and metric, added
    to a copy of the benchmark as new files and entries only."""
    import importlib
    tmp = tempfile.mkdtemp(prefix="bench_selfcheck_")
    try:
        shutil.copytree(HERE, os.path.join(tmp, "benchmark"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        bench = os.path.join(tmp, "benchmark")
        base_cfg = manifest["workloads"][0]["config"]
        base_trf = manifest["workloads"][0]["traffic"]
        for kind, src, dst in (("configs", base_cfg, "throwaway_cfg"),
                               ("traffic", base_trf, "throwaway_mix")):
            with open(os.path.join(bench, kind, f"{src}.json")) as f:
                spec = json.load(f)
            spec["name"] = dst
            with open(os.path.join(bench, kind, f"{dst}.json"),
                      "w") as f:
                json.dump(spec, f)
        with open(os.path.join(bench, "metrics", "throwaway.py"),
                  "w") as f:
            f.write('METRICS = {"throwaway_count": {"unit": "count", '
                    '"better": "lower", "source": "program_counter", '
                    '"layer": "client", "moves": "write_MBps"}}\n\n\n'
                    'def read(ctx):\n'
                    '    return {"throwaway_count": len(ctx["run"]'
                    '["ops"])}\n')
        manifest["configs"].append(
            {"name": "throwaway_cfg", "source": "none",
             "file": "benchmark/configs/throwaway_cfg.json",
             "reduced": [], "why": "selfcheck"})
        manifest["workloads"].append(
            {"name": "throwaway_cell", "config": "throwaway_cfg",
             "traffic": "throwaway_mix", "chips": 1, "why": "selfcheck"})
        manifest["per_layer"].append(
            {"name": "throwaway_count", "unit": "count",
             "better": "lower", "source": "program_counter",
             "layer": "client", "moves": "write_MBps"})
        sys.path.insert(0, bench)
        for name in ("run", "deploy"):
            sys.modules.pop(name, None)
        run = importlib.import_module("run")
        deploy = importlib.import_module("deploy")
        check(os.path.dirname(run.__file__) == bench,
              "the copy of the harness is the one under test")
        check(deploy.load_json("configs", "throwaway_cfg")["name"]
              == "throwaway_cfg"
              and deploy.load_json("traffic", "throwaway_mix")["name"]
              == "throwaway_mix",
              "a new configuration and a new traffic mix are found by "
              "name")
        readers = run.metric_readers()
        values = {}
        for r in readers:
            if "throwaway_count" in r.METRICS:
                values = r.read({"run": {"ops": [1, 2, 3]}})
        check(values == {"throwaway_count": 3},
              "a new per-layer reader is found by globbing")
        reported = {m["name"] for m in run.cell_metrics(
            manifest, "throwaway_cell", "end_to_end")}
        wanted = {m["name"] for m in run.cell_metrics(
            manifest, "throwaway_cell", "per_layer", reported)}
        check("throwaway_count" in wanted and "setup_s" in reported,
              "a new cell reports the metrics whose end-to-end metric "
              "it reports, the new one among them")
    finally:
        sys.path.remove(bench) if bench in sys.path else None
        for name in ("run", "deploy"):
            sys.modules.pop(name, None)
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    for part in (check_stats, check_roofline, check_reference,
                 check_trace, check_manifest, check_discovery):
        print(f"-- {part.__name__}")
        part()
    print("selfcheck: all ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
