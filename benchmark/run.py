#!/usr/bin/env python3
"""The benchmark's entry: one cell, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name from
BENCHMARK.json: benchmark/configs/<config>.json (the deployment, with
the plain reference it names in benchmark/references/),
benchmark/traffic/<traffic>.json (the parameters of a generator in
benchmark/generators/), and the per-layer readers globbed from
benchmark/metrics/.  No cell, configuration, mix or metric is named in
this file.

One process holds the chip and runs mon, OSDs and client as threads.
A run that finds no accelerator fails and prints no result.  The last
stdout line is the result object the driver reads.

`--rehearse` is for the builder: tiny sizes on the CPU, to find wrong
paths before a chip call.  It prints no device metric and says so.
"""

from __future__ import annotations

import argparse
import faulthandler
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DEADLINE_S = 1150       # the contract allows a cold run 1200 s


def process_start() -> float:
    """time.time() at which this process was started, from /proc, so
    that the interpreter's own start-up counts as set-up."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(line.split()[1]) for line in f
                         if line.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, StopIteration):
        return _IMPORTED_AT


_IMPORTED_AT = time.time()


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, found by the name alone."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_readers() -> list:
    return [load_module("metrics", os.path.basename(p)[:-3])
            for p in sorted(glob.glob(os.path.join(HERE, "metrics",
                                                   "*.py")))]


def cell_metrics(manifest: dict, cell: str, section: str,
                 reported: set | None = None) -> list[dict]:
    """The entries of `section` that this cell reports: those that
    list it, and those that list no cells (for a per-layer metric:
    whose end-to-end metric the cell reports)."""
    out = []
    for entry in manifest[section]:
        if "workloads" in entry:
            if cell in entry["workloads"]:
                out.append(entry)
        elif reported is None or entry["moves"] in reported:
            out.append(entry)
    return out


def find_devices(chips: int, rehearse: bool) -> dict:
    import jax
    devs = jax.devices()
    if not rehearse and (devs[0].platform == "cpu" or len(devs) < chips):
        raise SystemExit(
            f"benchmark: this cell needs {chips} accelerator chip(s); "
            f"JAX found {len(devs)} x {devs[0].platform} "
            f"({devs[0].device_kind}). Refusing to run.")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


class Tracer:
    """A jax.profiler trace of one slice inside the window (traced
    runs only).  Python-call tracing is off: ~80 threads of it would
    swamp both the host and the trace."""

    def __init__(self, dep, slice_s: float):
        self.dep, self.slice_s = dep, slice_s
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.window_s = None
        self.queue_bytes = None

    def __call__(self, t_open: float, t_close: float) -> None:
        import jax
        slice_s = min(self.slice_s, (t_close - t_open) / 2)
        start = t_open + (t_close - t_open - slice_s) / 2
        delay = start - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        b0, t0 = self.dep.launch_queue_bytes(), time.perf_counter()
        time.sleep(slice_s)
        b1, t1 = self.dep.launch_queue_bytes(), time.perf_counter()
        jax.profiler.stop_trace()
        self.window_s, self.queue_bytes = t1 - t0, b1 - b0

    def reduce(self) -> dict:
        import trace_reduce
        try:
            rows = trace_reduce.device_rows(
                trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        out = trace_reduce.reduce_rows(rows, self.window_s)
        out["launch_queue_bytes"] = self.queue_bytes
        return out


def run(args) -> tuple[int, dict]:
    started = process_start()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"benchmark: no workload {args.workload!r} in "
                         f"BENCHMARK.json (has: {sorted(cells)})")
    cell = cells[args.workload]
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    sys.path.insert(0, HERE)

    import deploy
    config = deploy.load_json("configs", cell["config"])
    traffic = deploy.load_json("traffic", cell["traffic"])
    if args.rehearse:
        config = deploy.rehearsal_of(config)
        traffic = deploy.rehearsal_of(traffic)
    gen = load_module("generators", traffic["generator"])
    reference = load_module("references", config["reference"])

    # -- set-up: device, compile cache, this cell's launch shapes, the
    #    cluster, the data.  Timed from process start to the opening
    #    of the window.
    device = find_devices(cell["chips"], args.rehearse)
    from ceph_tpu.common import native
    from ceph_tpu.ops import compile_cache
    if not native.available():
        raise SystemExit(f"benchmark: the native library did not "
                         f"build: {native.build_error()}")
    cache_dir = compile_cache.enable()
    setup = {"compile_cache_dir": cache_dir}
    t0 = time.perf_counter()
    setup["prewarm"] = deploy.prewarm(config,
                                      gen.launch_shapes(traffic, config))
    setup["prewarm_s"] = time.perf_counter() - t0
    dep = deploy.Deployment(config)
    snaps = {}
    try:
        t0 = time.perf_counter()
        dep.start()
        setup["boot_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        state = gen.make_payloads(traffic, args.seed)
        setup["payloads_s"] = time.perf_counter() - t0
        # what set-up allocated will not be collected: keep the
        # collector from walking it again and again inside the window
        gc.collect()
        gc.freeze()

        tracer = Tracer(dep, traffic["trace_slice_s"]) \
            if args.trace else None
        run_ = gen.drive(
            dep, traffic, state, args.seconds,
            before_window=lambda: snaps.__setitem__(
                "before", dep.snapshot()),
            in_window=tracer)
        snaps["after"] = dep.snapshot()
        setup_s = (time.time() - started) \
            - (time.perf_counter() - run_["t_open"])
        peak = memory_peak_bytes()
        e2e = gen.end_to_end(traffic, run_)
        e2e["setup_s"] = setup_s
        verdict = gen.verify(dep, traffic, state, run_, args.seed,
                             reference)
    finally:
        dep.stop()
    trace = tracer.reduce() if tracer is not None else None

    # -- the result line
    if args.trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    device["memory_peak_bytes"] = peak
    reported = {m["name"] for m in cell_metrics(
        manifest, cell["name"], "end_to_end")}
    if args.trace:
        ctx = {"config": config, "traffic": traffic, "device": device,
               "before": snaps["before"], "after": snaps["after"],
               "run": run_, "verdict": verdict, "trace": trace,
               "rehearsal": args.rehearse}
        values = {}
        for reader in metric_readers():
            values.update(reader.read(ctx) or {})
        wanted = cell_metrics(manifest, cell["name"], "per_layer",
                              reported)
    else:
        values, wanted = e2e, cell_metrics(manifest, cell["name"],
                                           "end_to_end")
    metrics = {}
    for entry in wanted:
        if args.rehearse and entry["source"] == "device_trace":
            continue
        val = values.get(entry["name"])
        if val is not None:
            metrics[entry["name"]] = {"value": val,
                                      "unit": entry["unit"]}
    result = {
        "correct": verdict["correct"],
        "attempted": verdict["attempted"],
        "failed": verdict["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace and not args.rehearse:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if args.rehearse:
        result["rehearsal"] = True
        result["device"].pop("busy_s", None)
        print("benchmark: REHEARSAL on the CPU at tiny sizes: no "
              "device metric is printed, and no number of this run "
              "is a measurement", file=sys.stderr)
    result["facts"] = {
        "workload": cell["name"], "seed": args.seed,
        "seconds": args.seconds, "window_ops": e2e["window_ops"],
        "setup": setup, "checked": verdict["checked"],
        "readback_s": verdict["readback_s"],
        "audit_s": verdict["audit_s"],
        "counter_read_s": [snaps["before"]["took_s"],
                           snaps["after"]["took_s"]],
        "osdmap_epoch": [snaps["before"]["osdmap_epoch"],
                         snaps["after"]["osdmap_epoch"]],
        "end_to_end": e2e,
    }
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in verdict["compared"].items()}
    return 0, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; prints no device "
                         "metric")
    args = ap.parse_args(argv)
    if not 0 <= args.seed < 2 ** 63:
        raise SystemExit("benchmark: --seed must be a whole number >= 0")
    # a hang must end as a failure inside the contract's time limit,
    # with every thread's stack on stderr
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    rc, result = run(args)
    faulthandler.cancel_dump_traceback_later()
    sys.stdout.flush()
    for name, row in result["compared"].items():
        print(f"compared {name}: {row['value']} (limit {row['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return rc


def cli(argv=None):
    """main(), then a hard exit with its code: threads of a
    half-stopped cluster must not hold the process."""
    try:
        code = main(argv)
    except SystemExit as e:
        if e.code not in (None, 0) and not isinstance(e.code, int):
            print(e.code, file=sys.stderr)
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:  # noqa: BLE001 — reported, then a hard exit
        import traceback
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    cli()
