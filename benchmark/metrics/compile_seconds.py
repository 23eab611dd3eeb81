"""Seconds the backend spent on compile requests between the two
snapshots — hit, miss or uncached alike (`compile_cache.counters()`
`compile_s`, jax.monitoring's backend_compile_duration).  Beside
`compiles_in_window`, which counts what the cache CALLED a miss: a
"hit" that still compiles shows here.  0 in a warm run, and 0 is a
value."""

METRICS = {
    "compile_s_in_window": {
        "unit": "s", "better": "lower", "source": "program_counter",
        "layer": "compile lifecycle", "moves": "write_p95_ms"},
}


def read(ctx: dict) -> dict:
    c0, c1 = ctx["before"]["compile"], ctx["after"]["compile"]
    if "compile_s" not in c0 or "compile_s" not in c1:
        return {}
    return {"compile_s_in_window": c1["compile_s"] - c0["compile_s"]}
