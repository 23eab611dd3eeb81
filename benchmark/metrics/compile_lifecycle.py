"""Compilations inside the window: persistent-cache misses (real
compiles) between the two counter snapshots.  Must read 0 in a warm
run; it is a count, so 0 is a value and is reported."""

METRICS = {
    "compiles_in_window": {
        "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "compile lifecycle", "moves": "write_p95_ms"},
}


def read(ctx: dict) -> dict:
    return {"compiles_in_window":
            ctx["after"]["compile"]["misses"]
            - ctx["before"]["compile"]["misses"]}
