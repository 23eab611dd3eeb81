"""Store: bytes the pool's shard objects hold in the stores per user
byte acknowledged (space amplification), after the run."""

METRICS = {
    "stored_bytes_per_user_byte": {
        "unit": "ratio", "better": "lower", "source": "program_counter",
        "layer": "store", "moves": "write_MBps"},
}


def read(ctx: dict) -> dict:
    v = ctx["verdict"]
    if v["acked_bytes"] <= 0:
        return {}
    return {"stored_bytes_per_user_byte":
            v["stored_bytes"] / v["acked_bytes"]}
