"""Store, what an EC overwrite costs its shard holders beyond the
write itself, per user byte: the bytes copied into kept generations
(`osd.N` `ec_shard_clone_bytes`: the rollback state, the WHOLE shard
object per overwrite today) and the bytes re-read and re-hashed for
the shard's `chunk_crc` (`ec_shard_chunk_crc_bytes`).  k+m if both
were O(write).  A program without these counters gives nothing."""

from counter_presence import has_counter, user_bytes_between
from perf_dumps import counter_delta

_STORE = {"unit": "ratio", "better": "lower",
          "source": "program_counter", "layer": "store",
          "moves": "write_MBps"}
_COUNTERS = {"rollback_clone_bytes_per_user_byte": "ec_shard_clone_bytes",
             "chunk_crc_bytes_per_user_byte": "ec_shard_chunk_crc_bytes"}
METRICS = {name: dict(_STORE) for name in _COUNTERS}


def read(ctx: dict) -> dict:
    user = user_bytes_between(ctx)
    if user <= 0:
        return {}
    return {name: counter_delta(ctx, "osd.", counter) / user
            for name, counter in _COUNTERS.items()
            if has_counter(ctx, "osd.", counter)}
