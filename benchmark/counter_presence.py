"""Helpers the readers of PR 28's counters share: whether the program
under test has a counter at all (the parent of the PR that added it has
not: such a reader then gives nothing, and does not raise), and the
client bytes a run acknowledged between its two snapshots."""

from __future__ import annotations

from perf_dumps import _walk, client_ops_between


def has_counter(ctx: dict, set_prefix: str, key: str) -> bool:
    return any(True for _ in _walk(ctx["after"], set_prefix, key))


def user_bytes_between(ctx: dict) -> int:
    return client_ops_between(ctx) * ctx["traffic"]["object_bytes"]
