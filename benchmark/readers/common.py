"""Readers that any role's cells can use. A reader takes what a traced run
hands over (`obs`: the reduced trace, the runner's observations, the cell's
configuration and traffic, the chip's peaks) and returns a number, or None
where it finds nothing to read."""
from __future__ import annotations


def observed(obs, key, scale=1.0):
    """A count or reading the runner noted under `key`."""
    value = obs["run"].get(key)
    return None if value is None else value * scale


def device_idle_share(obs):
    share = obs["trace"].get("idle_share") if obs["trace"] else None
    return None if share is None else 100.0 * share


def op_time_share(obs, pattern):
    """Device time of the operations matching `pattern`, as a share of the
    device's busy time."""
    from benchmark.lib import trace_reduce
    seconds, count = trace_reduce.op_seconds(obs["trace"], pattern)
    if not count or not obs["trace"]["busy_s"]:
        return None
    return 100.0 * seconds / obs["trace"]["busy_s"]
