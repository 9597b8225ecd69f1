"""Shared mesh-axis classification for the context-parallel attention
paths (ring_attention / ulysses): conventional batch-like and head-like
axis names pass through shard_map untouched on their natural dims."""
from __future__ import annotations

BATCH_AXIS_NAMES = ("dp", "fsdp", "data", "sharding")
HEAD_AXIS_NAMES = ("mp", "tp", "model")


def classify_axes(jmesh, seq_axis: str):
    """Returns (batch_axes, head_axes) among the mesh axes != seq_axis."""
    others = [a for a in jmesh.axis_names if a != seq_axis]
    batch_axes = tuple(a for a in others if a in BATCH_AXIS_NAMES)
    head_axes = tuple(a for a in others if a in HEAD_AXIS_NAMES)
    return batch_axes, head_axes
