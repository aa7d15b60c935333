"""Execution-strategy backends for the unified Engine.

Importing this package registers the three built-in strategies:
``segment`` (CSR sort + segmented scans), ``tile`` (padded-neighbor /
Pallas kernels), and ``sharded`` (multi-device shard_map).
"""
from repro.engine.backends import segment, sharded, tile  # noqa: F401
