"""Bytes and operations that the work needs, from sizes and shapes.

Two models, both lower bounds on the traffic to HBM:

Sweep model (``sweep_bytes``), independent of layout and kernels.  One
sweep over a graph with ``m`` directed edges and ``n`` vertices has to read,
for every directed edge, the neighbor's id (4 B), the edge's weight (4 B)
and the neighbor's label (4 B), and has to read and write every vertex's
label (4 B + 4 B): ``12 m + 8 n`` bytes.  A propagation iteration (its two
parity sub-sweeps together visit each vertex once) and a split iteration
each cost one such sweep, so a fit moves at least
``(12 m + 8 n) * (lpa_iterations + split_iterations)`` bytes.  Whatever
implements the sweep, it cannot move less without skipping vertices; the
share of this over the time of propagation and split is the sweep's
roofline share.

Tile-kernel model (``fused_move_call`` and ``fused_split_call``), from the
operand shapes of one Pallas call over ``rows x d`` neighbor tiles:

* fused move: per tile cell the neighbor label (4 B), weight (4 B), mask
  (1 B) and changed flag (1 B) -- 10 B/cell; per row the current label,
  active, previous candidates, class and real flags in (4+1+1+1+1 B) and
  the new label and active flag out (4+1 B) -- 13 B/row.  Operations: the
  equality-masked matmul, a (1, d) x (d, d) product per row, 2 d per cell.
* fused split (no prune): neighbor label, community and mask -- 9 B/cell;
  the row's label and community in and its new label out -- 12 B/row.  No
  matmul: compare-bound, 0 operations counted.

At 2 d operations per 10 B the move kernel's intensity is 25.6 FLOP/B at
d = 128, far under a v5e's 240 FLOP/B ridge (197 TFLOP/s over 819 GB/s), so
bytes bound both kernels.  The roofline time is the larger of bytes over
bandwidth and operations over peak.
"""
from __future__ import annotations

EDGE_BYTES = 4 + 4 + 4          # neighbor id, weight, neighbor label
VERTEX_BYTES = 4 + 4            # label read, label written

MOVE_CELL_BYTES = 4 + 4 + 1 + 1
MOVE_ROW_BYTES = (4 + 1 + 1 + 1 + 1) + (4 + 1)
SPLIT_CELL_BYTES = 4 + 4 + 1
SPLIT_ROW_BYTES = (4 + 4) + 4


def sweep_bytes(m: int, n: int, sweeps: int) -> int:
    return (EDGE_BYTES * m + VERTEX_BYTES * n) * sweeps


def fused_move_call(rows: int, d: int) -> tuple[int, int]:
    """(bytes, operations) of one fused move call."""
    return (MOVE_CELL_BYTES * rows * d + MOVE_ROW_BYTES * rows,
            2 * d * rows * d)


def fused_split_call(rows: int, d: int) -> tuple[int, int]:
    return SPLIT_CELL_BYTES * rows * d + SPLIT_ROW_BYTES * rows, 0


def roofline_share(nbytes: float, flops: float, seconds: float,
                   peaks: dict) -> float | None:
    """Percent of the roofline time that ``seconds`` achieves; None when
    there is no time to divide by.  Never clamped: a reading above 100
    means the model counts too much or the time leaves out work."""
    if seconds <= 0:
        return None
    bound = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * bound / seconds
