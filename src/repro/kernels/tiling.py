"""VMEM tile budgeting shared by the kernel wrappers and ops dispatch.

The label-scan kernels materialise a (TILE_B, D, D) equality cube in VMEM.
``pick_tile_b`` is the one place tile sizes are derived, so every
cube-building dispatch goes through the same rules:

* Mosaic requires the second-to-last block dimension to be a multiple of
  8 (the sublane count) unless the block spans the whole array, so a row
  tile is a multiple of ``MIN_TILE_B`` or the whole ``n_pad``;
* the preferred tile keeps the cube within ``CUBE_BUDGET_BYTES`` (4 MB),
  leaving headroom for the (TILE_B, D) operand tiles and double-buffering;
* at widths where even 8 rows exceed that budget the tile is 8 rows, and
  ``MAX_TILE_DEGREE`` is the widest row whose kernels were compiled for a
  TPU v5e at 8 rows (``tests/test_tpu_compile.py``).  Wrappers that build
  the cube assert ``CUBE_LIMIT_BYTES``, the cube at that widest width
  (R004 checks the assert is present).
"""
from __future__ import annotations

MIN_TILE_B = 8
MAX_TILE_B = 256
MAX_TILE_DEGREE = 1024
CUBE_BUDGET_BYTES = 4 * 1024 * 1024
CUBE_LIMIT_BYTES = MIN_TILE_B * MAX_TILE_DEGREE * MAX_TILE_DEGREE * 4


def pick_tile_b(n_pad: int, d_max: int) -> int:
    """Largest row tile Mosaic accepts whose equality cube fits the budget.

    Returns a multiple of 8 that divides ``n_pad``, or ``n_pad`` itself
    when ``n_pad`` is not a multiple of 8 (a whole-array block).
    """
    if n_pad % MIN_TILE_B:
        return n_pad
    tile = CUBE_BUDGET_BYTES // max(d_max * d_max * 4, 1)
    tile = min(max(tile, MIN_TILE_B), MAX_TILE_B, n_pad)
    tile -= tile % MIN_TILE_B
    while n_pad % tile:
        tile -= MIN_TILE_B
    return tile
