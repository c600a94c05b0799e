"""The page-row layout of the served paged KV pool.

The pool's writers (``layers.attention``), the paged decode kernel and its
reference all lay a page out through ``row_shape``, so none of them takes
the layout from another."""
from __future__ import annotations

LANES = 128


def row_shape(block_size: int, heads: int, width: int) -> tuple:
    """Trailing shape of one pool row: a page's ``block_size`` tokens of
    ``heads`` heads of ``width`` values, head-major (head, token, value).

    Where each head's tokens fill whole groups of 8 rows of 128 lanes, the
    row is (heads·rph, 128) with rph = block_size·width/128: on the TPU a
    page is then one contiguous run of whole tiles, which a DMA copies as
    it lies, and a head's rows are an aligned slice.  Otherwise the row
    stays flat."""
    per_head = block_size * width
    if per_head % (8 * LANES) == 0:
        return (heads * per_head // LANES, LANES)
    return (heads * per_head,)
