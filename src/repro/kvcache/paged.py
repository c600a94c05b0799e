"""Paged KV cache: pool bookkeeping + functional ops on the pages pytree.

``PagedKVCache`` owns the geometry (block size, pool size, blocks per
slot) and the ``BlockPool`` allocator; the device pages themselves are a
plain cache pytree (``models.make_paged_cache`` — leaves shaped
``(n_superblocks, G, P, *row)``: one row per layer, head group and
page) that the engine threads through ``forward`` functionally.  The
page axis is axis 2 of every leaf.  Methods that touch pages take and return the
pytree rather than mutating hidden state, so jit boundaries stay clean.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.inference.kv_quant import KV_DTYPES
from repro.kvcache.allocator import BlockPool
from repro.models import make_paged_cache


def _rows(leaf, idx):
    """Indices of pages ``idx``' rows in every layer and head group of a
    pool leaf: ``leaf[_rows(leaf, idx)]`` is (L, G, len(idx), *row).
    Indexing all three leading axes leaves the row alone as the window, so
    on the TPU a scatter writes the rows in place; a page index on axis 2
    alone makes XLA relayout the whole pool around the scatter."""
    n_l, n_g = leaf.shape[:2]
    return (jnp.arange(n_l)[:, None, None], jnp.arange(n_g)[None, :, None],
            idx[None, None, :])


@jax.jit
def _zero_rows(leaf, idx):
    """``leaf`` with pages ``idx`` zeroed: one program per leaf shape and
    count.  Leaf by leaf, only one leaf's new copy is allocated at a time
    beside the pool (the pool is not donated)."""
    return leaf.at[_rows(leaf, idx)].set(0)


@jax.jit
def _take_rows(leaf, idx):
    """Pages ``idx``' rows of ``leaf``: (L, G, len(idx), *row)."""
    return leaf[_rows(leaf, idx)]


@jax.jit
def _put_rows(leaf, idx, rows):
    """``leaf`` with pages ``idx`` set to ``rows`` (L, G, len(idx), *row)."""
    return leaf.at[_rows(leaf, idx)].set(rows.astype(leaf.dtype))


class PagedKVCache:
    """Geometry + allocator for a block-table paged KV cache."""

    def __init__(self, cfg, *, num_blocks: int, block_size: int,
                 max_len: int, dtype=None, kv_dtype: str = "bf16"):
        if max_len < 1:
            raise ValueError(f"max_len must be >= 1, got {max_len}")
        if kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got {kv_dtype!r}")
        self.cfg = cfg
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_len = max_len
        self.kv_dtype = kv_dtype
        # every block-table row spans the full max_len so the gathered
        # logical view has ONE static shape (ceil(max_len/bs) pages) —
        # no recompiles as sequences grow, and bitwise-comparable masked
        # attention against the contiguous cache when bs divides max_len
        self.nb_per_slot = -(-max_len // block_size)
        self.pool = BlockPool(num_blocks, block_size)
        self.dtype = dtype or cfg.cdtype

    # page id guaranteed out of range: scatters drop it, gathers clamp it
    @property
    def sentinel(self) -> int:
        return self.num_blocks

    def make_pages(self, groups: int = 1):
        """Fresh zeroed pages pytree for ``forward`` (quantized layout when
        ``kv_dtype="int8"``; ``groups`` whole-head groups for tensor
        parallelism).  Stamps the pool with the per-block byte size so
        ``kv_bytes_saved`` prices shared blocks correctly."""
        pages = make_paged_cache(self.cfg, self.num_blocks, self.block_size,
                                 self.dtype, kv_dtype=self.kv_dtype,
                                 groups=groups)
        self.pool.block_bytes = self.block_bytes(pages, 1)
        return pages

    # ------------------------------------------------------------ tables
    def table_row(self, owner) -> np.ndarray:
        return self.pool.table_row(owner, self.nb_per_slot, self.sentinel)

    def block_tables(self, owners: list) -> np.ndarray:
        """(B, nb_per_slot) int32 table; ``None`` entries (inactive rows)
        become all-sentinel rows whose writes are dropped."""
        rows = [self.table_row(o) if o is not None
                else np.full(self.nb_per_slot, self.sentinel, np.int32)
                for o in owners]
        return np.stack(rows).astype(np.int32)

    # ------------------------------------------------- functional page ops
    def zero_pages(self, pages, ids: list):
        """Copy-on-free: zero-fill the freed pages before the pool hands
        them to the next owner (no cross-request KV leaks, and masked
        attention over stale entries stays exact-zero)."""
        if not ids:
            return pages
        idx = jnp.asarray(ids, jnp.int32)
        return jax.tree.map(lambda p: _zero_rows(p, idx), pages)

    def gather_host(self, pages, ids: list) -> list:
        """Copy ``ids``' page contents device->host (the offload DMA);
        returns one np.ndarray per cache leaf, in jax.tree.leaves order."""
        idx = jnp.asarray(ids, jnp.int32)
        return [np.asarray(_take_rows(leaf, idx))
                for leaf in jax.tree.leaves(pages)]

    def scatter_host(self, pages, ids: list, host_leaves: list):
        """Copy host->device into freshly allocated pages (the restore)."""
        idx = jnp.asarray(ids, jnp.int32)
        leaves, treedef = jax.tree.flatten(pages)
        return jax.tree.unflatten(treedef, [
            _put_rows(leaf, idx, jnp.asarray(h))
            for leaf, h in zip(leaves, host_leaves)])

    def copy_pages(self, pages, src_id: int, dst_id: int):
        """Copy-on-write divergence: duplicate block ``src_id``'s page
        contents into freshly allocated block ``dst_id`` across every
        cache leaf, so the subsequent write lands on a private copy."""
        src = jnp.asarray([src_id], jnp.int32)
        dst = jnp.asarray([dst_id], jnp.int32)
        return jax.tree.map(
            lambda p: _put_rows(p, dst, _take_rows(p, src)), pages)

    def block_bytes(self, pages, n_blocks: int = 1) -> int:
        """Bytes of KV held by ``n_blocks`` pool blocks across all layers."""
        total = 0
        for leaf in jax.tree.leaves(pages):
            per_block = leaf.dtype.itemsize * int(np.prod(
                leaf.shape[:2] + leaf.shape[3:]))
            total += per_block * n_blocks
        return total

    def reset(self) -> None:
        block_bytes = self.pool.block_bytes
        self.pool = BlockPool(self.num_blocks, self.block_size)
        self.pool.block_bytes = block_bytes


def default_num_blocks(max_batch: int, max_len: int, block_size: int,
                       num_blocks: Optional[int] = None,
                       kv_dtype: str = "bf16",
                       hd: Optional[int] = None,
                       payload_bytes: int = 2) -> int:
    """Pool size: explicit, else sized by KV BYTES — enough bytes for
    every slot at full length in the native cache dtype
    (capacity-equivalent to the contiguous cache).  A quantized pool
    holds the SAME byte budget, so with ``kv_dtype="int8"`` (and ``hd``
    given, for the per-entry byte math) the default grows by
    ``payload_bytes*hd / (hd+4)`` blocks (~1.88x for bf16 at hd=64) —
    that's where the extra admission capacity comes from.
    ``payload_bytes`` is the native dtype's itemsize (2 for bf16)."""
    if num_blocks is not None:
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        return num_blocks
    base = max_batch * (-(-max_len // block_size))
    if kv_dtype == "bf16" or hd is None:
        return base
    ratio = (payload_bytes * hd) / (hd + 4)
    return int(base * ratio)
