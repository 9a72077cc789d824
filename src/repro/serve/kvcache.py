"""Paged (block-pool) KV cache for continuous batching.

The device side is ONE fixed-shape pool per layer stack —
``{"k","v"}: (L, num_blocks, block_size, K, hd)`` for GQA, one latent row
per token ``{"latent": (L, num_blocks, block_size, D)}`` for MLA — so
every jitted step sees static shapes no matter how requests join, leave,
grow, or get preempted. The host side is a free-list allocator plus per-slot block
tables (``(max_slots, max_blocks_per_slot)`` int32) that map each slot's
logical positions onto physical blocks.

Block 0 is reserved as the **null block**: table entries past a slot's
allocation point at it, writes into it are garbage, and reads from it are
always masked by the per-slot length — so padded tables need no special
casing inside jit.
"""

from __future__ import annotations

from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig

NULL_BLOCK = 0


def pool_sharding(cfg: ModelConfig, mesh):
    """The pool's fixed layout on ``mesh`` (None without one): KV heads
    split like the attention projections, blocks whole on every shard; a
    latent pool (MLA, no head dim) whole on every device."""
    if mesh is None:
        return None
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding import paged_pool_spec
    if cfg.attention == "mla":
        return NamedSharding(mesh, P())
    return NamedSharding(mesh, paged_pool_spec(mesh, cfg.num_kv_heads,
                                               stacked=True))


def init_block_pool(cfg: ModelConfig, num_blocks: int, block_size: int,
                    dtype=jnp.bfloat16, mesh=None) -> Dict[str, jnp.ndarray]:
    """Device-side pool, created in place in its ``pool_sharding``: GQA
    ``{"k","v"}: (L, N, bs, K, hd)``, MLA ``{"latent": (L, N, bs, D)}``
    (one row per token, ``attention.mla_row_width``). ``L`` is the uniform
    stack's depth; leading dense layers (``cfg.first_k_dense``) get a pool
    of their own under ``"dense"``, as ``init_cache`` lays out the linear
    cache. Requires a uniform-stack decoder (the continuous engine asserts
    this)."""
    if cfg.attention == "mla":
        from repro.models.attention import mla_row_width
        row = {"latent": (num_blocks, block_size, mla_row_width(cfg))}
    else:
        kv = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
        row = {"k": kv, "v": kv}

    def make():
        pool = {n: jnp.zeros((cfg.num_moe_layers,) + s, dtype)
                for n, s in row.items()}
        if cfg.first_k_dense:
            pool["dense"] = {n: jnp.zeros((cfg.first_k_dense,) + s, dtype)
                             for n, s in row.items()}
        return pool
    return jax.jit(make, out_shardings=pool_sharding(cfg, mesh))()


def write_prefill_blocks(pool: Dict[str, jnp.ndarray],
                         temp: Dict[str, jnp.ndarray],
                         table: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Scatter a prefilled linear cache (each leaf (L, 1, S_pad, ...)) into
    the pool (each leaf (L, N, bs, ...)).

    ``table``: (S_pad // block_size,) physical-block ids (traced). Entries
    past the request's allocation are NULL_BLOCK — those writes land in
    the null block and are never read. jit this once per prefill bucket.
    """
    def upd(p, t):
        L, _, S = t.shape[:3]
        bs = p.shape[2]
        blocks = t.reshape((L, S // bs, bs) + t.shape[3:])
        return p.at[:, table].set(blocks.astype(p.dtype))
    with jax.named_scope("attn.kv_write"):
        return jax.tree.map(upd, pool, temp)


class BlockAllocator:
    """Host-side free-list over the physical blocks (block 0 reserved).

    ``quota`` caps *in-use* blocks below the physical pool size, so a
    fleet arbiter can carve one physical pool into per-model shares and
    move capacity between them without reshaping any device array.
    Shrinking the quota below current usage is legal: nothing is
    reclaimed eagerly, the allocator just refuses growth until enough
    blocks drain back through ``free`` (deferred handback).
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need at least one block beyond the null block")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: deque = deque(range(1, num_blocks))
        self._quota = num_blocks - 1

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    @property
    def quota(self) -> int:
        return self._quota

    def set_quota(self, n: int) -> None:
        """Cap in-use blocks at ``n`` (clamped to the physical pool)."""
        self._quota = max(0, min(int(n), self.num_blocks - 1))

    def blocks_for(self, num_tokens: int) -> int:
        return -(-num_tokens // self.block_size)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n physical blocks, or None (all-or-nothing) if the pool is dry
        or the grant would exceed the quota."""
        if n > len(self._free) or self.in_use + n > self._quota:
            return None
        return [self._free.popleft() for _ in range(n)]

    def free(self, blocks: List[int]):
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("freeing the null block")
            self._free.append(b)


class SlotTables:
    """Per-slot logical->physical block maps + lengths, as one pinned numpy
    pair that is shipped to the device every iteration (small: ints)."""

    def __init__(self, max_slots: int, max_blocks_per_slot: int):
        self.max_slots = max_slots
        self.max_blocks_per_slot = max_blocks_per_slot
        self.tables = np.full((max_slots, max_blocks_per_slot), NULL_BLOCK,
                              np.int32)
        self.lengths = np.zeros((max_slots,), np.int32)
        self.owned: List[List[int]] = [[] for _ in range(max_slots)]

    def assign(self, slot: int, blocks: List[int], length: int):
        self.tables[slot] = NULL_BLOCK
        self.tables[slot, :len(blocks)] = blocks
        self.lengths[slot] = length
        self.owned[slot] = list(blocks)

    def grow(self, slot: int, block: int):
        n = len(self.owned[slot])
        if n >= self.max_blocks_per_slot:
            raise ValueError(f"slot {slot} exceeds max_blocks_per_slot")
        self.tables[slot, n] = block
        self.owned[slot].append(block)

    def release(self, slot: int) -> List[int]:
        blocks, self.owned[slot] = self.owned[slot], []
        self.tables[slot] = NULL_BLOCK
        self.lengths[slot] = 0
        return blocks

    def capacity_tokens(self, slot: int, block_size: int) -> int:
        """Positions this slot can hold before needing another block."""
        return len(self.owned[slot]) * block_size
