"""paddle_tpu.inference.decode.block_pool — paged KV-cache allocator.

The dense KV cache (`GPTForCausalLM.init_cache`) allocates one
``[B, max_len, Hkv*D]`` buffer per layer per *batch slot*: every sequence
pays for its worst-case length up front, and a serving batch of mixed
lengths wastes most of that memory. The paged layout (vLLM/PagedAttention,
SOSP '23) instead keeps ONE device-resident pool of fixed-size blocks per
layer —

    k_pool: [num_blocks, block_size, Hkv*D]       (bf16 cache)
    kq/ks/vq/vs pools for the int8 layout           (int8 values +
                                                    [num_blocks, block_size,
                                                    Hkv] f32 scales)

(a cache row is stored flat, heads x head_dim side by side: the minor
dimension is then a multiple of the chip's 128 lanes and the device keeps
the pool row-major as it is, where ``[.., Hkv, 64]`` made every step
relayout the whole pool twice — PERF.md section 5) — and gives each
sequence a *block table*: the ordered list of pool block
ids that hold its tokens (token position ``p`` lives at
``(table[p // block_size], p % block_size)``). Sequences allocate blocks
as they grow and return them the moment they finish, so the pool's
capacity is shared by actual token usage, not worst-case reservations.

Blocks are REFCOUNTED: beyond its allocating owner, a block can be
referenced by other owners (`incref`) — the engine's prefix cache and
prefix-sharing sequences hold one reference each, so N sequences over a
shared system prompt keep ONE physical copy of the shared blocks. A
block returns to the free list when its LAST reference drops (`decref` /
`free_owned`); a holder that must mutate a block it does not exclusively
own copies it first (`copy_block` — copy-on-write, orchestrated by the
engine).

`BlockKVCache` is the allocator half: device tensors plus a host-side
free list, per-owner reference accounting, and conservation/fragmentation
stats. Scheduling (who allocates when, gather/scatter through the tables,
COW policy) lives in `engine.DecodeEngine`; the TPU-native
read-through-the-table attention kernel is
`ops/pallas/decode_attn.paged_decode_attention`.

Block 0 is RESERVED as the padding sink: padded rows of a bucketed decode
step carry an all-zeros block table, so their (garbage) KV writes land in
block 0 and can never corrupt a live sequence — the allocator simply
never hands block 0 out.

The same paging idiom serves LoRA adapters: `adapter_pool.AdapterPool`
pages per-tenant A/B weights through slot-stacked device tensors with
the identical refcount/reserved-slot-0/LRU-eviction contract (slots
instead of blocks, `release_owned` instead of `free_owned`), so
multi-tenant decode shares one allocator mental model end to end.

A model with RECURRENT layers (the gated delta rule of
`models/linear_attention.py`) keeps, for those layers, a state of fixed size
a sequence and no rows at all. Such a layer's entry in the pool is one SLOT a
sequence — tensors `[num_slots + 1, ...]`, not `[num_blocks, block_size,
...]` — handed out by `alloc_slot` at admission and returned by `free_slot`
when the sequence ends. Slot 0 is reserved like block 0: padded rows of a
bucketed step read and write it. A slot is never shared and never copied;
what the engine cannot do with one (prefix reuse, copy-on-write,
speculation's rollback) it refuses at construction. `slots + free slots +
reserved == total` holds beside the blocks' law.

A LATENT-ATTENTION layer (`models/latent_attention.py`) keeps rows like any
attention layer, but one tensor of them and not a pair: its entry spec is a
1-tuple `((kv_lora_rank + qk_rope_head_dim,), dtype, 1)`, a block of it is
`[block_size, 576]` at the published sizes, and it is allocated, shared,
copied and freed by block table with the other layers' blocks. Nothing here
tells the three kinds apart but `slot_layers`: a layer's tuple is walked,
whatever its length.

Invariant (asserted by the decode fault-injection harness):
``allocated + free + reserved == total`` at all times (a block is
"allocated" while it has >= 1 reference, however many holders share it),
and a drained engine always returns to ``allocated == 0`` — no fault
path may leak a block or a reference.
"""
from __future__ import annotations

import math

from ...analysis import locks as _locks

__all__ = ["BlockKVCache", "OutOfBlocks"]

#: block ids below this are never allocated (block 0 = padding sink)
RESERVED_BLOCKS = 1
#: likewise slot 0 of a recurrent layer's state
RESERVED_SLOTS = 1


class OutOfBlocks(RuntimeError):
    """The pool cannot satisfy an allocation. The engine's admission gate
    reserves worst-case growth for every admitted sequence, so live
    sequences never see this — it surfaces only on over-admission bugs or
    direct allocator misuse."""


class BlockKVCache:
    """Device-resident paged KV pool + host-side free-list allocator.

    Args:
        num_blocks: total pool blocks (>= RESERVED_BLOCKS + 1).
        block_size: tokens per block.
        entry_specs: per-layer tuple of ``(suffix_shape, dtype,
            kv_heads)`` triples — one per cache tensor in the layer's
            cache-entry order (``(k, v)`` for bf16, ``(kq, ks, vq, vs)``
            for int8). Each pool tensor is allocated as ``[num_blocks,
            block_size, *suffix_shape]`` of the given dtype (the model's
            K/V suffix is one flat row, ``(Hkv*D,)``); ``kv_heads`` is
            how many heads lie side by side along suffix dimension 0,
            which is all `shard_` needs to know of them. Models build
            this via ``init_block_pool`` so the geometry always matches
            their ``decode_step`` cache layout.
        quant: informational layout tag (None or "int8") carried for
            engine fingerprinting and stats.
        name: informational pool tag carried in stats()/repr — the
            speculative decode engine runs TWO pools (the target model's
            and the draft model's, same conservation law each), and a
            leak report must say which one leaked.
        slot_layers: per-layer booleans, True where the layer's entry is
            one slot a sequence (a recurrent state) and its `entry_specs`
            tuple holds ``(suffix_shape, dtype)`` pairs, allocated as
            ``[num_slots + 1, *suffix_shape]``. None: every layer holds
            blocks.
        num_slots: slots to hand out (needed with a slot layer).
    """

    def __init__(self, num_blocks, block_size, entry_specs, quant=None,
                 name=None, slot_layers=None, num_slots=0):
        import jax.numpy as jnp

        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if num_blocks < RESERVED_BLOCKS + 1:
            raise ValueError(
                f"num_blocks must be > {RESERVED_BLOCKS} (block 0 is the "
                f"reserved padding sink), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.quant = quant
        self.name = name
        #: per-layer tuples of device arrays; the engine replaces this
        #: wholesale after each committed (prefill/decode) step
        self.slot_layers = tuple(bool(x) for x in (
            slot_layers or [False] * len(entry_specs)))
        self.num_slots = int(num_slots) if any(self.slot_layers) else 0
        if any(self.slot_layers) and self.num_slots < 1:
            raise ValueError(
                f"a pool with slot layers needs num_slots >= 1, got "
                f"{num_slots}")
        #: (shape, dtype) of every pool tensor, per layer
        self._specs = [
            tuple(((self.num_slots + RESERVED_SLOTS, *spec[0]), spec[1])
                  for spec in layer) if is_slot else
            tuple(((self.num_blocks, self.block_size, *suffix), dtype)
                  for suffix, dtype, _ in layer)
            for layer, is_slot in zip(entry_specs, self.slot_layers)]
        self.tensors = [tuple(jnp.zeros(shape, dtype)
                              for shape, dtype in layer)
                        for layer in self._specs]
        self._kv_heads = [() if is_slot else tuple(h for _, _, h in layer)
                          for layer, is_slot in zip(entry_specs,
                                                    self.slot_layers)]
        #: device bytes of ONE slot over all slot layers
        self.slot_bytes = sum(
            math.prod(t.shape[1:]) * t.dtype.itemsize
            for layer, is_slot in zip(self.tensors, self.slot_layers)
            if is_slot for t in layer)
        self._free_slots = list(range(self.num_slots + RESERVED_SLOTS - 1,
                                      RESERVED_SLOTS - 1, -1))
        self._slot_of = {}         # owner -> slot id
        self.slot_allocs = 0
        self.slot_frees = 0
        self.peak_slots = 0
        self.mesh = None        # set by shard_() for tensor-parallel pools
        self.shardings = None
        self._lock = _locks.new_lock("decode.block_pool")
        self._free = list(range(self.num_blocks - 1, RESERVED_BLOCKS - 1,
                                -1))  # pop() hands out low ids first
        self._refs = {}            # block id -> list of holder tags
        self.allocs = 0
        self.frees = 0
        self.increfs = 0
        self.decrefs = 0
        self.failed_allocs = 0
        self.peak_allocated = 0

    # -- tensor-parallel placement (paddle_tpu.sharding) -------------------
    def shard_(self, mesh, rules=None):
        """Shard every pool tensor along the KV heads (logical axis
        "kv", suffix dim 0 — pool layout [N, bs, Hkv*D] or [N, bs, Hkv])
        over `mesh` via the axis-rule table: contiguous groups of whole
        heads, so what divides is the entry's head count, not the flat
        row's width. Head counts an axis does not divide replicate
        instead of erroring. Returns the per-tensor NamedShardings (per
        layer, matching `tensors` structure)."""
        import jax
        from ... import sharding as _shardlib

        if any(self.slot_layers):
            raise ValueError(
                "a pool with recurrent-state slots does not shard: only "
                "rows of kv heads have a sharding rule")
        self.mesh = mesh
        self.shardings = [
            tuple(_shardlib.logical_to_sharding(
                (None, None, "kv") + (None,) * (t.ndim - 3),
                mesh, rules=rules, shape=(*t.shape[:2], heads))
                for t, heads in zip(layer, layer_heads))
            for layer, layer_heads in zip(self.tensors, self._kv_heads)]
        self.tensors = [
            tuple(jax.device_put(t, sh) for t, sh in zip(layer, shs))
            for layer, shs in zip(self.tensors, self.shardings)]
        return self.shardings

    def reset_tensors(self):
        """New zeroed tensors of the pool's own shapes and placement, in
        place of the old ones, which are deleted: for the engine whose
        dispatch consumed the pool and failed. Whatever the rows and
        slots held is gone; the allocator's books are the caller's to
        clear (every table it handed out names rows that no longer
        exist)."""
        import jax
        import jax.numpy as jnp

        for layer in self.tensors:
            for t in layer:
                if not t.is_deleted():
                    t.delete()
        self.tensors = [tuple(jnp.zeros(shape, dtype)
                              for shape, dtype in layer)
                        for layer in self._specs]
        if self.shardings is not None:
            self.tensors = [
                tuple(jax.device_put(t, sh) for t, sh in zip(layer, shs))
                for layer, shs in zip(self.tensors, self.shardings)]

    # -- geometry ----------------------------------------------------------
    def blocks_for(self, num_tokens):
        """Blocks needed to hold `num_tokens` cache positions."""
        return max(1, math.ceil(num_tokens / self.block_size))

    @property
    def capacity_tokens(self):
        """Token capacity of the allocatable (non-reserved) pool."""
        return (self.num_blocks - RESERVED_BLOCKS) * self.block_size

    # -- allocation --------------------------------------------------------
    def alloc(self, n, owner=None):
        """All-or-nothing allocation of `n` blocks (one reference each,
        held by `owner`); returns their ids. Raises `OutOfBlocks`
        (leaving the pool untouched) when fewer than `n` are free."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        with self._lock:
            if n > len(self._free):
                self.failed_allocs += 1
                raise OutOfBlocks(
                    f"pool exhausted: {n} block(s) requested, "
                    f"{len(self._free)} free of "
                    f"{self.num_blocks - RESERVED_BLOCKS} allocatable")
            blocks = [self._free.pop() for _ in range(n)]
            for b in blocks:
                self._refs[b] = [owner]
            self.allocs += n
            self.peak_allocated = max(self.peak_allocated, len(self._refs))
            return blocks

    def reset_peak(self):
        """Re-arm the `peak_allocated` high-water mark at the CURRENT
        allocation level and return it. The mark is otherwise monotone
        for the life of the pool, which makes it useless for windowed
        measurements on a long-lived engine (capacity tests, admission
        headroom probes) — resetting turns `peak_allocated - allocated`
        into a per-window footprint delta."""
        with self._lock:
            self.peak_allocated = len(self._refs)
            return self.peak_allocated

    def incref(self, blocks, owner=None):
        """Add one `owner`-held reference to each allocated block — the
        prefix-sharing move: a sequence (or the prefix cache) joins an
        existing physical copy instead of allocating its own. Unknown /
        reserved ids raise ValueError."""
        with self._lock:
            for b in blocks:
                if b not in self._refs:
                    raise ValueError(
                        f"block {b} is not allocated — cannot add a "
                        f"reference (reserved/unknown id?)")
            for b in blocks:
                self._refs[b].append(owner)
            self.increfs += len(blocks)

    def decref(self, blocks, owner=None):
        """Drop one `owner`-held reference per block; a block whose last
        reference drops returns to the free list. An owner dropping a
        reference it does not hold raises ValueError (a refcount bug must
        be loud). Returns how many blocks were physically freed."""
        with self._lock:
            for b in blocks:
                holders = self._refs.get(b)
                if holders is None or owner not in holders:
                    raise ValueError(
                        f"block {b} holds no reference for owner "
                        f"{owner!r} (double-decref, or a reserved/unknown "
                        f"id)")
            freed = 0
            for b in blocks:
                holders = self._refs[b]
                holders.remove(owner)
                self.decrefs += 1
                if not holders:
                    del self._refs[b]
                    self._free.append(b)
                    self.frees += 1
                    freed += 1
            return freed

    def refcount(self, block):
        """Current reference count of `block` (0 if free/unknown)."""
        with self._lock:
            return len(self._refs.get(block, ()))

    def free(self, blocks):
        """Return exclusively-held blocks to the pool. Double-frees and
        reserved/unknown ids raise ValueError (a conservation bug must be
        loud), as does freeing a SHARED block — a holder of a shared
        block must `decref` with its owner tag instead."""
        with self._lock:
            for b in blocks:
                holders = self._refs.get(b)
                if holders is None:
                    raise ValueError(
                        f"block {b} is not allocated (double-free, or a "
                        f"reserved/unknown id)")
                if len(holders) != 1:
                    raise ValueError(
                        f"block {b} is SHARED ({len(holders)} refs) — "
                        f"free() is for exclusive blocks; use decref()")
            for b in blocks:
                del self._refs[b]
                self._free.append(b)
            self.decrefs += len(blocks)
            self.frees += len(blocks)

    def free_owned(self, owner):
        """Drop every reference held by `owner` (freeing blocks whose
        last reference that was); returns how many references were
        dropped. Idempotent (an owner with no references drops zero) —
        the engine's eviction paths call this so a sequence can never
        double-free, shared prefix blocks included."""
        with self._lock:
            dropped = 0
            for b in [b for b, hs in self._refs.items() if owner in hs]:
                holders = self._refs[b]
                n = holders.count(owner)
                self._refs[b] = holders = [h for h in holders
                                           if h != owner]
                dropped += n
                self.decrefs += n
                if not holders:
                    del self._refs[b]
                    self._free.append(b)
                    self.frees += 1
            return dropped

    # -- recurrent-state slots ---------------------------------------------
    def alloc_slot(self, owner):
        """The slot `owner` keeps its recurrent state in (one an owner;
        asking again returns the same). Raises `OutOfBlocks` when every
        slot is taken. What the slot holds is its last owner's: the
        engine zeroes it before the first chunk."""
        with self._lock:
            if owner in self._slot_of:
                return self._slot_of[owner]
            if not self._free_slots:
                self.failed_allocs += 1
                raise OutOfBlocks(
                    f"state slots exhausted: all {self.num_slots} in use")
            slot = self._free_slots.pop()
            self._slot_of[owner] = slot
            self.slot_allocs += 1
            self.peak_slots = max(self.peak_slots, len(self._slot_of))
            return slot

    def free_slot(self, owner):
        """Return `owner`'s slot (None, and nothing done, where it holds
        none: idempotent like `free_owned`)."""
        with self._lock:
            slot = self._slot_of.pop(owner, None)
            if slot is not None:
                self._free_slots.append(slot)
                self.slot_frees += 1
            return slot

    @property
    def slots_in_use(self):
        with self._lock:
            return len(self._slot_of)

    # -- copy-on-write -----------------------------------------------------
    def copy_block(self, src, dst):
        """Device-copy block `src`'s rows into block `dst` across every
        layer tensor — the eager reference implementation of the
        copy-on-write primitive (each `at[].set` functionally
        re-materializes its whole pool tensor, so this is for tests and
        small pools). The engine's hot path uses a compiled DONATED
        single-dispatch copy instead (`DecodeEngine._cow_fn`), which
        aliases the pool buffers in place."""
        self.tensors = [
            layer if is_slot else tuple(t.at[dst].set(t[src])
                                        for t in layer)
            for layer, is_slot in zip(self.tensors, self.slot_layers)]

    @property
    def free_count(self):
        with self._lock:
            return len(self._free)

    @property
    def allocated_count(self):
        with self._lock:
            return len(self._refs)

    # -- observability -----------------------------------------------------
    def stats(self):
        """Snapshot. Conservation: ``allocated + free + reserved ==
        total`` always holds (checked here, not just reported) — a block
        counts as allocated while ANY holder references it;
        ``shared_refs`` reports how many references ride on top of the
        first (the capacity multiplier prefix sharing buys)."""
        with self._lock:
            allocated = len(self._refs)
            free = len(self._free)
            assert allocated + free + RESERVED_BLOCKS == self.num_blocks, (
                f"block conservation violated: {allocated} allocated + "
                f"{free} free + {RESERVED_BLOCKS} reserved != "
                f"{self.num_blocks} total")
            shared_blocks = sum(1 for hs in self._refs.values()
                                if len(hs) > 1)
            shared_refs = sum(len(hs) - 1 for hs in self._refs.values()
                              if len(hs) > 1)
            slots = len(self._slot_of)
            assert slots + len(self._free_slots) == self.num_slots, (
                f"slot conservation violated: {slots} in use + "
                f"{len(self._free_slots)} free != {self.num_slots}")
            return {
                "name": self.name,
                "state_slots_total": self.num_slots,
                "state_slots": slots,
                "state_slots_peak": self.peak_slots,
                "state_slot_bytes": self.slot_bytes,
                "state_slot_allocs": self.slot_allocs,
                "state_slot_frees": self.slot_frees,
                "total": self.num_blocks,
                "reserved": RESERVED_BLOCKS,
                "block_size": self.block_size,
                "quant": self.quant,
                "free": free,
                "allocated": allocated,
                "shared_blocks": shared_blocks,
                "shared_refs": shared_refs,
                "peak_allocated": self.peak_allocated,
                "allocs": self.allocs,
                "frees": self.frees,
                "increfs": self.increfs,
                "decrefs": self.decrefs,
                "failed_allocs": self.failed_allocs,
                "utilization": allocated / max(
                    1, self.num_blocks - RESERVED_BLOCKS),
            }

    def __repr__(self):
        s = self.stats()
        if self.name:
            return (f"BlockKVCache[{self.name}](total={s['total']}, "
                    f"free={s['free']}, allocated={s['allocated']}, "
                    f"shared={s['shared_refs']}, "
                    f"block_size={self.block_size}, quant={self.quant!r})")
        return (f"BlockKVCache(total={s['total']}, free={s['free']}, "
                f"allocated={s['allocated']}, shared={s['shared_refs']}, "
                f"block_size={self.block_size}, quant={self.quant!r})")
