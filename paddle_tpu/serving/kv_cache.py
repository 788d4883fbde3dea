"""The KV cache for continuous-batching decode: one paged block pool.

The legacy decode path in models/gpt.py grows a `(k, v)` concat cache by
one position per step, so every step has a new shape and eager decode
retraces at every token.  The serving engine keeps static shapes instead:

* **Paged pool** (:class:`PagedKVPool` + :class:`PagedKV`) — ONE
  per-layer ``[num_blocks, block_size, kv_heads, head_dim]`` pool
  (vLLM-style fixed blocks) shared by every slot AND the prefix cache,
  addressed through a per-slot block table.  Decode attention reads only
  the table-mapped blocks below each row's length (ragged), prefix hits
  lease cached blocks straight into a slot's table (copy-free,
  refcounted), and preempting an idle sequence is just releasing its
  table entries.

All device-side helpers are pure jnp functions so they trace into one
XLA program.

Horizon-scan contract (engine.py fused decode): the engine advances all
slots H steps inside one ``lax.scan``, and lanes that hit EOS/max-tokens
mid-horizon are *frozen* — their ``pos`` stops advancing — but the scan
body still issues a cache write for every lane every step.  A frozen
lane keeps rewriting the same position with garbage.  That is safe by
construction: the garbage lands at exactly the position the next real
write will overwrite first (decode writes before it attends), everything
written is finite, and the row's visible window is bounded by ``pos``.
After a slot retires, the engine zeroes its block-table row, so any
further masked-lane writes land in the reserved scratch block 0 — slot
reuse never depends on overwriting stale rows, the freed blocks simply
return to the pool.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp


# --------------------------------------------------------------- paged

@dataclass(frozen=True)
class CacheLayout:
    """What one layer keeps of a cached token, as the MODEL states it.

    ``token_shape``: the trailing dims of a pool buffer
    ``[num_blocks, block_size, *token_shape]``.  ``buffers``: how many such
    buffers a layer has — 2 is the k/v pair every dense-attention model
    keeps (``token_shape = (kv_heads, head_dim)``, the default built by
    :func:`kv_pair_layout`); 1 is a LATENT layer (MLA), whose one buffer
    holds keys whose leading columns are the values too, so no value
    buffer exists (``pool.v`` is a list of ``None``).  The pool, the radix
    store and the host tier take their bytes a block from here; a model
    without a ``kv_cache_layout()`` method gets the k/v pair."""

    token_shape: tuple
    buffers: int = 2

    @property
    def token_elems(self):
        return int(np.prod(self.token_shape))

    def block_bytes(self, num_layers, block_size, itemsize, quantized=False):
        """Device bytes of one block across every buffer and layer (a
        quantized pool stores a 4-byte scale beside each token)."""
        token = self.token_elems * itemsize + (4 if quantized else 0)
        return self.buffers * num_layers * block_size * token


def kv_pair_layout(kv_heads, head_dim):
    return CacheLayout((int(kv_heads), int(head_dim)), 2)


def model_cache_layout(model):
    """The layout a model states (``model.kv_cache_layout()``), else the
    k/v pair of its config's ``kv_heads`` x ``head_dim``."""
    stated = getattr(model, "kv_cache_layout", None)
    if stated is not None:
        return stated()
    return kv_pair_layout(model.config.kv_heads, model.config.head_dim)


@dataclass
class PagedKV:
    """One layer's paged-cache view for a batch of lanes.

    k, v:    [num_blocks, block_size, kv_heads, head_dim] — the layer's
             slice of the unified pool (block 0 is reserved scratch)
    tables:  [batch, nb] int32 block table — entry j maps token
             positions ``j*block_size .. (j+1)*block_size-1`` of a lane
             to a pool block; 0 marks an unallocated entry (scratch)
    pos:     [batch] int32 — tokens already cached per lane; incoming
             tokens are written at positions pos .. pos+s-1 and attend
             over keys 0 .. pos+s-1 (ragged: only the table-mapped
             blocks are ever read)
    k_scale, v_scale:  [num_blocks, block_size] f32, only when the pool
             stores quantized blocks: the per-token dequantization step
             written beside each int8 token by ``paged_write_quant``;
             None on the fp path (attention then skips dequant).
    v is None on a latent pool (``CacheLayout.buffers == 1``).
    stats:   what a layer wants counted with the step's harvest, a small
             int32 vector set on the view it RETURNS (an expert layer's
             routing counts); None from every other layer, and then no
             program changes (an empty pytree).
    """

    k: jax.Array
    v: jax.Array
    tables: jax.Array
    pos: jax.Array
    k_scale: jax.Array = None
    v_scale: jax.Array = None
    stats: jax.Array = None

    @property
    def block_size(self):
        return self.k.shape[1]


def _write_coords(bs, s, tables, pos):
    """Per-token (block, offset) scatter coordinates [B, s] for a write
    of ``s`` tokens at per-lane positions ``pos`` through ``tables``.
    Positions past the table's coverage — padding lanes, frozen lanes
    whose table row was zeroed, write positions in not-yet-allocated
    entries — resolve to block 0 (scratch), where colliding garbage
    writes are harmless by convention."""
    tpos = pos[:, None] + jnp.arange(s, dtype=pos.dtype)         # [B, s]
    blk_idx = tpos // bs
    in_range = blk_idx < tables.shape[1]
    blk_idx = jnp.clip(blk_idx, 0, tables.shape[1] - 1)
    blocks = jnp.take_along_axis(tables, blk_idx, axis=1)        # [B, s]
    blocks = jnp.where(in_range, blocks, 0)
    return blocks, tpos % bs


def paged_write(pool, new, tables, pos):
    """Scatter ``new`` [B, s, H, D] into the paged ``pool``
    [NB, bs, H, D] at per-lane positions ``pos`` [B] through the block
    ``tables`` [B, nb] (out-of-coverage writes land in scratch — see
    :func:`_write_coords`)."""
    bs = pool.shape[1]
    b, s = new.shape[0], new.shape[1]
    blocks, offs = _write_coords(bs, s, tables, pos)
    flat = new.astype(pool.dtype).reshape((b * s,) + new.shape[2:])
    return pool.at[blocks.reshape(-1), offs.reshape(-1)].set(flat)


#: symmetric int8 range used for quantized KV blocks
KV_QMAX = 127.0


def paged_write_quant(pool, scales, new, tables, pos, axis_name=None):
    """Quantize-at-append: scatter ``new`` [B, s, H, D] into the int8
    ``pool`` [NB, bs, H, D] with one f32 absmax scale per TOKEN written
    beside it in ``scales`` [NB, bs].

    The scale granularity is per block-position, not per block: decode
    appends one token at a time, so a coarser per-block scale would have
    to requantize every already-written position of the block whenever a
    new token raised the block's absmax — making stored bytes (and
    therefore attention output) depend on append timing.  Per-token
    quantization is write-once: a token's stored bytes are a pure
    function of its own k/v vector, which preserves the engine's
    bitwise invariants (horizon partitioning, prefill-vs-decode replay
    on preemption resume, prefix-block sharing) within a quant config.
    The cost is 4 bytes per token against ``kv_heads*head_dim`` int8
    payload bytes.

    The per-token floor (``maximum(absmax, 1e-8)``) makes all-zero
    vectors — scratch writes, padding lanes — quantize to exact zeros,
    matching the fp pool's zero-initialized blocks.

    ``axis_name``: inside a shard_map where the head axis (H) is split
    over a mesh axis, pass that axis name and the per-token absmax is
    ``pmax``ed across shards before quantizing.  max is exact
    (associative, no rounding), so the scale equals the full-head
    absmax a single chip would compute and the stored int8 bytes of
    each shard's head slice match the single-chip pool bitwise."""
    bs = pool.shape[1]
    b, s = new.shape[0], new.shape[1]
    blocks, offs = _write_coords(bs, s, tables, pos)
    x = new.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=(2, 3))                    # [B, s]
    if axis_name is not None:
        absmax = jax.lax.pmax(absmax, axis_name)
    step = jnp.maximum(absmax, 1e-8) / KV_QMAX
    q = jnp.clip(jnp.round(x / step[..., None, None]),
                 -KV_QMAX, KV_QMAX)
    flat = q.astype(pool.dtype).reshape((b * s,) + new.shape[2:])
    bi, oi = blocks.reshape(-1), offs.reshape(-1)
    new_pool = pool.at[bi, oi].set(flat)
    new_scales = scales.at[bi, oi].set(
        step.reshape(-1).astype(scales.dtype))
    return new_pool, new_scales


class PagedKVPool:
    """The unified refcounted block pool: per layer, ONE
    ``[num_blocks, block_size, kv_heads, head_dim]`` k/v buffer pair
    shared by every slot's block table and the prefix cache.

    Block 0 is permanently reserved scratch (padding lanes and
    out-of-coverage writes target it).  Every other block is tracked by
    a host-side refcount: a slot-table entry and a prefix-store node
    each hold one reference; a block returns to the free list when the
    last reference is released — which is what makes prefix sharing
    copy-free and preemption just bookkeeping.

    ``quant_dtype="int8"`` switches block storage to int8 with a
    per-layer ``[num_blocks, block_size]`` f32 scale array beside each
    k/v buffer (``paged_write_quant`` fills both; attention dequantizes
    after the gather).  All block bookkeeping — refcounts, leasing,
    COW, preemption — is unchanged: it moves block ids, not bytes."""

    def __init__(self, num_layers, num_blocks, block_size, kv_heads,
                 head_dim, dtype=jnp.float32, quant_dtype=None,
                 layout=None):
        if num_blocks < 2:
            raise ValueError("paged pool needs >= 2 blocks (one scratch)")
        if quant_dtype not in (None, "int8"):
            raise ValueError(
                f"unsupported KV quant_dtype {quant_dtype!r} "
                "(supported: None, 'int8')")
        self.layout = layout or kv_pair_layout(kv_heads, head_dim)
        if quant_dtype and self.layout.buffers != 2:
            raise ValueError(
                "int8 KV is not supported on a latent pool: the per-token "
                "scale is taken over a k/v head pair, and a latent row's "
                "rotary key and compressed latent differ in range (use "
                "kv_cache_dtype=None)")
        self.num_layers = num_layers
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.quant_dtype = quant_dtype
        store_dtype = jnp.int8 if quant_dtype else dtype
        self.store_dtype = store_dtype
        shape = (num_blocks, block_size) + tuple(self.layout.token_shape)
        self.k = [jnp.zeros(shape, store_dtype) for _ in range(num_layers)]
        # a latent layer keeps no value buffer: None is an empty pytree,
        # so the engine's programs thread it like the fp path's scales
        self.v = [jnp.zeros(shape, store_dtype)
                  if self.layout.buffers == 2 else None
                  for _ in range(num_layers)]
        if quant_dtype:
            # zero scales dequantize the zero-initialized blocks to the
            # exact 0.0 the fp pool starts with
            sshape = (num_blocks, block_size)
            self.k_scale = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(num_layers)]
            self.v_scale = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(num_layers)]
        else:
            self.k_scale = self.v_scale = None
        self._refs = np.zeros(num_blocks, np.int32)
        self._refs[0] = 1                    # scratch: pinned forever
        self._free = list(range(num_blocks - 1, 0, -1))

    @property
    def capacity(self):
        """Allocatable blocks (excludes the scratch block)."""
        return self.num_blocks - 1

    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def blocks_in_use(self):
        return self.capacity - len(self._free)

    @property
    def bytes_per_block(self):
        """ACTUAL device bytes per block across k+v and every layer:
        payload at the storage dtype plus, when quantized, the 4-byte
        f32 scale stored beside each token — the figure the engine's
        ``serving.kv_bytes_read`` accounting multiplies, so quant bench
        numbers come from real bytes, not an fp-equivalent estimate."""
        return self.layout.block_bytes(
            self.num_layers, self.block_size,
            jnp.dtype(self.store_dtype).itemsize, bool(self.quant_dtype))

    def alloc(self):
        """Claim a free block (refcount 1), or None when exhausted."""
        if not self._free:
            return None
        bid = self._free.pop()
        self._refs[bid] = 1
        return bid

    def share(self, block_id):
        """Take one more reference on a live block (prefix lease into a
        slot table, radix-store adoption of a slot's block)."""
        if self._refs[block_id] <= 0:
            raise ValueError(f"block {block_id} shared while free")
        self._refs[block_id] += 1

    def release(self, block_id):
        """Drop one reference; the block returns to the free list when
        the last holder lets go.  Block 0 (scratch) is never released."""
        if block_id == 0:
            return
        if self._refs[block_id] <= 0:
            raise ValueError(f"block {block_id} over-released")
        self._refs[block_id] -= 1
        if self._refs[block_id] == 0:
            self._free.append(block_id)

    def refcount(self, block_id):
        return int(self._refs[block_id])

    def rebind(self, new_k, new_v, new_k_scale=None, new_v_scale=None):
        """Adopt updated pool buffers returned by a jitted program
        (scale buffers ride along on the quantized path; fp-path callers
        may pass the program's ``None`` placeholders back unchanged)."""
        self.k = list(new_k)
        self.v = list(new_v)
        if self.quant_dtype:
            self.k_scale = list(new_k_scale)
            self.v_scale = list(new_v_scale)


class PagedKVCache:
    """Engine-side owner of the paged serving cache: the unified pool,
    the per-slot block tables, and the slot free-list.

    The block table is host-authoritative (``tables`` np array, one row
    per slot, ``max_blocks_per_slot`` entries); the engine uploads the
    live prefix of each row before a dispatch whenever ``tables_dirty``
    is set.  Entries are filled lazily: admission covers the prompt,
    ``ensure_blocks`` extends coverage to each horizon's write window,
    and retirement releases every entry back to the pool."""

    def __init__(self, num_layers, num_slots, max_seq_len, block_size,
                 kv_heads, head_dim, dtype=jnp.float32, num_blocks=0,
                 extra_blocks=0, quant_dtype=None, layout=None):
        self.num_layers = num_layers
        self.num_slots = num_slots
        self.max_seq_len = max_seq_len
        self.block_size = block_size
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.dtype = dtype
        self.max_blocks_per_slot = -(-max_seq_len // block_size)
        if num_blocks <= 0:
            # auto: every slot can grow to a full row, plus headroom for
            # the prefix store, plus the scratch block
            num_blocks = (1 + num_slots * self.max_blocks_per_slot
                          + extra_blocks)
        self.pool = PagedKVPool(num_layers, num_blocks, block_size,
                                kv_heads, head_dim, dtype,
                                quant_dtype=quant_dtype, layout=layout)
        self.tables = np.zeros((num_slots, self.max_blocks_per_slot),
                               np.int32)
        self.tables_dirty = True
        self._free = list(range(num_slots - 1, -1, -1))

    # ---------------- slot bookkeeping (host side)
    def alloc(self):
        """Claim a free slot index, or None when every slot is taken."""
        return self._free.pop() if self._free else None

    def free(self, slot):
        if slot in self._free:
            raise ValueError(f"slot {slot} double-freed")
        self._free.append(slot)

    @property
    def free_slots(self):
        return len(self._free)

    @property
    def used_slots(self):
        return self.num_slots - len(self._free)

    # ---------------- block-table bookkeeping (host side)
    def lease_block(self, slot, index, block_id):
        """Map a SHARED pool block (a prefix-cache hit) into a slot's
        table: the table entry takes its own reference."""
        self.pool.share(block_id)
        self.tables[slot, index] = block_id
        self.tables_dirty = True

    def alloc_entry(self, slot, index):
        """Fill one table entry with a fresh private block; returns the
        block id or None when the pool is exhausted."""
        bid = self.pool.alloc()
        if bid is None:
            return None
        self.tables[slot, index] = bid
        self.tables_dirty = True
        return bid

    def ensure_blocks(self, slot, n_tokens):
        """Extend a slot's table to cover ``n_tokens`` positions
        (lazily: only entries still 0 are allocated).  Returns False —
        with any partial allocation kept, it stays valid coverage — when
        the pool runs dry; the engine then reclaims or preempts."""
        need = min(-(-n_tokens // self.block_size),
                   self.max_blocks_per_slot)
        for j in range(need):
            if self.tables[slot, j] == 0:
                if self.alloc_entry(slot, j) is None:
                    return False
        return True

    def release_slot_blocks(self, slot):
        """Release every table entry of a slot (retirement/preemption):
        shared blocks survive while other holders remain; private ones
        return to the pool.  The zeroed row routes any still-in-flight
        masked-lane writes to scratch."""
        row = self.tables[slot]
        for j in np.nonzero(row)[0]:
            self.pool.release(int(row[j]))
        row[:] = 0
        self.tables_dirty = True

    @property
    def leased_blocks(self):
        """Live (slot, entry) references across all block tables."""
        return int(np.count_nonzero(self.tables))

    def layer_views(self, tables, pos):
        """Per-layer PagedKV views over device arrays ``tables``/``pos``
        (the fused decode step runs every slot; inactive lanes are
        masked by their pos and write through zeroed table rows into
        scratch)."""
        ks = self.pool.k_scale or [None] * self.num_layers
        vs = self.pool.v_scale or [None] * self.num_layers
        return [PagedKV(self.pool.k[i], self.pool.v[i], tables, pos,
                        ks[i], vs[i])
                for i in range(self.num_layers)]

    def rebind(self, new_k, new_v, new_k_scale=None, new_v_scale=None):
        """Adopt updated pool buffers returned by a jitted program."""
        self.pool.rebind(new_k, new_v, new_k_scale, new_v_scale)
