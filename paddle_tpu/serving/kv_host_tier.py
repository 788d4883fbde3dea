"""Host-RAM spill arena under the unified paged KV pool: the tier that
turns eviction and preemption from "recompute it" into "copy it back".

Today's device pool is a strict cache of computed KV: an LRU-evicted
radix chain is simply gone, and a preempted lane re-prefills its whole
history at O(context) FLOPs.  This module adds the tier below it — a
numpy-backed, byte-budgeted host arena holding ``device_get`` copies of

* **demoted prefix blocks** — ``PrefixCache._evict`` hands the victim's
  full token path and block bytes here instead of dropping them, so the
  effective prefix cache stretches from HBM into host RAM; and
* **preempted lane images** — ``Engine.preempt`` saves the lane's whole
  block chain keyed by request id, so re-admission can re-bind the
  blocks with one batched host→device upload instead of re-prefilling.

Bitwise safety is inherited, not re-proven: stored bytes are exactly
the bytes the device pool held.  For fp pools a block's bytes are a
pure function of the tokens it covers (prefill-vs-decode write parity,
the preemption-resume doctrine engine.py already enforces); for int8
pools the per-token write-once absmax scales (``paged_write_quant``)
make stored bytes a pure function of each token's k/v vector — so a
host round-trip is indistinguishable from recompute, and the engine's
existing resume-divergence check doubles as the parity gate.  int8
blocks are stored at their quantized density: the arena's payload
arrays take the pool's ``store_dtype`` and the f32 scale planes ride
beside them (~4x more contexts per host byte than an fp arena).

Layout: ``k``/``v`` are ``[capacity, num_layers, block_size, kv_heads,
head_dim]`` arrays at the pool's storage dtype, plus
``[capacity, num_layers, block_size]`` f32 scale planes when the pool
is quantized — one host block mirrors one device block across every
layer, so a swap moves whole-block rows with no reshapes.  ``capacity``
is ``budget_bytes // bytes_per_block`` with ``bytes_per_block`` taken
from the DEVICE pool, so the budget means the same thing on both tiers.

Retention policy: host blocks are refcounted like device blocks.
Prefix entries are LRU-evictable (a demoted block may be dropped again
when the arena fills — that is the old behavior, now explicit in the
``serving.prefix_evictions{dest}`` split) — EXCEPT while pinned via
:meth:`pin_prefix`: the engine pins a matched run for the window
between ``match_prefix`` and ``pop_prefix``, because securing device
blocks for the swap-in can itself demote NEW victims into this arena,
and making room for those must not eat the entries about to be
promoted.  Lane images are pinned outright until consumed by a
swap-in, invalidated (abort/retire), or cleared —
a preempted request's state is never silently sacrificed to cache
pressure; instead ``save_lane`` evicts prefix entries to make room and
fails cleanly (engine falls back to recompute) when even that is not
enough.

Thread ownership (PTA510 doctrine): the arena is engine-owned state,
mutated only from the thread that drives the engine — the same
ownership rule as ``Engine.pool``/``Engine.prefix``.  It therefore
takes no locks, spawns no threads, and never blocks; cross-thread
readers get the same deal as ``Engine.stats()``: call it from the
owning thread or accept a torn-but-harmless counter read.

Deliberately NOT built here (see ARCHITECTURE "Tiered KV"): cross-host
shipping of arena blocks.  The arena is process-local; the multi-host
fleet's prefix warm-up uses it as the serialization format (ROADMAP),
but the wire protocol, the per-shard local-slice arenas a multi-host
mesh needs, and transfer scheduling are out of scope.
"""

from __future__ import annotations

import numpy as np


class _LaneImage:
    """A preempted lane's full KV block chain: ``hbs`` host blocks
    covering ``n_tokens`` positions (the last block may be partial —
    its trailing bytes are garbage the resume path never reads)."""

    __slots__ = ("hbs", "n_tokens")

    def __init__(self, hbs, n_tokens):
        self.hbs = list(hbs)
        self.n_tokens = int(n_tokens)


class _PrefixEntry:
    """One demoted radix block: ``hb`` holds the KV for the LAST
    ``block_size`` tokens of ``path`` (the full token path from the
    radix root, which is also the dict key it is indexed under).
    ``pinned`` counts in-flight swap-ins shielding it from arena-level
    LRU eviction (see :meth:`HostKVTier.pin_prefix`)."""

    __slots__ = ("hb", "path", "last_used", "pinned")

    def __init__(self, hb, path, clock):
        self.hb = hb
        self.path = path
        self.last_used = clock
        self.pinned = 0


class HostKVTier:
    """The pinned host arena: refcounted block index over preallocated
    numpy payload arrays, with a prefix index (token path -> entry,
    LRU-evictable) and a lane-image index (request id -> pinned chain).

    All payload setters/getters move raw block bytes; nothing here
    knows about tokens' meaning, sampling, or sharding — the engine
    owns which device blocks map to which host blocks and when.
    """

    def __init__(self, num_layers, block_size, kv_heads, head_dim,
                 store_dtype, budget_bytes, bytes_per_block,
                 quantized=False, layout=None):
        self.num_layers = int(num_layers)
        self.block_size = int(block_size)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)
        self.store_dtype = np.dtype(store_dtype)
        self.quantized = bool(quantized)
        self.bytes_per_block = int(bytes_per_block)
        self.budget_bytes = int(budget_bytes)
        self.capacity = max(0, self.budget_bytes // self.bytes_per_block)
        # the model's stated layout (kv_cache.CacheLayout) when given: a
        # latent layer keeps one buffer, and its value arena is zero-width
        # so that every block still moves as a (k, v) pair of arrays
        token_shape = (tuple(layout.token_shape) if layout is not None
                       else (self.kv_heads, self.head_dim))
        shape = (self.capacity, self.num_layers, self.block_size)
        self.k = np.zeros(shape + token_shape, self.store_dtype)
        self.v = np.zeros(
            shape + (token_shape if layout is None or layout.buffers == 2
                     else (0,)), self.store_dtype)
        if self.quantized:
            sshape = (self.capacity, self.num_layers, self.block_size)
            self.k_scale = np.zeros(sshape, np.float32)
            self.v_scale = np.zeros(sshape, np.float32)
        else:
            self.k_scale = self.v_scale = None
        self._refs = np.zeros(self.capacity, np.int32)
        self._free = list(range(self.capacity - 1, -1, -1))
        self._prefix = {}            # token path tuple -> _PrefixEntry
        self._lanes = {}             # request_id -> _LaneImage
        self._clock = 0
        # counters (engine surfaces them through stats()["kv_pool"])
        self.demotions = 0           # prefix blocks accepted from _evict
        self.demotions_dropped = 0   # spills refused (arena full)
        self.promotions = 0          # prefix blocks swapped back in
        self.lane_saves = 0
        self.lane_restores = 0
        self.lane_drops = 0          # images invalidated unconsumed
        self.prefix_evictions = 0    # arena-level LRU drops

    # ------------------------------------------------------ block index
    @property
    def free_blocks(self):
        return len(self._free)

    @property
    def blocks_in_use(self):
        return self.capacity - len(self._free)

    @property
    def bytes_in_use(self):
        return self.blocks_in_use * self.bytes_per_block

    @property
    def occupancy(self):
        return (self.blocks_in_use / self.capacity
                if self.capacity else 0.0)

    def _alloc(self):
        """Claim a free host block (refcount 1), evicting LRU prefix
        entries if the free list is dry; None when even that fails
        (everything left is pinned lane images)."""
        if not self._free and not self._evict_lru_prefix():
            return None
        hb = self._free.pop()
        self._refs[hb] = 1
        return hb

    def release(self, hb):
        """Drop one reference; the block returns to the free list when
        the last holder lets go."""
        if self._refs[hb] <= 0:
            raise ValueError(f"host block {hb} over-released")
        self._refs[hb] -= 1
        if self._refs[hb] == 0:
            self._free.append(hb)

    def _evict_lru_prefix(self):
        """Drop the least-recently-used unpinned prefix entry (lane
        images are pinned outright and entries under a
        :meth:`pin_prefix` hold are skipped — neither is ever a
        victim).  Returns True if one was freed."""
        victim = min((e for e in self._prefix.values() if not e.pinned),
                     key=lambda e: e.last_used, default=None)
        if victim is None:
            return False
        del self._prefix[victim.path]
        self.release(victim.hb)
        self.prefix_evictions += 1
        return True

    def _write_block(self, hb, kd, vd, ksd=None, vsd=None):
        self.k[hb] = kd
        self.v[hb] = vd
        if self.quantized:
            self.k_scale[hb] = ksd
            self.v_scale[hb] = vsd

    def read_block(self, hb):
        """(k, v, k_scale, v_scale) views of one host block — the
        engine stacks these into its batched upload.  Scale planes are
        None on fp arenas."""
        if self.quantized:
            return (self.k[hb], self.v[hb],
                    self.k_scale[hb], self.v_scale[hb])
        return self.k[hb], self.v[hb], None, None

    # ---------------------------------------------------- prefix spills
    def store_prefix(self, path, kd, vd, ksd=None, vsd=None):
        """Accept one demoted radix block: ``path`` is the FULL token
        path from the radix root through this block (the re-match key),
        ``kd``/``vd`` the ``[num_layers, block_size, kv_heads,
        head_dim]`` device_get payloads.  Returns True when stored;
        False (counted ``demotions_dropped``) when the arena cannot
        make room — the old drop-on-evict behavior."""
        path = tuple(path)
        self._clock += 1
        old = self._prefix.get(path)
        if old is not None:
            # re-demotion of a path we already hold: refresh in place
            self._write_block(old.hb, kd, vd, ksd, vsd)
            old.last_used = self._clock
            self.demotions += 1
            return True
        hb = self._alloc()
        if hb is None:
            self.demotions_dropped += 1
            return False
        self._write_block(hb, kd, vd, ksd, vsd)
        self._prefix[path] = _PrefixEntry(hb, path, self._clock)
        self.demotions += 1
        return True

    def match_prefix(self, tokens, start_block):
        """The longest run of consecutive demoted FULL blocks extending
        a device-side radix match: block indices ``start_block,
        start_block+1, ...`` of ``tokens`` whose full token paths are
        all held here.  Pure lookup — but NOT a reservation: a new
        spill landing before :meth:`pop_prefix` can LRU-evict a matched
        entry; callers that do work between match and pop (the engine
        allocates device blocks, whose reclaim path spills) must
        :meth:`pin_prefix` the result for that window.  A block
        covering tokens up
        to exactly ``len(tokens)`` is still promotable: the radix
        store's one-token-to-prefill invariant lives in its MATCH caps
        (``acquire``/``lookup`` stop at ``len - 1``, partially serving
        the last node copy-on-write), not in which nodes exist."""
        bs = self.block_size
        out = []
        i = int(start_block)
        while (i + 1) * bs <= len(tokens):
            path = tuple(tokens[:(i + 1) * bs])
            if path not in self._prefix:
                break
            out.append(path)
            i += 1
        return out

    def pin_prefix(self, paths):
        """Shield matched entries from arena-level LRU eviction for the
        match->pop window of a swap-in: while the engine secures device
        blocks, its reclaim fallback can demote NEW radix victims into
        this arena, and ``store_prefix`` making room for them must not
        eat the entries about to be promoted.  Pins nest (a counter per
        entry); paths already gone are ignored — ``pop_prefix`` reports
        the miss.  Pair every call with :meth:`unpin_prefix`."""
        for p in paths:
            entry = self._prefix.get(tuple(p))
            if entry is not None:
                entry.pinned += 1

    def unpin_prefix(self, paths):
        """Release a :meth:`pin_prefix` hold.  Safe on paths since
        consumed by ``pop_prefix`` (the pop already removed them)."""
        for p in paths:
            entry = self._prefix.get(tuple(p))
            if entry is not None and entry.pinned > 0:
                entry.pinned -= 1

    def pop_prefix(self, path):
        """Consume one matched entry for promotion: removes it from the
        index and returns its host block id — or None when the entry is
        gone, so an unpinned caller degrades to recompute instead of
        crashing (arena-level LRU eviction CAN invalidate
        ``match_prefix`` results; see its docstring).  The caller reads
        the payload (``read_block``), uploads it, then ``release``s the
        block."""
        entry = self._prefix.pop(tuple(path), None)
        if entry is None:
            return None
        self._clock += 1
        self.promotions += 1
        return entry.hb

    # ------------------------------------------------------ lane images
    def save_lane(self, request_id, n_tokens, blocks):
        """Store a preempted lane's full chain: ``blocks`` is a list of
        ``(kd, vd, ksd, vsd)`` per-block payloads in chain order,
        covering ``n_tokens`` positions.  All-or-nothing: if the arena
        cannot hold the whole chain even after evicting every prefix
        entry, nothing is kept and False is returned (the engine falls
        back to recompute-on-resume).  A previous unconsumed image for
        the same request is replaced."""
        self.drop_lane(request_id)
        hbs = []
        for kd, vd, ksd, vsd in blocks:
            hb = self._alloc()
            if hb is None:
                for h in hbs:
                    self.release(h)
                return False
            self._write_block(hb, kd, vd, ksd, vsd)
            hbs.append(hb)
        self._lanes[request_id] = _LaneImage(hbs, n_tokens)
        self.lane_saves += 1
        return True

    def peek_lane(self, request_id):
        """The saved image for a request, or None (non-consuming)."""
        return self._lanes.get(request_id)

    def take_lane(self, request_id):
        """Consume a lane image for swap-in: removes it from the index
        and returns it.  The caller uploads the blocks it needs and
        ``release``s every host block of the image (used or not)."""
        img = self._lanes.pop(request_id, None)
        if img is not None:
            self.lane_restores += 1
        return img

    def drop_lane(self, request_id):
        """Invalidate an unconsumed image (abort/retire/re-save): its
        blocks return to the free list.  Idempotent."""
        img = self._lanes.pop(request_id, None)
        if img is None:
            return False
        for hb in img.hbs:
            self.release(hb)
        self.lane_drops += 1
        return True

    # ------------------------------------------------------------ admin
    def clear_prefixes(self):
        """Drop every demoted prefix entry (drain: cache content is
        disposable; anything still held afterwards is a leaked lane
        image).  Returns how many entries were dropped."""
        n = len(self._prefix)
        for entry in list(self._prefix.values()):
            del self._prefix[entry.path]
            self.release(entry.hb)
        return n

    def clear(self):
        """Drop everything — prefix entries AND lane images."""
        self.clear_prefixes()
        for rid in list(self._lanes):
            self.drop_lane(rid)

    # ------------------------------------------------------------ stats
    def stats(self):
        return {
            "capacity_blocks": self.capacity,
            "free_blocks": self.free_blocks,
            "blocks_in_use": self.blocks_in_use,
            "bytes_in_use": self.bytes_in_use,
            "budget_bytes": self.budget_bytes,
            "bytes_per_block": self.bytes_per_block,
            "occupancy": self.occupancy,
            "prefix_entries": len(self._prefix),
            "lane_images": len(self._lanes),
            "demotions": self.demotions,
            "demotions_dropped": self.demotions_dropped,
            "promotions": self.promotions,
            "lane_saves": self.lane_saves,
            "lane_restores": self.lane_restores,
            "lane_drops": self.lane_drops,
            "prefix_evictions": self.prefix_evictions,
            "store_dtype": str(self.store_dtype),
            "quantized": self.quantized,
        }
