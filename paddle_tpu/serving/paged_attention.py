"""Ragged paged-attention over the unified KV block pool.

A cache of one full-length row a lane makes decode attention read the
whole ``max_seq`` row of every lane under a position mask: short
sequences pay bandwidth for the whole row, and a batch of 8 reads 8
full rows a step (its cost on the chip: not measured; ``PERF.md`` has
this kernel's numbers).  Paged attention instead walks each lane's block
table and reads ONLY the table-mapped blocks, so per-step KV traffic is
proportional to the live sequence length.

Two implementations behind one entry point:

* :func:`paged_attention` — the router.  Kernel or scan is a static
  rule on the backend alone: on TPU every query window — single-token
  decode, speculative K+1 verify windows, whole-prompt and
  chunked-prefill windows of any length — goes to the Pallas kernel; off
  TPU every window goes to the XLA scan (the parity reference).  No
  shape is routed away from the kernel and no exception selects a path
  (the kernel picks its own geometry from the shapes, below).  The
  kernel and the scan compile as separate programs, so raw outputs
  agree to a tolerance (below).  Override with
  ``PADDLE_TPU_PAGED_ATTN=xla|pallas``.
* **XLA fallback** — a blockwise online-softmax ``lax.scan`` over the
  table entries (flash-attention recurrence: running max ``m``, running
  normalizer ``l``, unnormalized accumulator ``acc``).  The scan is the
  engine's PARITY REFERENCE: a block with no visible keys contributes
  exactly nothing — its masked scores sit at the finite ``NEG_INF``
  floor so ``m`` is unchanged (``max(m, NEG_INF) == m``), its
  probabilities are forced to literal 0.0, and ``l``/``acc`` pass
  through bitwise (``x * 1.0 + 0.0 == x``).  Outputs are therefore
  invariant to the STATIC number of table columns ``nb``, which is what
  keeps batched/horizoned paged decode bitwise-equal to sequential
  generation even though the engine re-buckets ``nb`` as sequences grow.
* **Pallas TPU kernel** — one algorithm (online softmax over table
  columns, block table and lane lengths as scalar prefetch, f32 running
  max, sum and accumulator, in-kernel causal mask ``key_idx <= pos[b] +
  row``) in two geometries, chosen from static shapes alone:

  - **streaming** (:func:`_paged_stream_kernel`) for a window on an
    unquantized pool whose working set fits the VMEM budget
    (:func:`_stream_blocks`: at the serving widths QH=32, KH=8, D=128,
    blocks of 16, windows to s = 9 — decode and the verify windows to
    K = 8; at the MHA presets, KH = 32 or 40, decode and s = 2).  Grid
    ``(batch,)``: a cell walks its lane's LIVE table columns only, a
    chunk of pool blocks at a time, copied whole from pools left in HBM
    into two VMEM buffers, one filling while the other is scored.  One
    softmax update a chunk, all kv heads in one matmul.  The chunk is
    read from the POOL's shape alone (8 blocks at KH=8, 4 at KH=32),
    never from the window, so every streaming window of an engine runs
    one recurrence.
  - **tile** (:func:`_paged_attn_kernel`) for every other window:
    those whose rows against a chunk's columns do not fit (prefill
    buckets, cut into row tiles sized against the same budget by
    :func:`_query_tile`), and int8 pools at any window (their scale rows
    do not ride the streaming copies yet: ROADMAP queue 1 item 2).  Grid
    ``(batch, query tiles, nb)``: the table drives the k/v BlockSpec
    index maps, a cell DMAs one pool block, ``pl.when`` skips cells
    whose block starts past the tile's visible window, one 2-D matmul
    per kv head.  The rule is what fits, not where the two geometries
    cross: where both were timed the stream was the faster (PERF.md
    section 5).

  What is bitwise and what is to a tolerance.  Either geometry against
  itself is bitwise (same shapes, other tables or aliased blocks; and
  through its module-level jit against the unwrapped call), and the
  streaming output does not depend on ``nb`` (the chunking is read from
  ``pos``, never from the table's width).  The tile geometry runs the
  scan's recurrence term for term.  The streaming geometry is the same
  mathematics reordered — one update a chunk of blocks, the scale
  applied to f32 scores of pool-dtype products (a bf16 x bf16 product
  is exact in f32) — so against the scan, and against the tile
  geometry, it agrees to the output's last ulps (asserted at 1e-5 in
  f32 and two bf16 ulps at the serving widths).

  Whole-engine stream equalities under kernel routing.  Those whose two
  sides send every window through one geometry rest on the same
  recurrence and stay bitwise wherever the surrounding XLA programs
  are (the CPU tests assert them in interpret mode): batched against
  sequential decode, spec K > 0 against K = 0 while the K+1 window
  streams as decode does, chunked against whole-prompt prefill when
  both tile or both stream.  Those that replay through the OTHER
  geometry KV that was first written through one — preemption/resume
  (decode wrote it streaming, the re-prefill tiles), a streamed prefill
  chunk against a tiled whole prompt — see attention outputs that
  differ in their last ulps, so deeper layers' KV and the logits do
  too: greedy streams agree except at a near-tie
  (``test_engine_mixed_geometries`` holds a toy engine to that), seeded
  sampling likewise.  On the chip no stream equality across batch
  compositions has held since the kernel arrived (PERF.md, PR 21): the
  benchmark's ``correct`` compares logits against a reference.

  The kernel is entered through ONE module-level ``jax.jit``
  (:func:`_pallas_paged_attention`), so a program of L layers traces and
  lowers it once, not L times, and programs whose kernel shapes agree
  (the horizons of one table width) share the trace.  The counter
  ``paged_attn.trace{path=stream|tile, blocks_per_cell=C}`` counts a
  trace.

Layout contract (matches ``kv_cache.PagedKV``): q ``[B, s, QH, D]``,
pools ``[NB, bs, KH, D]`` with GQA group size ``G = QH // KH`` (query
head ``h`` reads kv head ``h // G``), tables ``[B, nb]`` int32 (0 =
scratch), pos ``[B]`` int32.  Returns ``[B, s, QH, D]`` in q's dtype.
"""

from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import metrics as _obs_metrics
from ..ops.pallas import VMEM_BUDGET_BYTES, rows_under_budget

NEG_INF = -1e30    # finite floor: keeps exp(s - m) NaN-free when a
#                    query row has no visible key in a block

def paged_attention(q, k_pool, v_pool, tables, pos,
                    k_scale=None, v_scale=None):
    """Route to the Pallas ragged kernel or the XLA online-softmax scan.

    Kernel or scan is a static rule on the backend, never the shapes:
    ``jax.default_backend() == "tpu"`` sends every window s >= 1 to the
    kernel (which picks its geometry from the shapes, module
    docstring), anything else sends every window to the scan (CPU
    tier-1, which is also the parity reference for every s).
    ``PADDLE_TPU_PAGED_ATTN=xla|pallas`` pins one of them; ``pallas``
    off TPU runs the kernel in interpret mode.

    ``k_scale``/``v_scale`` ([NB, bs] f32, or None) mark a quantized
    pool: both implementations dequantize each gathered block token-wise
    (``block.astype(f32) * scale``) before the softmax math, so the
    int8 path reuses the exact fp recurrence — and inherits its
    nb-invariance — just over dequantized values."""
    impl = os.environ.get("PADDLE_TPU_PAGED_ATTN", "auto")
    use_pallas = impl == "pallas" or (
        impl == "auto" and jax.default_backend() == "tpu")
    if use_pallas:
        # forcing `pallas` off-TPU runs the kernel in interpret mode —
        # how CPU tests drive the kernel through whole-engine (and
        # shard_map per-shard) paths and assert bitwise parity with the
        # fallback
        return _pallas_paged_attention(
            q, k_pool, v_pool, tables, pos, k_scale, v_scale,
            interpret=jax.default_backend() != "tpu")
    return _xla_paged_attention(q, k_pool, v_pool, tables, pos,
                                k_scale, v_scale)


# ------------------------------------------------------------------ XLA

def _xla_paged_attention(q, k_pool, v_pool, tables, pos,
                         k_scale=None, v_scale=None):
    """Blockwise online-softmax over the block table, one ``lax.scan``
    step per table column.  Fixed shapes per step ([B, bs] gathers), so
    the whole thing traces into the engine's horizon scan; see the
    module docstring for the nb-invariance argument (dequantizing a
    gathered block is an elementwise pre-multiply on values the masked
    positions never contribute, so the argument survives int8 pools
    unchanged)."""
    b, s, qh, d = q.shape
    bs, kh = k_pool.shape[1], k_pool.shape[2]
    g = qh // kh
    nb = tables.shape[1]
    scale = 1.0 / math.sqrt(d)

    qg = (q.astype(jnp.float32) * scale).reshape(b, s, kh, g, d)
    q_pos = pos[:, None] + jnp.arange(s, dtype=pos.dtype)        # [B, s]

    def block_step(carry, i):
        m, l, acc = carry
        blocks = jnp.take(tables, i, axis=1)                     # [B]
        kb = k_pool[blocks].astype(jnp.float32)                  # [B,bs,KH,D]
        vb = v_pool[blocks].astype(jnp.float32)
        if k_scale is not None:
            kb = kb * k_scale[blocks][:, :, None, None]
            vb = vb * v_scale[blocks][:, :, None, None]
        sc = jnp.einsum("bskgd,btkd->bskgt", qg, kb)
        key_idx = i * bs + jnp.arange(bs, dtype=pos.dtype)       # [bs]
        vis = key_idx[None, None, :] <= q_pos[:, :, None]        # [B,s,bs]
        vis = vis[:, :, None, None, :]                           # [B,s,1,1,bs]
        sc = jnp.where(vis, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        # exact-zero masked probabilities (not exp(NEG_INF - m)): padded
        # blocks and padded key columns contribute literal +0.0, which
        # is what makes the output bitwise-invariant to nb
        p = jnp.where(vis, jnp.exp(sc - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + \
            jnp.einsum("bskgt,btkd->bskgd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, s, kh, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s, kh, g), jnp.float32)
    acc0 = jnp.zeros((b, s, kh, g, d), jnp.float32)
    (_, l, acc), _ = jax.lax.scan(block_step, (m0, l0, acc0),
                                  jnp.arange(nb))
    # every query row sees at least key 0 (key_idx 0 <= q_pos), so l > 0
    out = acc / l[..., None]
    return out.reshape(b, s, qh, d).astype(q.dtype)


# --------------------------------------------------------------- Pallas

_TRACES = _obs_metrics.counter(
    "paged_attn.trace",
    "traces of the Pallas paged kernel, by path (stream|tile) and pool "
    "blocks a grid cell takes at a time")


def _row_bytes(qh, d, dtype):
    """VMEM a window row holds in either geometry: for each query head
    the double-buffered q and output blocks, the f32 accumulator, and
    the running max and sum (one lane-padded f32 vreg row each)."""
    return qh * (d * (4 * jnp.dtype(dtype).itemsize + 4) + 2 * 512)


def _query_tile(s, qh, d, dtype):
    """Query rows per grid cell of the tile geometry: all s when they
    fit the VMEM budget, else the largest power of two that does."""
    return min(rows_under_budget(_row_bytes(qh, d, dtype)), s)


def _stream_blocks(s, qh, bs, kh, d, q_dtype, pool_dtype):
    """Pool blocks a streaming grid cell takes at a time, or 0 where the
    window does not stream.

    The chunk is read from the pool's shape alone, never from the
    window, so decode, verify windows and short prefill chunks of one
    engine run the same recurrence: the largest power of two, at most 8
    (128 keys of a block size of 16: the chip timed 4, 8 and 16 alike),
    whose K and V double buffers and V's f32 copy take at most half the
    VMEM budget.  The window streams when its whole working set at that
    chunk fits the budget: the buffers, the rows' own state, and five
    4-byte values (score, probability, mask, and the hoisted key index
    and deepest-key arrays) for each of its ``s * qh`` rows against the
    chunk's ``bs * kh`` columns a block — every query head is scored
    against every kv head's columns, so this grows with ``kh`` squared
    and an MHA pool streams only its shortest windows."""
    block = bs * kh * d * (4 * jnp.dtype(pool_dtype).itemsize + 4)
    c = 8
    while c > 1 and c * block > VMEM_BUDGET_BYTES // 2:
        c //= 2
    working_set = c * block + s * _row_bytes(qh, d, q_dtype) \
        + 5 * 4 * s * qh * c * bs * kh
    return c if working_set <= VMEM_BUDGET_BYTES else 0


def _online_softmax_step(sc, vis, v, m, l, acc):
    """The recurrence both geometries run on one group of keys: scores
    ``sc`` [rows, keys] under the visibility mask, values ``v``
    [keys, D], running max ``m``, sum ``l`` and accumulator ``acc``.
    Same masking (exact-zero probabilities) and update order as the XLA
    fallback's ``block_step``."""
    sc = jnp.where(vis, sc, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
    p = jnp.where(vis, jnp.exp(sc - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return m_new, l_new, acc * alpha + pv


def _paged_attn_kernel(tables, pos, q_ref, k_ref, v_ref, *refs,
                       block_size, groups, nb, q_tile, scale, quantized):
    """Tile geometry.  One grid cell = (lane b, query tile t, table
    column i): accumulate pool block ``tables[b, i]`` into the
    online-softmax state of the tile's q_tile query rows.  The k/v
    BlockSpec index maps already selected the pool block from the
    scalar-prefetched table, so refs hold exactly one block.  q arrives
    as [KH, q_tile*G, D] (row ``r*G + g`` is window row r of the kv
    head's g-th query head), so each kv head is one 2-D matmul against
    its [bs, D] keys.  Window row r sits at absolute position
    ``pos[b] + r``, and the causal mask ``key_idx <= pos[b] + r`` is
    evaluated in-kernel per row — the same visibility rule, masking
    (exact-zero probabilities), and update order the XLA fallback
    applies, so the recurrences are term-for-term identical.  On a
    quantized pool two extra scale refs ride between the pool refs and
    the output (the 8 scale rows around the block's own, see the
    wrapper): the block is dequantized token-wise right after its DMA,
    before any softmax math."""
    if quantized:
        ksc_ref, vsc_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    b, t, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p_b = pos[b]
    row0 = t * q_tile

    # skip blocks that start past the tile's deepest visible key (its
    # last row sees up to pos + row0 + q_tile - 1): a retired/short
    # lane's tail blocks are never scored at all
    @pl.when(i * block_size <= p_b + row0 + (q_tile - 1))
    def _accumulate():
        shape = (q_tile * groups, block_size)
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // groups
        key_idx = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1)
        vis = key_idx <= p_b + row
        if quantized:
            # this block's scale row, turned from lanes to sublanes
            # ([1, bs] -> [bs, 1]) by an exact masked lane-sum: every
            # term but one is +0.0
            r = tables[b, i] % ksc_ref.shape[0]
            eye = (jax.lax.broadcasted_iota(jnp.int32, (block_size,) * 2, 0)
                   == jax.lax.broadcasted_iota(jnp.int32,
                                               (block_size,) * 2, 1))

            def column(sc_ref):
                return jnp.sum(jnp.where(eye, sc_ref[pl.ds(r, 1), :], 0.0),
                               axis=1, keepdims=True)

            k_col, v_col = column(ksc_ref), column(vsc_ref)
        for h in range(k_ref.shape[2]):
            q = q_ref[0, h].astype(jnp.float32) * scale      # [ts*G, D]
            k = k_ref[0, :, h, :].astype(jnp.float32)        # [bs, D]
            v = v_ref[0, :, h, :].astype(jnp.float32)
            if quantized:
                k = k * k_col
                v = v * v_col
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)          # [ts*G, bs]
            m_ref[h], l_ref[h], acc_ref[h] = _online_softmax_step(
                sc, vis, v, m_ref[h], l_ref[h], acc_ref[h])

    @pl.when(i == nb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_stream_kernel(tables, pos, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                         v_buf, sems, *, block_size, groups, nb, window,
                         scale):
    """Streaming geometry.  One grid cell = one lane: walk its LIVE
    table columns, ``cdiv(pos[b] + window, bs)`` of them, ``chunk`` at a
    time (the leading extent of the buffers).  The pools stay in HBM;
    each block, one contiguous run, is copied whole into one of two
    buffers while the other is scored, and the loop's bound is read from
    ``pos``, so a dead column costs nothing.  Columns past the live ones
    in a lane's last chunk fetch whatever their table entry names (the
    scratch block) and are masked.

    All kv heads in one matmul: q arrives as [KH*window*G, D] (row
    ``(h*window + r)*G + g``), a chunk's keys as [chunk*bs*KH, D] (a
    free view of the pool's layout, column ``t*KH + h'``), and a score
    is visible where the column's head is the row's own and
    ``key_idx <= pos[b] + r``.  The other heads' columns get the
    exact-zero probability of every masked key, so a row's sums hold the
    terms the tile geometry's hold; one softmax update a chunk.  K and q
    enter the MXU in the pool's dtype when they share it, accumulated in
    f32 with the scale applied to the f32 scores; probabilities and V
    are f32 into P.V."""
    b = pl.program_id(0)
    chunk, kh, d = k_buf.shape[1], k_buf.shape[3], k_buf.shape[4]
    keys = chunk * block_size
    rows, cols = q_ref.shape[1], keys * kh
    live = jnp.minimum(pl.cdiv(pos[b] + window, block_size), nb)
    n_chunks = pl.cdiv(live, chunk)

    def copies(i, slot):
        for j in range(chunk):
            block = tables[b, jnp.minimum(i * chunk + j, nb - 1)]
            yield pltpu.make_async_copy(
                k_hbm.at[block], k_buf.at[slot, j], sems.at[0, slot])
            yield pltpu.make_async_copy(
                v_hbm.at[block], v_buf.at[slot, j], sems.at[1, slot])

    for c in copies(0, 0):
        c.start()

    # a column holds key ``n // KH`` of its chunk for kv head ``n % KH``;
    # `deepest` is the last key a row sees, -1 in another head's columns
    r_i = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    n_i = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    per_head = rows // kh
    key = n_i // kh
    deepest = jnp.where(
        n_i % kh == r_i // per_head,
        jnp.minimum(pos[b] + (r_i % per_head) // groups,
                    nb * block_size - 1), -1)

    q = q_ref[0]
    if q.dtype != k_buf.dtype:
        q = q.astype(jnp.float32)

    def chunk_step(i, state):
        slot = i % 2

        @pl.when(i + 1 < n_chunks)
        def _prefetch():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        k = k_buf[slot].reshape(cols, d).astype(q.dtype)
        v = v_buf[slot].reshape(cols, d).astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # [rows, cols]
        return _online_softmax_step(sc, key + i * keys <= deepest, v,
                                    *state)

    _, l, acc = jax.lax.fori_loop(0, n_chunks, chunk_step, (
        jnp.full((rows, 1), NEG_INF, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32),
        jnp.zeros((rows, d), jnp.float32)))
    # every row sees key 0 of its own head in chunk 0, so l > 0
    o_ref[0] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_paged_attention(q, k_pool, v_pool, tables, pos,
                            k_scale=None, v_scale=None, *,
                            interpret=False):
    """The Pallas kernel for any static query window s >= 1, in one of
    two geometries chosen from static shapes alone (module docstring).
    Block table and lane lengths are scalar-prefetched either way; q is
    regrouped by kv head outside the kernel ([B, KH, s*G, D]).

    Jitted at module level, so a program that calls it once a layer
    traces and lowers the kernel once (the layers present identical
    avals) and programs whose kernel shapes agree share that trace:
    ``paged_attn.trace`` counts one a trace.  ``interpret=True`` runs the
    kernel in Pallas interpret mode (the CPU test path)."""
    b, s, qh, d = q.shape
    bs, kh = k_pool.shape[1], k_pool.shape[2]
    g = qh // kh
    nb = tables.shape[1]
    quantized = k_scale is not None
    chunk = 0 if quantized else _stream_blocks(s, qh, bs, kh, d, q.dtype,
                                               k_pool.dtype)
    stream = chunk > 0

    ts = s if stream else _query_tile(s, qh, d, q.dtype)
    sp = s + (-s) % ts
    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    q = q.reshape(b, sp, kh, g, d).transpose(0, 2, 1, 3, 4)
    q = q.reshape(b, kh, sp * g, d)
    scale = 1.0 / math.sqrt(d)

    if stream:
        _TRACES.inc(path="stream", blocks_per_cell=chunk)
        q_spec = pl.BlockSpec((1, qh * s, d),
                              lambda bb, tables, pos: (bb, 0, 0))
        in_hbm = pl.BlockSpec(memory_space=pl.ANY)
        out = pl.pallas_call(
            functools.partial(
                _paged_stream_kernel, block_size=bs, groups=g, nb=nb,
                window=s, scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,             # tables, pos
                grid=(b,),
                in_specs=[q_spec, in_hbm, in_hbm],
                out_specs=q_spec,
                scratch_shapes=[
                    pltpu.VMEM((2, chunk, bs, kh, d), k_pool.dtype),
                    pltpu.VMEM((2, chunk, bs, kh, d), v_pool.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),     # (k|v, buffer)
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, qh * s, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(tables, pos, q.reshape(b, qh * s, d), k_pool, v_pool)
    else:
        _TRACES.inc(path="tile", blocks_per_cell=1)
        q_spec = pl.BlockSpec((1, kh, ts * g, d),
                              lambda bb, t, i, tables, pos: (bb, 0, t, 0))
        kv_spec = pl.BlockSpec(
            (1, bs, kh, d),
            lambda bb, t, i, tables, pos: (tables[bb, i], 0, 0, 0))
        in_specs = [q_spec, kv_spec, kv_spec]
        operands = [tables, pos, q, k_pool, v_pool]
        if quantized:
            rows = min(8, k_scale.shape[0])
            sc_spec = pl.BlockSpec(
                (rows, bs),
                lambda bb, t, i, tables, pos: (tables[bb, i] // rows, 0))
            in_specs += [sc_spec, sc_spec]
            operands += [k_scale, v_scale]
        out = pl.pallas_call(
            functools.partial(
                _paged_attn_kernel, block_size=bs, groups=g, nb=nb,
                q_tile=ts, scale=scale, quantized=quantized),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,             # tables, pos
                grid=(b, sp // ts, nb),
                in_specs=in_specs,
                out_specs=q_spec,
                scratch_shapes=[
                    pltpu.VMEM((kh, ts * g, 1), jnp.float32),  # running max
                    pltpu.VMEM((kh, ts * g, 1), jnp.float32),  # running sum
                    pltpu.VMEM((kh, ts * g, d), jnp.float32),  # accumulator
                ],
            ),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",) * 3),
            interpret=interpret,
        )(*operands)
    out = out.reshape(b, kh, sp, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, sp, qh, d)[:, :s]
