"""Ragged paged-attention over the unified KV block pool.

Decode attention against a slotted cache reads the full ``max_seq``
row of every lane under a position mask — short sequences pay bandwidth
for the whole row (DECODE_BENCH.json: fused decode stuck at 41-47% of
the weight roofline at b1 and 25.5% at b8, where the masked reads are 8
full rows per step).  Paged attention instead walks each lane's block
table and reads ONLY the table-mapped blocks, so per-step KV traffic is
proportional to the live sequence length.

Two implementations behind one entry point:

* :func:`paged_attention` — the router.  The rule is static and reads
  only the backend: on TPU every query window — single-token decode,
  speculative K+1 verify windows, whole-prompt and chunked-prefill
  windows of any length — goes to the Pallas kernel; off TPU every
  window goes to the XLA scan (the parity reference).  No shape is
  routed away from the kernel and no exception selects a path.  The kernel executes
  the fallback's exact per-block recurrence; since the two compile as
  separate programs, raw outputs agree to reassociation-level ulps
  (exact at most shapes), and the serving gate is BITWISE stream
  equality of whole-engine runs under kernel routing, which CPU tests
  assert in interpret mode.  Override with
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
* **Pallas TPU kernel** — grid ``(batch, query tiles, nb)`` with the
  flattened block table and per-lane lengths as scalar prefetch (the
  table drives the k/v BlockSpec index maps, so each grid cell DMAs
  exactly one pool block); ``pl.when`` skips cells whose block starts
  past the tile's visible window, so a short sequence's tail blocks
  cost no compute.  The query window is a static dimension s >= 1, cut
  into row tiles sized against a VMEM budget (:func:`_query_tile`):
  each grid cell scores one tile's rows against its block under an
  in-kernel causal mask (``key_idx <= pos[b] + row``), one 2-D matmul
  per kv head, so spec verify windows and prefill buckets of any
  length run the same kernel as s == 1 decode.  f32 accumulation in
  VMEM scratch, finalized on the last block column.

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

from ..ops.pallas import rows_under_budget

NEG_INF = -1e30    # finite floor: keeps exp(s - m) NaN-free when a
#                    query row has no visible key in a block

def paged_attention(q, k_pool, v_pool, tables, pos,
                    k_scale=None, v_scale=None):
    """Route to the Pallas ragged kernel or the XLA online-softmax scan.

    The rule is static and reads only the backend, never the shapes:
    ``jax.default_backend() == "tpu"`` sends every window s >= 1 to the
    kernel, anything else sends every window to the scan (CPU tier-1,
    which is also the parity reference for every s).
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

def _query_tile(s, qh, d, dtype):
    """Query rows per grid cell: all s when they fit the VMEM budget,
    else the largest power of two that does.  Per row and query head the
    kernel holds the double-buffered q and output blocks, the f32
    accumulator, and the running max and sum (one lane-padded f32 vreg
    row each)."""
    per_row = qh * (d * (4 * jnp.dtype(dtype).itemsize + 4) + 2 * 512)
    return min(rows_under_budget(per_row), s)


def _paged_attn_kernel(tables, pos, q_ref, k_ref, v_ref, *refs,
                       block_size, groups, nb, q_tile, scale, quantized):
    """One grid cell = (lane b, query tile t, table column i):
    accumulate pool block ``tables[b, i]`` into the online-softmax state
    of the tile's q_tile query rows.  The k/v BlockSpec index maps
    already selected the pool block from the scalar-prefetched table, so
    refs hold exactly one block.  q arrives as [KH, q_tile*G, D] (row
    ``r*G + g`` is window row r of the kv head's g-th query head), so
    each kv head is one 2-D matmul against its [bs, D] keys.  Window row
    r sits at absolute position ``pos[b] + r``, and the causal mask
    ``key_idx <= pos[b] + r`` is evaluated in-kernel per row — the same
    visibility rule, masking (exact-zero probabilities), and update
    order the XLA fallback applies, so the recurrences are term-for-
    term identical.  On a quantized pool two extra scale refs ride
    between the pool refs and the output (the 8 scale rows around the
    block's own, see the wrapper): the block is dequantized token-wise
    right after its DMA, before any softmax math."""
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
            sc = jnp.where(vis, sc, NEG_INF)
            m_prev = m_ref[h]                                # [ts*G, 1]
            m_new = jnp.maximum(m_prev,
                                jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.where(vis, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            pv = jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [ts*G, D]
            acc_ref[h] = acc_ref[h] * alpha + pv
            m_ref[h] = m_new

    @pl.when(i == nb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _pallas_paged_attention(q, k_pool, v_pool, tables, pos,
                            k_scale=None, v_scale=None, *,
                            interpret=False):
    """Ragged kernel for any static query window s >= 1: grid
    (B, query tiles, nb), block table + lane lengths scalar-prefetched
    so the k/v index maps gather pool blocks directly and ``pl.when``
    culls dead columns.  The window is cut into tiles of
    :func:`_query_tile` rows (padded up to a whole number of tiles; the
    padding rows are computed and dropped), so VMEM holds one tile's
    state whatever s is — decode (s=1), spec verify (s=K+1) and prefill
    buckets up to max_seq_len share the program structure.  q is
    regrouped by kv head outside the kernel ([B, KH, s*G, D]) so the
    kernel body is 2-D matmuls.  Quantized pools add the two [NB, bs]
    scale arrays as they are, read through the same table entry as
    their blocks: an (8, bs) block of scale rows is the smallest the
    chip's tiling admits, and the kernel picks its row.
    ``interpret=True`` runs the kernel in Pallas interpret mode (the
    CPU test path)."""
    b, s, qh, d = q.shape
    bs, kh = k_pool.shape[1], k_pool.shape[2]
    g = qh // kh
    nb = tables.shape[1]
    quantized = k_scale is not None

    ts = _query_tile(s, qh, d, q.dtype)
    sp = s + (-s) % ts
    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    q = q.reshape(b, sp, kh, g, d).transpose(0, 2, 1, 3, 4)
    q = q.reshape(b, kh, sp * g, d)

    kernel = functools.partial(
        _paged_attn_kernel, block_size=bs, groups=g, nb=nb, q_tile=ts,
        scale=1.0 / math.sqrt(d), quantized=quantized)
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
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # tables, pos
        grid=(b, sp // ts, nb),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((kh, ts * g, 1), jnp.float32),    # running max m
            pltpu.VMEM((kh, ts * g, 1), jnp.float32),    # running sum l
            pltpu.VMEM((kh, ts * g, d), jnp.float32),    # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
    out = out.reshape(b, kh, sp, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, sp, qh, d)[:, :s]
