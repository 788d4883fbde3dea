"""Ragged paged attention over a LATENT pool (multi-head latent attention,
DeepSeek-V2's MLA, in its absorbed form).

A latent layer caches one vector a token, ``[c ‖ k_r]``: the normed
compressed latent (``v_width`` values, 512) followed by the rotated key all
heads share (64).  With the key up-projection absorbed into the query and
the value up-projection applied after the attention, every query head
attends over that ONE cached head:

    score[h, t] = scale * q[h] . pool[t]            all W = 576 columns
    out[h]      = sum_t softmax(score[h])[t] * pool[t, :v_width]

so the values are the first ``v_width`` columns of the same rows the keys
are — they are read out of the key block, never stored again (storing
them twice would undo what MLA saves).  No existing kernel computes this:
the k/v kernel (``paged_attention.py``) takes two pools of equal width.

Layout contract: q ``[B, s, H, W]`` (already absorbed and rotated), pool
``[NB, bs, 1, Wp]`` (``kv_cache.CacheLayout`` of a latent layer; block 0 is
scratch) with ``Wp = lane_padded(W)``, the next multiple of 128 (640 for
576): the chip's tiling stores a 576-wide row in 640 lanes whatever the
array says, and Mosaic refuses to copy a 576-wide slice out of it (sandbox
compile for a described v5e, PR 27), so the pad is part of the stated
layout, is written as zeros and is scored against a zero-padded query.
tables ``[B, nb]`` int32, pos ``[B]`` int32 (keys already cached;
window row r sits at position ``pos + r`` and sees keys ``<= pos + r``,
the window's own tokens having been written first).  Returns
``[B, s, H, v_width]`` in q's dtype.

Two implementations behind :func:`mla_paged_attention`, routed like
``paged_attention`` (the kernel on TPU, the gather off it,
``PADDLE_TPU_PAGED_ATTN=xla|pallas`` pins one):

* the XLA fallback gathers a lane's table-mapped blocks and runs a plain
  masked softmax in f32 — the parity reference;
* the Pallas kernel walks each lane's LIVE table columns only, ``chunk``
  pool blocks at a time, copied whole out of the pool left in HBM into two
  VMEM buffers (one fills while the other is scored), one online-softmax
  update a chunk.  One kernel body in two geometries chosen from static
  shapes alone (:func:`_geometry`): **stream**, grid ``(B, 1)``, the whole
  window in one cell (decode and verify windows: 16 rows a window row);
  **tile**, grid ``(B, s / q_tile)``, a prefill window cut into row tiles
  of at most 512 rows, each walking the blocks its last row can see.  A
  masked key gets the exact probability 0.  Probabilities enter P.V in the
  pool's dtype (f32 pools: exact; bf16 pools: the usual flash-attention
  rounding), accumulated in f32.

The counter ``mla_paged_attn.trace{path, blocks_per_cell}`` counts a trace
of the kernel, as ``paged_attn.trace`` does; like that kernel this one is
entered through one module-level ``jax.jit``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability import metrics as _obs_metrics
from ..ops.pallas import VMEM_BUDGET_BYTES

NEG_INF = -1e30    # finite floor, as in paged_attention.py

_TRACES = _obs_metrics.counter(
    "mla_paged_attn.trace",
    "traces of the Pallas latent paged kernel, by path (stream|tile) and "
    "pool blocks a grid cell takes at a time")

#: most rows (window rows x heads) one grid cell scores at a time
MAX_CELL_ROWS = 512
#: pool blocks a cell copies and scores at a time (128 keys at blocks of 16)
CHUNK_BLOCKS = 8


def lane_padded(width):
    """A latent row's stored width: the next multiple of the chip's 128
    lanes (module docstring)."""
    return -(-int(width) // 128) * 128


def mla_paged_attention(q, pool, tables, pos, *, scale, v_width):
    """Route to the Pallas kernel (TPU) or the gather (elsewhere)."""
    impl = os.environ.get("PADDLE_TPU_PAGED_ATTN", "auto")
    if impl == "pallas" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        return _pallas_mla_paged_attention(
            q, pool, tables, pos, scale=float(scale), v_width=int(v_width),
            interpret=jax.default_backend() != "tpu")
    return _xla_mla_paged_attention(q, pool, tables, pos, scale=scale,
                                    v_width=v_width)


def _xla_mla_paged_attention(q, pool, tables, pos, *, scale, v_width):
    """Gather every table-mapped block of every lane, then a masked
    softmax in f32.  Reads ``nb`` blocks a lane whatever its length: the
    CPU's parity reference, not a serving path."""
    b, s, h, w = q.shape
    bs, nb = pool.shape[1], tables.shape[1]
    keys = pool[tables].reshape(b, nb * bs, -1)[..., :w].astype(jnp.float32)
    sc = jnp.einsum("bshw,btw->bsht", q.astype(jnp.float32), keys) * scale
    q_pos = pos[:, None] + jnp.arange(s, dtype=pos.dtype)            # [B, s]
    vis = (jnp.arange(nb * bs, dtype=pos.dtype)[None, None, :]
           <= q_pos[:, :, None])[:, :, None, :]                  # [B,s,1,T]
    sc = jnp.where(vis, sc, NEG_INF)
    p = jnp.where(vis, jnp.exp(sc - sc.max(-1, keepdims=True)), 0.0)
    out = jnp.einsum("bsht,btv->bshv", p, keys[..., :v_width])
    return (out / p.sum(-1, keepdims=True)).astype(q.dtype)


def _geometry(s, heads, bs, width, v_width, q_dtype, pool_dtype):
    """(path, window rows a cell, pool blocks a chunk) from static shapes:
    the whole window in one cell while its rows fit ``MAX_CELL_ROWS`` and
    the VMEM budget (**stream**), else the largest power-of-two row tile
    that does (**tile**).  Counted: the double-buffered q and output
    blocks, the f32 accumulator, max and sum, the two pool buffers, and
    four 4-byte values a row x key (score, probability, mask, index)."""
    qb, pb = jnp.dtype(q_dtype).itemsize, jnp.dtype(pool_dtype).itemsize
    chunk, keys = CHUNK_BLOCKS, CHUNK_BLOCKS * bs

    def fits(rows):
        per_row = (2 * width * qb + 2 * v_width * qb + 4 * v_width
                   + 2 * 512 + 4 * 4 * keys)
        return rows * per_row + 2 * keys * width * pb <= VMEM_BUDGET_BYTES

    tile = s
    while tile > 1 and (tile * heads > MAX_CELL_ROWS
                        or not fits(tile * heads)):
        tile = 1 << ((tile - 1).bit_length() - 1)      # next lower power
    return ("stream" if tile == s else "tile"), tile, chunk


def _mla_kernel(tables, pos, q_ref, pool_hbm, o_ref, buf, sems, m_ref,
                l_ref, acc_ref, *, block_size, heads, nb, q_tile, v_width,
                scale):
    """One grid cell = (lane b, row tile t): rows ``r * heads + h`` hold
    head h of window row ``t * q_tile + r``.  Walk the lane's table
    columns up to the tile's deepest visible key, ``chunk`` blocks at a
    time; columns past the live ones in the last chunk fetch whatever
    their (clamped) table entry names and are masked."""
    b, t = pl.program_id(0), pl.program_id(1)
    chunk, width = buf.shape[1], buf.shape[3]
    keys = chunk * block_size
    rows = q_ref.shape[1]
    row0 = t * q_tile
    live = jnp.minimum(pl.cdiv(pos[b] + row0 + q_tile, block_size), nb)
    n_chunks = pl.cdiv(live, chunk)

    def copies(i, slot):
        for j in range(chunk):
            block = tables[b, jnp.minimum(i * chunk + j, nb - 1)]
            yield pltpu.make_async_copy(
                pool_hbm.at[block], buf.at[slot, j], sems.at[slot])

    for c in copies(0, 0):
        c.start()

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    r_i = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 0)
    key = jax.lax.broadcasted_iota(jnp.int32, (rows, keys), 1)
    deepest = jnp.minimum(pos[b] + row0 + r_i // heads,
                          nb * block_size - 1)
    q = q_ref[0]
    if q.dtype != buf.dtype:
        q = q.astype(jnp.float32)

    @pl.loop(0, n_chunks)
    def _chunk(i):
        slot = i % 2

        @pl.when(i + 1 < n_chunks)
        def _prefetch():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        k = buf[slot].reshape(keys, width)
        sc = jax.lax.dot_general(
            q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # [rows, keys]
        vis = key + i * keys <= deepest
        sc = jnp.where(vis, sc, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.where(vis, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = k[:, :v_width]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # [rows, v]
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    # every row sees key 0 in chunk 0, so l > 0
    o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "v_width", "interpret"))
def _pallas_mla_paged_attention(q, pool, tables, pos, *, scale, v_width,
                                interpret=False):
    """The Pallas kernel for any static window s >= 1 (module docstring).
    Jitted at module level, so a program traces and lowers it once for
    all its layers; ``interpret=True`` is the CPU test path."""
    b, s, h, _ = q.shape
    nblocks, bs, w = pool.shape[0], pool.shape[1], pool.shape[-1]
    nb = tables.shape[1]
    q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, w - q.shape[-1])))
    path, ts, chunk = _geometry(s, h, bs, w, v_width, q.dtype, pool.dtype)
    _TRACES.inc(path=path, blocks_per_cell=chunk)
    sp = s + (-s) % ts
    if sp != s:
        q = jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    rows = ts * h
    out = pl.pallas_call(
        functools.partial(
            _mla_kernel, block_size=bs, heads=h, nb=nb, q_tile=ts,
            v_width=v_width, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                 # tables, pos
            grid=(b, sp // ts),
            in_specs=[
                pl.BlockSpec((1, rows, w),
                             lambda bb, t, tables, pos: (bb, t, 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, rows, v_width),
                                   lambda bb, t, tables, pos: (bb, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, bs, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 1), jnp.float32),       # running max
                pltpu.VMEM((rows, 1), jnp.float32),       # running sum
                pltpu.VMEM((rows, v_width), jnp.float32),  # accumulator
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, sp * h, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables, pos, q.reshape(b, sp * h, w), pool.reshape(nblocks, bs, w))
    return out.reshape(b, sp, h, v_width)[:, :s]
