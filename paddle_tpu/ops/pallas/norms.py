"""Fused LayerNorm / RMSNorm Pallas kernels.

Reference parity: paddle/phi/kernels/gpu/layer_norm_kernel.cu + fused
bias+residual+LN kernels (SURVEY.md §2.1 N3/N4). TPU-native: one VMEM pass
per row block computing the statistics and the normalized output (saving
mean/rstd for backward); backward fuses dx with the dγ/dβ reduction, which
accumulates across row blocks in f32 scratch over a sequential grid.

All statistics in f32 regardless of input dtype (bf16-safe).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import rows_under_budget


def _interpret_default():
    return jax.default_backend() != "tpu"


def _row_block(n, h, dtype):
    """Rows per block for an [n, h] operand, or all n rows when there are
    fewer.  The backward kernels are the widest: x, g and dx blocks
    double-buffered in the input dtype plus about two live f32
    temporaries per element (the TPU compiler counted 17.3 B/element for
    bf16 at hidden 4096)."""
    return min(rows_under_budget(h * (6 * jnp.dtype(dtype).itemsize + 8)),
               n)


def _pad_rows(x2):
    """Pad [n, h] up to a whole number of row blocks."""
    pad = (-x2.shape[0]) % _row_block(*x2.shape, x2.dtype)
    return jnp.pad(x2, ((0, pad), (0, 0))) if pad else x2


# --------------------------------------------------------------- layer_norm

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * w_ref[0].astype(jnp.float32) + b_ref[0].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _ln_bwd_kernel(x_ref, w_ref, mean_ref, rstd_ref, g_ref,
                   dx_ref, dw_ref, db_ref, dw_scr, db_scr, *, n_blocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_scr[:] = jnp.zeros_like(dw_scr)
        db_scr[:] = jnp.zeros_like(db_scr)

    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mean) * rstd

    gw = g * w
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = rstd * (gw - m1 - xhat * m2)
    dx_ref[:] = dx.astype(dx_ref.dtype)

    dw_scr[:] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_scr[:] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == n_blocks - 1)
    def _():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)
        db_ref[:] = db_scr[:].astype(db_ref.dtype)


def _ln_call_fwd(x2, w, b, eps, interpret):
    n, h = x2.shape
    bn = _row_block(n, h, x2.dtype)
    grid = (pl.cdiv(n, bn),)
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h), x2.dtype),
            # (n, 1): 2-D keeps XLA/Mosaic layouts aligned (1-D f32 mismatches)
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, w.reshape(1, h), b.reshape(1, h))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm(x, weight, bias, eps=1e-5, interpret=None):
    """LayerNorm over the last dim. x: [..., H]."""
    if interpret is None:
        interpret = _interpret_default()
    h = x.shape[-1]
    x2 = x.reshape(-1, h)
    n = x2.shape[0]
    xp = _pad_rows(x2)
    y, _, _ = _ln_call_fwd(xp, weight, bias, eps, interpret)
    return y[:n].reshape(x.shape)


def _ln_vjp_fwd(x, weight, bias, eps, interpret):
    if interpret is None:
        interpret = _interpret_default()
    h = x.shape[-1]
    x2 = x.reshape(-1, h)
    n = x2.shape[0]
    xp = _pad_rows(x2)
    y, mean, rstd = _ln_call_fwd(xp, weight, bias, eps, interpret)
    return y[:n].reshape(x.shape), (xp, weight, mean, rstd, x.shape)


def _ln_vjp_bwd(eps, interpret, saved, g):
    if interpret is None:
        interpret = _interpret_default()
    xp, w, mean, rstd, orig_shape = saved
    h = xp.shape[-1]
    n_pad = xp.shape[0]
    g2 = g.reshape(-1, h)
    n = g2.shape[0]
    if n_pad != n:
        g2 = jnp.pad(g2, ((0, n_pad - n), (0, 0)))
    bn = _row_block(n_pad, h, xp.dtype)
    n_blocks = pl.cdiv(n_pad, bn)
    dx, dw, db = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, h), xp.dtype),
            jax.ShapeDtypeStruct((1, h), w.dtype),
            jax.ShapeDtypeStruct((1, h), w.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, h), jnp.float32),
                        pltpu.VMEM((1, h), jnp.float32)],
        interpret=interpret,
    )(xp, w.reshape(1, h), mean, rstd, g2)
    return dx[:n].reshape(orig_shape), dw[0], db[0]


layer_norm.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


# ---------------------------------------------------------------- rms_norm

def _rms_fwd_kernel(x_ref, w_ref, y_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y_ref[:] = (x * rstd * w_ref[0].astype(jnp.float32)).astype(y_ref.dtype)
    rstd_ref[:] = rstd


def _rms_bwd_kernel(x_ref, w_ref, rstd_ref, g_ref, dx_ref, dw_ref, dw_scr,
                    *, n_blocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x * rstd
    gw = g * w
    m = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = rstd * (gw - xhat * m)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dw_scr[:] += jnp.sum(g * xhat, axis=0, keepdims=True)

    @pl.when(i == n_blocks - 1)
    def _():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def rms_norm(x, weight, eps=1e-6, interpret=None):
    y, _ = _rms_fwd_call(x, weight, eps, interpret)
    return y


def _rms_fwd_call(x, weight, eps, interpret):
    if interpret is None:
        interpret = _interpret_default()
    h = x.shape[-1]
    x2 = x.reshape(-1, h)
    n = x2.shape[0]
    xp = _pad_rows(x2)
    bn = _row_block(*xp.shape, xp.dtype)
    y, rstd = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(pl.cdiv(xp.shape[0], bn),),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x.dtype),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp, weight.reshape(1, h))
    return y[:n].reshape(x.shape), (xp, rstd, x.shape)


def _rms_vjp_fwd(x, weight, eps, interpret):
    y, res = _rms_fwd_call(x, weight, eps, interpret)
    return y, (res, weight)


def _rms_vjp_bwd(eps, interpret, saved, g):
    if interpret is None:
        interpret = _interpret_default()
    (xp, rstd, orig_shape), w = saved
    h = xp.shape[-1]
    n_pad = xp.shape[0]
    g2 = g.reshape(-1, h)
    n = g2.shape[0]
    if n_pad != n:
        g2 = jnp.pad(g2, ((0, n_pad - n), (0, 0)))
    bn = _row_block(n_pad, h, xp.dtype)
    n_blocks = pl.cdiv(n_pad, bn)
    dx, dw = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, n_blocks=n_blocks),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, h), xp.dtype),
            jax.ShapeDtypeStruct((1, h), w.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((1, h), jnp.float32)],
        interpret=interpret,
    )(xp, w.reshape(1, h), rstd, g2)
    return dx[:n].reshape(orig_shape), dw[0]


rms_norm.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


# --------------------------------------------------------------- group_norm

# one VMEM budget governs both the group-block sizing and the routing
# guard in nn/functional/norm.py (keep them from diverging)
_GN_VMEM_BUDGET = 256 * 1024  # f32 elements per block (~1MB)


def _gn_group_block(g, row):
    """Largest divisor of g whose [gb, row] f32 block stays under the
    budget — bounds every VMEM buffer independent of channel count (the
    UNet up-blocks reach C=2560 after skip concats).  Only multiples of
    8 (and g itself) are candidates: Mosaic refuses a block whose
    sublane dimension is neither a multiple of 8 nor the array's own.
    The smallest candidate is the floor; `group_norm_supported` keeps
    shapes whose floor does not compile off this kernel."""
    cands = [gb for gb in range(1, g + 1)
             if g % gb == 0 and (gb % 8 == 0 or gb == g)]
    fits = [gb for gb in cands if gb * row <= _GN_VMEM_BUDGET]
    return max(fits) if fits else min(cands)


def _gn_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    # one row per (sample, group): strictly 2-D blocks — Mosaic's layout
    # engine rejects the 4-D [G, Cg, HW] form (hard Check in layout.h)
    x = x_ref[:].astype(jnp.float32)                    # [gb, Cg*HW]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _gn_bwd_kernel(x_ref, w_ref, mean_ref, rstd_ref, g_ref,
                   dx_ref, dwc_ref, dbc_ref):
    # grid = (G/gb, N): samples innermost, so the (j,)-indexed dwc/dbc
    # output blocks are revisited consecutively and accumulate in VMEM
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        dwc_ref[:] = jnp.zeros_like(dwc_ref)
        dbc_ref[:] = jnp.zeros_like(dbc_ref)

    x = x_ref[:].astype(jnp.float32)                    # [gb, Cg*HW]
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mean) * rstd
    gw = g * w
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx = rstd * (gw - m1 - xhat * m2)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # per-column accumulators; the Cg*HW -> Cg reduction finishes in XLA
    dwc_ref[:] += g * xhat
    dbc_ref[:] += g


def _gn_prep(x, weight, bias, num_groups):
    n, c = x.shape[0], x.shape[1]
    cg = c // num_groups
    hw = 1
    for s in x.shape[2:]:
        hw *= s
    x2 = x.reshape(n * num_groups, cg * hw)
    wf = weight.astype(jnp.float32)
    bf = bias.astype(jnp.float32)
    w2 = jnp.broadcast_to(wf.reshape(num_groups, cg, 1),
                          (num_groups, cg, hw)).reshape(num_groups, cg * hw)
    b2 = jnp.broadcast_to(bf.reshape(num_groups, cg, 1),
                          (num_groups, cg, hw)).reshape(num_groups, cg * hw)
    return x2, w2, b2, (n, num_groups, cg, hw)


def _gn_call_fwd(x2, w2, b2, dims, eps, interpret):
    n, g, cg, hw = dims
    row = cg * hw
    gb = _gn_group_block(g, row)
    ngb = g // gb
    return pl.pallas_call(
        functools.partial(_gn_fwd_kernel, eps=eps),
        grid=(ngb, n),
        in_specs=[
            pl.BlockSpec((gb, row), lambda j, i: (i * ngb + j, 0)),
            pl.BlockSpec((gb, row), lambda j, i: (j, 0)),
            pl.BlockSpec((gb, row), lambda j, i: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gb, row), lambda j, i: (i * ngb + j, 0)),
            pl.BlockSpec((gb, 1), lambda j, i: (i * ngb + j, 0)),
            pl.BlockSpec((gb, 1), lambda j, i: (i * ngb + j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n * g, row), x2.dtype),
            jax.ShapeDtypeStruct((n * g, 1), jnp.float32),
            jax.ShapeDtypeStruct((n * g, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2, w2, b2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def group_norm(x, weight, bias, num_groups, eps=1e-5, interpret=None):
    """Fused GroupNorm over NC* layout (the SD-UNet hot norm; ref: the
    fused GroupNorm CUDA kernels in phi/kernels/fusion (U), SURVEY §2.1 N4).
    Grid is (group-blocks, samples): each step normalizes a block of groups
    for one sample in a single VMEM pass; backward fuses dx with dw/db
    accumulation into consecutively-revisited output blocks."""
    y, _ = _gn_fwd(x, weight, bias, num_groups, eps, interpret)
    return y


def _gn_fwd(x, weight, bias, num_groups, eps, interpret):
    if interpret is None:
        interpret = _interpret_default()
    x2, w2, b2, dims = _gn_prep(x, weight, bias, num_groups)
    y, mean, rstd = _gn_call_fwd(x2, w2, b2, dims, eps, interpret)
    return y.reshape(x.shape), (x2, weight, mean, rstd, dims, x.shape)


def _gn_vjp_fwd(x, weight, bias, num_groups, eps, interpret):
    y, res = _gn_fwd(x, weight, bias, num_groups, eps, interpret)
    return y, res


def _gn_vjp_bwd(num_groups, eps, interpret, saved, gy):
    if interpret is None:
        interpret = _interpret_default()
    x2, weight, mean, rstd, dims, orig_shape = saved
    n, g, cg, hw = dims
    row = cg * hw
    gb = _gn_group_block(g, row)
    ngb = g // gb
    w2 = jnp.broadcast_to(
        weight.astype(jnp.float32).reshape(g, cg, 1),
        (g, cg, hw)).reshape(g, row)
    g2 = gy.reshape(n * g, row)
    dx, dwc, dbc = pl.pallas_call(
        _gn_bwd_kernel,
        grid=(ngb, n),
        in_specs=[
            pl.BlockSpec((gb, row), lambda j, i: (i * ngb + j, 0)),
            pl.BlockSpec((gb, row), lambda j, i: (j, 0)),
            pl.BlockSpec((gb, 1), lambda j, i: (i * ngb + j, 0)),
            pl.BlockSpec((gb, 1), lambda j, i: (i * ngb + j, 0)),
            pl.BlockSpec((gb, row), lambda j, i: (i * ngb + j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((gb, row), lambda j, i: (i * ngb + j, 0)),
            pl.BlockSpec((gb, row), lambda j, i: (j, 0)),
            pl.BlockSpec((gb, row), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n * g, row), x2.dtype),
            jax.ShapeDtypeStruct((g, row), jnp.float32),
            jax.ShapeDtypeStruct((g, row), jnp.float32),
        ],
        interpret=interpret,
    )(x2, w2, mean, rstd, g2)
    dw = dwc.reshape(g, cg, hw).sum(-1).reshape(-1).astype(weight.dtype)
    db = dbc.reshape(g, cg, hw).sum(-1).reshape(-1).astype(weight.dtype)
    return dx.reshape(orig_shape), dw, db


group_norm.defvjp(_gn_vjp_fwd, _gn_vjp_bwd)


def group_norm_supported(x_shape, num_groups):
    """True when channels split evenly into groups, the groups into
    blocks of 8 (see `_gn_group_block`), and the smallest such block fits
    VMEM: an [8, row] block at twice the budget is the largest whose f32
    backward the v5e compiler accepted."""
    if (len(x_shape) < 3 or x_shape[1] % num_groups
            or num_groups % 8):
        return False
    row = x_shape[1] // num_groups
    for s in x_shape[2:]:
        row *= s
    return 8 * row <= 2 * _GN_VMEM_BUDGET
