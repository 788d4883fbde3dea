"""Grouped matrix product for dropless sparse experts.

``grouped_matmul(lhs, rhs, group_sizes)``: the rows of ``lhs`` [M, K] are
sorted by group, group g owning the next ``group_sizes[g]`` of them, and
each row is multiplied by its own group's matrix ``rhs[g]`` [K, N].  Cost
follows the rows, not groups x rows: a group no row chose is never visited,
and a group's matrix is read once for each row tile its rows touch, not
once a row.  Rows past ``sum(group_sizes)`` come back undefined.

On TPU this is the megablox kernel that ships with jax
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: grid over (column tile,
visited (group, row tile) pair, depth tile), the visits computed from
``group_sizes`` on the device, empty groups skipped); elsewhere
``jax.lax.ragged_dot``, whose default lowering is a plain masked product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import pallas as _pallas


def _row_tile(m):
    """Rows a visit: 128 while the rows are few (a decode step's 6 rows a
    lane: a larger tile would only multiply masked rows), 512 once there
    are thousands, so that a group's matrix is read once for 512 rows and
    the product is bound by the MXU, not by re-reading weights."""
    return 128 if m <= 4096 else 512


def _tiles(k, n, tm, itemsize):
    """(tk, tn): the 128-multiple divisors of k and n whose [tk, tn] weight
    tile is largest (then deepest) while the double-buffered weight and
    row tiles, the f32 accumulator and the output tile fit the VMEM
    budget: large tiles keep the weight stream in few, long copies."""
    def divisors(x):
        return [t for t in range(128, x + 1, 128) if x % t == 0]

    best = (128, 128)
    for tk in divisors(k):
        for tn in divisors(n):
            need = (2 * itemsize * tk * (tn + tm)
                    + tm * tn * (4 + 2 * itemsize))
            if (need <= _pallas.VMEM_BUDGET_BYTES
                    and (tk * tn, tk) > (best[0] * best[1], best[0])):
                best = (tk, tn)
    return best


def _pallas_grouped_matmul(lhs, rhs, group_sizes, interpret=False):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = lhs.shape
    n = rhs.shape[2]
    tm = _row_tile(m)
    pad = (-m) % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    itemsize = jnp.dtype(rhs.dtype).itemsize
    if k % 128 or n % 128:
        tiling = (tm, k, n)             # the tests' widths: one tile
    else:
        tiling = (tm,) + _tiles(k, n, tm, itemsize)
    out = gmm(lhs, rhs, group_sizes.astype(jnp.int32),
              preferred_element_type=lhs.dtype, tiling=tiling,
              interpret=interpret)
    return out[:m] if pad else out


def grouped_matmul(lhs, rhs, group_sizes):
    """[M, K] x [G, K, N] -> [M, N] in lhs's dtype (module docstring)."""
    if jax.default_backend() == "tpu":
        return _pallas_grouped_matmul(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
