"""Grouped matrix product over the experts a chip holds: rows sorted by
expert, one ``[k, n]`` matrix a group, no padding to a capacity.

``lhs [m, k]`` holds ``sizes[g]`` consecutive rows for each of the ``G``
groups, ``rhs [G, k, n]`` their matrices; rows past ``sum(sizes)`` are
nobody's and come out as garbage the caller drops. On the TPU this is
JAX's own Pallas kernel (``pallas.ops.tpu.megablox.gmm``): it visits only
the row tiles a group owns, so its time follows the rows that are real —
0.98 ms for three 16 x 7168 x 2048 experts' worth of weights whether 64
or 1 024 of 16 384 rows are real, 58 % of the weights' read time, against
``lax.ragged_dot``'s 1.73 ms (my chip run, PR 31, tiles (256, 1024,
1024); the kernel's default tiles of 128 read 5.3 ms). Elsewhere, and
where a dimension does not take the tiles, ``lax.ragged_dot``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from apex_tpu.kernels._utils import use_interpret

#: row, contraction and column tiles of the Pallas kernel
TILES = (256, 1024, 1024)


def _tiles(m: int, k: int, n: int):
    """``TILES`` cut to the problem, or None where a dimension is not a
    whole number of (lane-aligned) tiles."""
    out = []
    for size, tile, unit in zip((m, k, n), TILES, (8, 128, 128)):
        tile = min(tile, size)
        if size % tile or tile % unit:
            return None
        out.append(tile)
    return tuple(out)


def grouped_matmul(lhs, rhs, sizes):
    """``out[r] = lhs[r] @ rhs[group of row r]`` in ``lhs``'s dtype."""
    m, k = lhs.shape
    tiles = None if use_interpret() else _tiles(m, k, rhs.shape[2])
    if tiles is None:
        return lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=lhs.dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(lhs, rhs, sizes.astype(jnp.int32),
               preferred_element_type=lhs.dtype, tiling=tiles)
