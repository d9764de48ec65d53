"""The grouped product of an expert layer: rows sorted by expert, each
group of rows against its own expert's matrix.

``grouped_matmul(x, w, group_sizes)`` is ``x[a_g : a_g + size_g] @ w[g]``
for every group ``g``, ``a_g`` the sum of the sizes before it.  ``x`` has a
static worst-case number of rows; the groups fill it from the front and the
rows past their sum belong to no group.  The work follows the rows that are
there: a tile of rows is visited once for each group that has rows in it, a
group with no row is never visited and its matrix never read, and the tiles
past the last row cost nothing.  That serves both shapes an expert layer
meets: a decode tick's few ragged rows an expert, where the time is the
reading of each hit expert's matrix once, and a prefill chunk's tens of
rows an expert, where evaluating every held expert on every row would cost
the held experts' fold of the routed FLOPs.

The kernel is the grouped matmul that ships with JAX's Pallas TPU ops
(``jax.experimental.pallas.ops.tpu.megablox.gmm``: grid (column tiles,
active row tiles, K tiles), the tile-to-group map prefetched as scalars,
the number of active tiles a traced grid bound), with tilings chosen on
the chip for this layer's shapes (PERF.md section 6, PR 33:
``lax.ragged_dot`` and a dense evaluation were the other candidates).  Off
the TPU it runs interpreted, so CPU tests exercise the code the chip runs.
Rows that belong to no group come back unwritten (whatever the buffer
held): the caller masks them.
"""

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

# (rows, K, columns) of a tile on the chip: 128 rows fill the matrix unit's
# height, the whole K of an expert spares the accumulation loop, and 512
# columns make a step's slice of an expert's matrix 4 MB at K 4096, long
# enough for its copy to hide a grid step's overhead
TILE = (128, 4096, 512)


def tiling(m, k, n):
    """The tile for an [m, k] x [k, n] group product: ``TILE`` cut to the
    shape (the kernel wants the rows in whole tiles; K and the columns may
    have a rest)."""
    tm = min(TILE[0], m)
    while m % tm:
        tm //= 2
    return tm, min(TILE[1], k), min(TILE[2], n)


def grouped_matmul(x, w, group_sizes):
    """Args:
      x: [m, k] rows sorted by group; ``m`` a multiple of 8.
      w: [groups, k, n], a matrix a group.
      group_sizes: [groups] int32 rows in each group, summing to at most m.

    Returns [m, n] in ``x``'s type, float32 accumulated: row r of group g
    is ``x[r] @ w[g]``; the rows past the groups' sum are not written.
    """
    m, k = x.shape
    return gmm(x, w, group_sizes.astype(jnp.int32),
               preferred_element_type=x.dtype,
               tiling=tiling(m, k, w.shape[-1]),
               interpret=jax.default_backend() != "tpu")
