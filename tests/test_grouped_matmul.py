"""``client_tpu.ops.grouped_matmul``: the tile it picks for every product
the expert cells run, and one interpreted product (the code the chip runs)
at a tile as wide as its matrices, against a plain float32 product a group.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from client_tpu.ops.grouped_matmul import (
    VMEM_BUDGET, grouped_matmul, tiling, vmem_bytes)

# (m, K, N) of each cell's gate-and-up and down products, a tick's rows and
# a chunk's, and the tile each runs at: an expert's whole width where VMEM
# holds it (the fixedgen cell's four), else 512 columns (the rag cell's four
# and the longdoc cell's).
CELL_TILES = {
    "rag.tick.gate_up": ((256, 4096, 8192), (128, 4096, 512)),
    "rag.chunk.gate_up": ((4096, 4096, 8192), (128, 4096, 512)),
    "rag.tick.down": ((256, 4096, 4096), (128, 4096, 512)),
    "rag.chunk.down": ((4096, 4096, 4096), (128, 4096, 512)),
    "longdoc.tick.gate_up": ((256, 7168, 4096), (128, 3584, 512)),
    "longdoc.chunk.gate_up": ((4096, 7168, 4096), (128, 3584, 512)),
    "longdoc.tick.down": ((256, 2048, 7168), (128, 2048, 512)),
    "longdoc.chunk.down": ((4096, 2048, 7168), (128, 2048, 512)),
    "fixedgen.tick.gate_up": ((1024, 2048, 1536), (128, 2048, 1536)),
    "fixedgen.chunk.gate_up": ((4096, 2048, 1536), (128, 2048, 1536)),
    "fixedgen.tick.down": ((1024, 768, 2048), (128, 768, 2048)),
    "fixedgen.chunk.down": ((4096, 768, 2048), (128, 768, 2048)),
}


@pytest.mark.parametrize("product", sorted(CELL_TILES))
def test_the_tile_of_each_cells_product(product):
    shape, tile = CELL_TILES[product]
    assert tiling(*shape) == tile


@pytest.mark.parametrize("product", sorted(CELL_TILES))
def test_the_tile_divides_in_whole_lanes_and_its_blocks_fit_vmem(product):
    """Every dimension whole tiles (the kernel pads a tile that does not
    divide), K and columns in whole lanes; the step's blocks under the
    budget, and an expert's whole width over it wherever the tile is
    narrower."""
    (m, k, n), _ = CELL_TILES[product]
    tm, tk, tn = tiling(m, k, n)
    assert m % tm == k % tk == n % tn == 0
    assert tk % 128 == tn % 128 == 0
    assert vmem_bytes(tm, tk, tn) <= VMEM_BUDGET
    assert tn == n or vmem_bytes(tm, tk, n) > VMEM_BUDGET


def test_a_product_at_the_whole_width_matches_each_groups_own():
    """m 256, K 256, N 768: one column tile of the whole width (a cap of
    512 columns would take two of 384).  Eight groups, the third
    empty, one straddling the two row tiles, 200 of 256 rows used; each
    group's rows against its own matrix, in float32 from the same bf16
    operands."""
    m, k, n = 256, 256, 768
    assert tiling(m, k, n) == (128, 256, 768)
    sizes = np.array([30, 17, 0, 60, 41, 5, 33, 14], np.int32)
    kx, kw = jax.random.split(jax.random.PRNGKey(41))
    x = jax.random.normal(kx, (m, k), jnp.bfloat16)
    w = (jax.random.normal(kw, (len(sizes), k, n), jnp.float32)
         * k ** -0.5).astype(jnp.bfloat16)
    got = np.asarray(grouped_matmul(x, w, jnp.asarray(sizes)), np.float32)
    xf, wf = np.asarray(x, np.float32), np.asarray(w, np.float32)
    ends = np.cumsum(sizes)
    for g, (a, b) in enumerate(zip(ends - sizes, ends)):
        np.testing.assert_allclose(got[a:b], xf[a:b] @ wf[g],
                                   rtol=1e-2, atol=2e-2)
