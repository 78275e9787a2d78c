import os

# Tests run single-device (the dry-run sets its own 512-device flag in its
# own process). Cap compilation parallelism noise on the 1-core container.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np
import pytest
from hypothesis import settings

jax.config.update("jax_enable_x64", False)


# Property tests compile a kernel on their first example, which no
# per-example deadline survives.
settings.register_profile("repro", deadline=None)
settings.load_profile("repro")


@pytest.fixture
def mixed_span_bands():
    """A batch for the banded kernels' per-Q-block tile counts: returns
    ``(starts, lens, rows, budget)``, 35 queries over a bank of ``rows``
    (13 tiles of 128, the last ragged) with an 8-tile budget. With
    block_q 8 and block_r 128 its Q blocks are:

    0. narrow windows in tile 1, the last ending on the tile's edge;
    1. windows from tile 2 to tile 9, the whole budget;
    2. windows reaching the bank's end, so the tile base is clipped;
    3. windows in tiles 4-6 around an empty window at row 640;
    4. three queries in tiles 2-3, edge-padded to a block by the
       wrappers.

    Some windows are narrower than k=5, so overflow slots are compared
    too.
    """
    starts = np.array(
        [130, 140, 150, 170, 180, 190, 200, 210,
         261, 300, 420, 600, 800, 1000, 1100, 1200,
         1450, 1480, 1500, 1510, 1520, 1530, 1540, 1570,
         520, 560, 600, 640, 650, 700, 730, 760,
         330, 360, 380], np.int32)
    lens = np.array(
        [20, 3, 60, 2, 50, 30, 40, 46,
         40, 100, 300, 60, 200, 100, 80, 52,
         123, 93, 73, 63, 53, 43, 33, 3,
         40, 50, 60, 0, 70, 40, 4, 90,
         30, 2, 60], np.int32)
    return starts, lens, 12 * 128 + 37, 8
