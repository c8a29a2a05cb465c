"""``family_trinity`` with ONE PLANTED FAULT on the served side, for the
rehearsal that a cell's ``correct`` turns false on it
(``tests/test_trinity_cell.py``): the position-ordered view of a lane's
ring over the window layers' blocks starts one block EARLY once the
window has passed — at an entry whose block the lane has given back,
the null block by then or another lane's — so a released block is read.
The reference, the counts and everything else are the family's own.
Never a benchmark configuration's family.
"""

import family_trinity
from family_trinity import *  # noqa: F401,F403
from family_trinity import __all__  # noqa: F401


def serving_parts(**model_kwargs):
    from dlrover_tpu.ops import paged_attention as pa

    view = pa.window_table_view

    def one_block_early(ring, first_block, n_blocks=None):
        return view(ring, first_block - (first_block > 0), n_blocks)

    pa.window_table_view = one_block_early  # this replica process only
    return family_trinity.serving_parts(**model_kwargs)
