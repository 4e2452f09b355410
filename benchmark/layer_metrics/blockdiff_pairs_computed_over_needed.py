"""Kernels: the (query, key) pairs the compiled attention call computes
under the block-diffusion mask, the kernel blocks the mask leaves
non-empty times a block's area, over the `L^2 + L*B` pairs the mask needs;
1 were no block partial. A static of the compiled call, from the mask's
block table at trace time (`ops/attention.block_table`; benchlib/
blockdiff_reduce.py): at a block length of 4 every kernel block on the
three diagonals is partial."""


def read(record):
    from benchlib import blockdiff_reduce
    return blockdiff_reduce.pairs_computed_over_needed(record)
