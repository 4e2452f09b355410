"""Kernels: the (query, key) pairs the attention kernels' blocks compute
a head and step under the causal mask of the whole packed sequence (a
static of the compiled call: `ops/attention.causal_block_pairs`, the
blocks on and under the diagonal; splash's `SegmentIds` mask inside a
block and skip none) over the pairs the document mask needs, the sum over
the step's documents of n (n + 1) / 2 (the program's counter
`packed_attn_pairs_needed`, median over the window's steps); 1 were every
computed pair read. Program counter."""


def read(record):
    import statistics

    computed = record.get("static", {}).get("packed_attn_pairs_computed")
    needed = record.get("counters", {}).get("packed_attn_pairs_needed")
    if not (computed and needed):
        return None
    return computed / statistics.median(needed)
