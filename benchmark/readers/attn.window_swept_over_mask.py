"""(query, key) pairs of the score blocks the window kernels swept over the
pairs inside the window's mask, from the step's report
(`attn.window_pairs_swept / attn.window_pairs_in_mask`, forward and backward
together, a head): 1.0 is nothing swept in vain, ~1.25 at 512-row blocks and
~1.5 at 1024-row blocks under a window of 2,048, 2.3 a causal sweep that only
masks. A program that reports no pairs gives nothing."""
from benchmark.readers import _moe


def read(ctx):
    swept, mask = (_moe.mean(ctx, k) for k in ("attn.window_pairs_swept",
                                               "attn.window_pairs_in_mask"))
    if swept is None or not mask:
        return None
    return swept / mask
