"""Count function of `packed_window_attention_kernel_roofline`: the
contract of sliding-window attention over grouped query heads on PACKED
rows, for the calls one traced train makes, whatever implements it (here
the banded kernels, `window_attention_pallas_*`, told each position's
session).

A query at t of a session sees that session's keys s with t - W < s <=
t, so a session of n positions and a query head have w (w + 1) / 2 + (n
- w) w pairs, w = min(W, n), and a train's rows the sum over the
sessions laid into them (`shapes["session_positions"]`); nothing a block
computes outside a session or outside the band is counted. Operations a
pair, bytes a position and calls a layer as
counts/packed_attention_kernel.py states them, at the sliding layers'
heads and window (`shapes["swa"]`)."""

from benchmarks.counts.packed_attention_kernel import kernel_counts


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    band = s.get("swa")
    if not band:
        return None
    return kernel_counts(s, "swa", band["heads"], band["window"])
