"""Count function of the scorer's roofline: each matching program scores
`rows_shape` user rows (a key of the evidence's shapes: the chunk size,
every chunk of the traced job being full) against the catalogue."""
from benchmarks.lib import roofline


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    ops, nbytes = roofline.scorer_counts(s[reader["rows_shape"]],
                                         s["n_items"], s["rank"], s["num"])
    return n_events * ops, n_events * nbytes
