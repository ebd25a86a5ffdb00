"""Count function of `solve_roofline`: every iteration of an ALS train
solves each user's and each item's K x K system once."""
from benchmarks.lib import roofline


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    ops_u, bytes_u = roofline.solve_counts(s["n_users"], s["rank"])
    ops_i, bytes_i = roofline.solve_counts(s["n_items"], s["rank"])
    it = s["num_iterations"]
    return it * (ops_u + ops_i), it * (bytes_u + bytes_i)
