"""Count function of `expert_kernel_roofline`: the contract of the expert
layer's grouped products (`grouped_product_pallas_*`) for one traced
train, whatever implements them.

An expert layer and step multiplies the slots routed to the experts held
here by their experts' matrices twelve times: gate, up and down forward,
the same three again inside the layer's own backward pass, and six
gradient products (`dy W^T` three times, `x^T dy` a group three times).
Each is 2 d w operations a routed slot and moves, at the model's
float32, the slots' rows on both of its row sides (d and w wide) and the
held experts' d x w matrices once. The slots are those the program
counted (`pio_train_seqrec_expert_tokens_total`, as
counts/seqrec_model.py takes them), never the rows of a pass: rows past
the last routed slot are no work. Counted so, the share holds for any
implementation of the same twelve products and cannot pass 100."""

from benchmarks.lib import layer_readers

PRODUCTS = 12
SLOTS_METRIC = "pio_train_seqrec_expert_tokens_total"


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    jobs = evidence.get("jobs", [])
    slots = layer_readers._delta(evidence, SLOTS_METRIC, None)
    if s.get("ffn") != "moe" or not s.get("steps") or not slots or not jobs:
        return None
    d, w = s["d_model"], s["moe_width"]
    lo, hi = s["held_experts"]
    layers = s["n_layers"] - s.get("first_dense_layers", 0)
    slots = slots / len(jobs)                   # of one train, all layers
    matrices = layers * s["steps"] * (hi - lo) * d * w
    ops = PRODUCTS * 2.0 * slots * d * w
    nbytes = PRODUCTS * 4.0 * (slots * (d + w) + matrices)
    return ops, nbytes
