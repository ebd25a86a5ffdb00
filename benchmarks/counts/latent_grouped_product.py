"""Count function of `latent_expert_kernel_roofline`: the contract of the
expert layer's grouped products (`grouped_product_pallas_*`) for one
traced train where an expert is TWO matrices around a squared ReLU in a
latent of `moe_latent_size`, whatever implements them.

As counts/grouped_product.py (which returns nothing for this spec: its
twelve products at d_model are a SwiGLU's), with eight products for
twelve: up and down forward, the same two again inside the layer's own
backward pass, and four gradient products (`dy W^T` twice, `x^T dy` a
group twice). Each is 2 c w operations a routed slot (c the latent's
width) and moves, at the model's float32, the slots' rows on both of its
row sides and the held experts' c x w matrices once. The slots are those
the program counted (`pio_train_seqrec_expert_tokens_total`), never the
rows of a pass, over every expert layer a step runs (`shapes["layers"]`,
the multi-token-prediction module's among them)."""

from benchmarks.lib import layer_readers

PRODUCTS = 8
SLOTS_METRIC = "pio_train_seqrec_expert_tokens_total"


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    jobs = evidence.get("jobs", [])
    slots = layer_readers._delta(evidence, SLOTS_METRIC, None)
    if s.get("expert_act") != "relu2" or not s.get("moe_latent_size") \
            or not s.get("steps") or not slots or not jobs:
        return None
    c, w = s["moe_latent_size"], s["moe_width"]
    layers = sum(1 for _, ffn in s["layers"] if ffn == "moe")
    slots = slots / len(jobs)                   # of one train, all layers
    matrices = layers * s["steps"] * s["held"]["experts"] * c * w
    ops = PRODUCTS * 2.0 * slots * c * w
    nbytes = PRODUCTS * 4.0 * (slots * (c + w) + matrices)
    return ops, nbytes
