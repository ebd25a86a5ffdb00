"""The sequence model's own operations for one train's tokens under a
period of full ("gqa") and sliding-window ("swa") attention layers with
head counts of their own, a leading dense layer and an expert layer in
each layer after it, forward and backward, recomputation not counted:
what `seqrec_window_mfu_pct` divides by the steps' time and the chip's
peak.

6 per matrix parameter a token passes (an attention layer's query, key,
value and output projections and its gate's one column a head, at its
kind's heads; the dense layer's three matrices; an expert layer's
router, all its outputs, and its shared expert; the head, once: one
product of d x V a token; the routed experts by the slots they really
computed) and the pair work, 2 (qk + v) operations a pair forward and
twice that backward at qk = v = head_dim: a full layer's L^2 / 2 causal
pairs a session and query head (as counts/seqrec_hybrid_model.py counts
them), a sliding layer's W (W + 1) / 2 + (L - W) W pairs inside its band.
Norms, rotary turns, gates and the router's top-k are no matrix work and
are not counted; nor is what a block computes outside the band.

`shapes` is the check's (`checks/seqrec_window_step.shapes`): the layer
spec and the sizes of a train. `held_slots` is the routed (token,
expert) pairs the experts held here computed in one train
(`pio_train_seqrec_expert_tokens_total`)."""


def counts(shapes: dict, held_slots: float):
    """-> operations of one train."""
    s, band = shapes, shapes["swa"]
    d, hd, layers = s["d_model"], s["head_dim"], s["n_layers"]
    length = s["max_len"]
    kinds = [s["mixer"][i % len(s["mixer"])] for i in range(layers)]
    tokens = s["tokens_per_step"] * s["steps"]

    def attention(heads):       # wq, the gate, wk and wv, wo
        return d * heads * hd + d * heads + 2 * d * s["n_kv_heads"] * hd \
            + heads * hd * d

    expert = 3 * d * s["moe_width"]
    dense = s["first_dense_layers"]
    per_token = kinds.count("gqa") * attention(s["n_heads"]) \
        + kinds.count("swa") * attention(band["heads"]) \
        + dense * 3 * d * s["ffn_width"] \
        + (layers - dense) * (d * s["n_routed_experts"]
                              + s["n_shared_experts"] * expert) \
        + d * s["n_vocab"]
    sessions = tokens / length
    window = min(band["window"], length)
    pairs = kinds.count("gqa") * s["n_heads"] * sessions * length ** 2 / 2 \
        + kinds.count("swa") * band["heads"] * sessions * (
            window * (window + 1) / 2 + (length - window) * window)
    return 6.0 * (tokens * per_token + held_slots * expert) \
        + 3 * pairs * 2 * 2 * hd
