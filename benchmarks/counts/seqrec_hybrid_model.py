"""The sequence model's own operations for one train's tokens under a
period of linear-attention ("gdn") and grouped-query ("gqa") layers with
an expert layer in each, forward and backward, recomputation not
counted: what `seqrec_hybrid_mfu_pct` divides by the steps' time and the
chip's peak.

6 per active parameter and token (every matrix a token passes: the
mixers' projections and the convolution's taps, the router, the shared
expert and its gate, the head; the routed experts by the slots they
really computed); the full layers' causal scores and weighted values;
and the delta rule's state, position by position as its equations are
written: three products over a head's dk x dv state a position (S'^T k,
k delta^T, S^T q), 2 dk dv operations each, the backward pass twice the
forward. What the chunked form computes besides (the triangular system
within a chunk) is the implementation's and is not counted.

`shapes` is the check's (`checks/seqrec_hybrid_step.shapes`): the layer
spec and the sizes of a train. `held_slots` is the routed (token,
expert) pairs the experts held here computed in one train
(`pio_train_seqrec_expert_tokens_total`)."""


def counts(shapes: dict, held_slots: float):
    """-> operations of one train."""
    s = shapes
    d, h, layers = s["d_model"], s["n_heads"], s["n_layers"]
    mixer = s["mixer"]
    kinds = [mixer] * layers if isinstance(mixer, str) else [
        mixer[i % len(mixer)] for i in range(layers)]
    linear, full = kinds.count("gdn"), kinds.count("gqa")
    tokens = s["tokens_per_step"] * s["steps"]
    keys = s["linear_key_heads"] * s["linear_key_head_dim"]
    values = s["linear_value_heads"] * s["linear_value_head_dim"]
    gdn = d * (2 * keys + 2 * values) + d * 2 * s["linear_value_heads"] \
        + s["linear_conv_kernel"] * (2 * keys + values) + values * d
    gqa = d * 2 * h * s["head_dim"] + 2 * d * s["n_kv_heads"] * s["head_dim"] \
        + h * s["head_dim"] * d
    expert = 3 * d * s["moe_width"]
    per_token = linear * gdn + full * gqa + layers * (
        d * s["n_routed_experts"] + s["n_shared_experts"] * expert + d) \
        + d * s["n_vocab"]
    # causal scores and weighted values: L^2 / 2 pairs a sequence and head,
    # 2 operations each for q.k and for p.v over head_dim, forward; the
    # backward pass is twice the forward
    pairs = s["max_len"] / 2 * tokens
    causal = 3 * full * h * pairs * 2 * 2 * s["head_dim"]
    state = 3 * linear * tokens * s["linear_value_heads"] * 3 * 2 \
        * s["linear_key_head_dim"] * s["linear_value_head_dim"]
    return 6.0 * (tokens * per_token + held_slots * expert) + causal + state
