"""The sequence model's own operations for one train's tokens, forward
and backward (6 per active parameter and token, plus causal attention),
recomputation not counted: what `seqrec_model_flops_pct` divides by the
steps' time and the chip's peak.

`shapes` is the check's (`checks/seqrec_step.shapes`): the layer spec
and the sizes of a train. `held_slots` is the routed (token, expert)
pairs the experts held here computed in one train
(`pio_train_seqrec_expert_tokens_total`): the experts' operations follow
the routing, not an average."""


def counts(shapes: dict, held_slots: float):
    """-> operations of one train."""
    s = shapes
    d, h, layers = s["d_model"], s["n_heads"], s["n_layers"]
    tokens = s["tokens_per_step"] * s["steps"]
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    attention = d * h * qk + d * (s["kv_lora_rank"] + s["qk_rope_head_dim"]) \
        + s["kv_lora_rank"] * h * (s["qk_nope_head_dim"] + s["v_head_dim"]) \
        + h * s["v_head_dim"] * d
    dense = 3 * d * s["ffn_width"]
    expert = 3 * d * s["moe_width"]
    expert_layers = layers - s["first_dense_layers"]
    per_token = layers * attention + s["first_dense_layers"] * dense \
        + expert_layers * (d * s["n_routed_experts"]
                           + s["n_shared_experts"] * expert) \
        + d * s["n_vocab"]
    # causal scores and weighted values: L^2 / 2 pairs a sequence and head,
    # 2 operations each for q.k (width qk) and for p.v (width v), forward;
    # the backward pass is twice the forward
    pairs = s["max_len"] / 2 * tokens
    causal = 3 * layers * h * pairs * 2 * (qk + s["v_head_dim"])
    return 6.0 * (tokens * per_token + held_slots * expert) + causal
