"""The sequence model's own operations for one train's tokens under a
pattern of gated short-convolution ("conv") and grouped-query ("gqa")
layers, leading dense SwiGLU layers and then an expert layer in each,
with a tied head, forward and backward, recomputation not counted: what
`seqrec_conv_mfu_pct` divides by the steps' time and the chip's peak.

6 per matrix parameter a token passes (a convolution layer's two
projections and its taps; an attention layer's four projections, with or
without a gate's half; the dense layer's three matrices; the router; the
head, which is the embedding table, once; the routed experts by the
slots they really computed; there is no shared expert unless the spec
names one), and the attention layers' causal scores and weighted values.
The convolution's two elementwise products are not matrix work and are
not counted.

`shapes` is the check's (`checks/seqrec_conv_step.shapes`): the layer
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
    short, full = kinds.count("conv"), kinds.count("gqa")
    dense = s.get("first_dense_layers", 0)
    tokens = s["tokens_per_step"] * s["steps"]
    conv = d * 3 * d + s.get("conv_kernel", 0) * d + d * d
    gate = 2 if s.get("attention_gate", True) else 1
    gqa = d * gate * h * s["head_dim"] \
        + 2 * d * s["n_kv_heads"] * s["head_dim"] + h * s["head_dim"] * d
    expert = 3 * d * s["moe_width"]
    per_token = short * conv + full * gqa + dense * 3 * d * s["ffn_width"] \
        + (layers - dense) * (d * s["n_routed_experts"]
                              + s.get("n_shared_experts", 0) * expert) \
        + d * s["n_vocab"]
    # causal scores and weighted values: L^2 / 2 pairs a sequence and head,
    # 2 operations each for q.k and for p.v over head_dim, forward; the
    # backward pass is twice the forward
    pairs = s["max_len"] / 2 * tokens
    causal = 3 * full * h * pairs * 2 * 2 * s["head_dim"]
    return 6.0 * (tokens * per_token + held_slots * expert) + causal
