"""The sequence model's own operations for one train's tokens under a
looped stack of multi-head-attention layers with dense SwiGLU
feed-forwards and an untied head read once a pass, forward and backward,
recomputation not counted: what `seqrec_looped_mfu_pct` divides by the
steps' time and the chip's peak.

6 per matrix parameter a token passes (a layer's fused q/k/v projection,
its output projection and its three feed-forward matrices, once a LAYER
PASS; the head once a pass of the stack), and the layers' causal scores
and weighted values: L (L + 1) / 2 pairs a session, head and layer pass,
2 (qk + v) operations a pair forward and 2 (3 qk + 2 v) backward at qk =
v = d / heads. The norms, the exit gate's column and the exit
distribution are no matrix work and are not counted.

`shapes` is the check's (`checks/seqrec_looped_step.shapes`): the layer
spec and the sizes of a train. `layer_pass_tokens` is the positions of
one train's batches times the layer passes the compiled step reported
having run (`pio_train_seqrec_layer_pass_tokens_total`, both labels): a
step that ran the stack once counts a quarter of what one that ran it
four times counts, whatever the spec says."""


def counts(shapes: dict, layer_pass_tokens: float):
    """-> operations of one train."""
    s = shapes
    d, heads, length = s["d_model"], s["n_heads"], s["max_len"]
    tokens = s["tokens_per_step"] * s["steps"]
    layer = d * 3 * d + d * d + 3 * d * s["ffn_width"]
    # the layer passes and the passes of the stack the program ran
    layer_passes = layer_pass_tokens / tokens
    loops = layer_passes / s["n_layers"]
    width = d // heads
    pairs = tokens / length * heads * length * (length + 1) / 2
    causal = layer_passes * pairs * (2 * 2 * width + 2 * 5 * width)
    return 6.0 * (layer_pass_tokens * layer
                  + loops * tokens * d * s["n_vocab"]) + causal
