"""Count function of `gdn_chain_roofline`: the contract of what a
linear-attention ("gdn") layer does around the gated delta rule, between
its two projections (`gdn_chain_*`), for the passes one traced train
makes, whatever implements it.

Bytes, every array once each way at the width the model gives it, at the
model's float32. Forward: the projection's q, k, v columns read where
the projection wrote them (Hk dk + Hk dk + Hv dv a position) and, behind
the convolution, SiLU and (q, k) the unit length, written once, q and k
at the Hk KEY heads; the rule's output o and the projection's z columns
read and the gated output written once (3 Hv dv). Backward: the gated
output's gradient, o and z read and the gradients of o and z written
(5 Hv dv); the gradients of q, k, v and the pre-convolution columns read
and the projection's gradient columns written (3 (2 Hk dk + Hv dv)).
The taps, the norm's scale and their gradients are a few kilobytes and
not counted. A layer and step makes one backward pass and one forward
pass, two forward under `remat` (the block is recomputed; the backward
pass keeps what the forward pass made and runs none again).

Operations: the convolution's taps, 2 K a q, k, v element forward and
twice that backward (its transpose and the taps' gradient); the
elementwise chain beside them is no product and not counted. The
contract is memory-bound by over two orders of magnitude."""


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    mixer = s.get("mixer")
    layers = [mixer] * s.get("n_layers", 0) if isinstance(mixer, str) else [
        mixer[i % len(mixer)] for i in range(s.get("n_layers", 0))]
    if not s.get("steps") or "gdn" not in layers:
        return None
    front = s["tokens_per_step"] * (
        2 * s["linear_key_heads"] * s["linear_key_head_dim"]
        + s["linear_value_heads"] * s["linear_value_head_dim"])
    gate = s["tokens_per_step"] * s["linear_value_heads"] \
        * s["linear_value_head_dim"]
    forward_passes = 2 if s.get("remat") else 1
    forward_bytes = (2 * front + 3 * gate) * 4.0
    backward_bytes = (3 * front + 5 * gate) * 4.0
    taps_ops = 2 * s["linear_conv_kernel"] * front
    passes = s["steps"] * layers.count("gdn")
    return (passes * (forward_passes + 2) * taps_ops,
            passes * (forward_passes * forward_bytes + backward_bytes))
