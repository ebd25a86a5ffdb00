"""Count function of `short_conv_chain_roofline`: the contract of what a
gated short-convolution ("conv") layer does between its two projections
(`short_conv_chain_*`), y = c * conv(b * u) on the input projection's
columns [b | c | u], for the passes one traced train makes, whatever
implements it.

Bytes, every array once each way at the width the model gives it (D =
`d_model` columns a part), at the model's float32. Forward: b, c, u read
where the projection wrote them (3 D a position) and y written once (D).
Backward: b, c, u and y's gradient read (3 D + D) and the projection's
gradient written (3 D). The taps and their gradient are a few kilobytes
and not counted. A layer and step makes one backward pass and one
forward pass, two forward under `remat` (the block is recomputed; the
backward pass keeps the projection's output and nothing else).

Operations: the convolution's taps, 2 K an element of D forward and
twice that backward (its transpose and the taps' gradient); the two
gates beside them are no product and not counted. The contract is
memory-bound by over two orders of magnitude."""


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    mixer = s.get("mixer")
    if not s.get("steps") or not mixer:     # (a model of sub-layers: None)
        return None
    layers = [mixer] * s["n_layers"] if isinstance(mixer, str) else [
        mixer[i % len(mixer)] for i in range(s["n_layers"])]
    if "conv" not in layers:
        return None
    part = s["tokens_per_step"] * s["d_model"]
    forward_passes = 2 if s.get("remat") else 1
    forward_bytes = (3 + 1) * part * 4.0
    backward_bytes = (3 + 1 + 3) * part * 4.0
    taps_ops = 2 * s["conv_kernel"] * part
    passes = s["steps"] * layers.count("conv")
    return (passes * (forward_passes + 2) * taps_ops,
            passes * (forward_passes * forward_bytes + backward_bytes))
