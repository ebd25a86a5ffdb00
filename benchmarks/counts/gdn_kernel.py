"""Count function of `gdn_kernel_roofline`: the contract of the gated
delta rule (`gated_delta_rule_pallas_*`) for the calls one traced train
makes, whatever implements it.

Operations: the rule's state, position by position as its equations are
written (`counts/seqrec_hybrid_model.py` counts the same): three products
over a head's dk x dv state a position and value head (S'^T k, k delta^T,
S^T q), 2 dk dv operations each, forward; the backward pass twice that.
What the chunked form computes besides (the triangular system within a
chunk, the chunk's scores) is the implementation's and is not counted.
Bytes: q, k, v, g and beta of every value head read and `o` written once
forward; those and `do` read and the five gradients written once
backward, at the model's float32. A linear-attention ("gdn") layer and
step makes one backward and one forward call, two forward under `remat`
(the block is recomputed)."""


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    mixer = s.get("mixer")
    layers = [mixer] * s.get("n_layers", 0) if isinstance(mixer, str) else [
        mixer[i % len(mixer)] for i in range(s.get("n_layers", 0))]
    if not s.get("steps") or "gdn" not in layers:
        return None
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    rows = s["tokens_per_step"] * s["linear_value_heads"]
    forward_calls = 2 if s.get("remat") else 1
    forward_ops = rows * 3 * 2 * dk * dv
    ops = (forward_calls + 2) * forward_ops
    operands = 2 * dk + dv + 2                  # q k v g beta
    forward_bytes = rows * (operands + dv) * 4.0
    backward_bytes = rows * (operands + dv + operands) * 4.0
    nbytes = forward_calls * forward_bytes + backward_bytes
    calls = s["steps"] * layers.count("gdn")
    return calls * ops, calls * nbytes
