"""The sequence model's own operations for one train's tokens under a
layer spec of single-sub-layer layers (state-space "ssm", grouped-query
"gqa", latent two-matrix experts "moe") with a multi-token-prediction
module, at the sizes this chip HOLDS of each layer, forward and backward,
recomputation not counted: what `seqrec_ssm_mfu_pct` divides by the
steps' time and the chip's peak.

It walks `shapes["layers"]`, every layer a step runs, the module's after
the stack's, at `shapes["held"]`'s sizes (`checks/seqrec_ssm_step.
shapes`): 6 per matrix parameter a token passes -- a state-space layer's
two input projections, its convolution's taps and its output projection
at the held heads and groups; an attention layer's four projections at
the held query and key/value heads; an expert layer's router (all its
outputs), the latent's two projections, the shared expert's two matrices
at the held columns, and the routed experts' two matrices by the slots
they really computed; the module's 2 d x d projection; the head once for
the stack and once more for the module -- and the pair work:

* attention: L (L + 1) / 2 causal pairs a session and held query head,
  2 (qk + v) operations a pair forward and 2 (3 qk + 2 v) backward at
  qk = v = head_dim;
* the scan, position by position as its equations are written: two
  products over a head's P x N state a position (the write dt x B^T
  into the decayed state, the read S C), 2 P N operations each, the
  backward pass twice the forward. What the chunked form computes
  besides (a chunk's score matrix) is the implementation's and is not
  counted.

Norms, gates, the squared ReLU and the decays are no matrix work and are
not counted. `held_slots` is the routed (token, expert) pairs the experts
held here computed in one train, every expert layer's
(`pio_train_seqrec_expert_tokens_total`)."""


def counts(shapes: dict, held_slots: float):
    """-> operations of one train."""
    s, held = shapes, shapes["held"]
    d, length, hd = s["d_model"], s["max_len"], s["head_dim"]
    tokens = s["tokens_per_step"] * s["steps"]
    ssm = s["ssm"]
    hp = held["ssm_heads"] * ssm["head_dim"]
    gn = held["ssm_groups"] * ssm["state"]
    per_layer = {
        "ssm": d * (2 * hp + 2 * gn) + d * held["ssm_heads"]
        + ssm["conv_kernel"] * (hp + 2 * gn) + hp * d,
        "gqa": 2 * d * held["n_heads"] * hd + 2 * d * held["n_kv_heads"] * hd,
        "moe": d * s["n_routed_experts"] + 2 * d * s["moe_latent_size"]
        + 2 * d * held["shared_width"]}
    kinds = [mixer or ffn for mixer, ffn in s["layers"]]
    module = bool(s.get("mtp_layers"))
    per_token = sum(per_layer[kind] for kind in kinds) \
        + (1 + module) * d * s["n_vocab"] + module * 2 * d * d
    expert = 2 * s["moe_latent_size"] * s["moe_width"]
    sessions = tokens / length
    pairs = sessions * held["n_heads"] * length * (length + 1) / 2
    causal = kinds.count("gqa") * pairs * (2 * 2 * hd + 2 * 5 * hd)
    state = kinds.count("ssm") * tokens * held["ssm_heads"] * 3 * 2 * 2 \
        * ssm["head_dim"] * ssm["state"]
    return 6.0 * (tokens * per_token + held_slots * expert) + causal + state
