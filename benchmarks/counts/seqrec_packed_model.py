"""The sequence model's own operations for one train of PACKED rows under
a period of sliding-window ("swa") and full ("gqa") attention layers and
an expert layer in every layer, forward and backward, recomputation not
counted: what `seqrec_packed_mfu_pct` divides by the steps' time and the
chip's peak.

6 per matrix parameter a token passes (an attention layer's query, key,
value and output projections at its kind's heads; a layer's router, all
its outputs; the head, once: one product of d x V a token; the routed
experts by the slots they really computed, a row's padding positions'
among them: the program routes those too) and the pair work, 2 (qk + v)
operations a pair forward and twice that backward at qk = v = head_dim,
over the pairs INSIDE sessions: a full layer's n (n + 1) / 2 a session of
n positions and query head, a sliding layer's w (w + 1) / 2 + (n - w) w,
w = min(window, n) (counts/packed_attention_kernel.session_pairs over
`shapes["session_positions"]`). A token is a position that lies in a
session: a row's padding is no work of the model's. Norms, rotary turns
and the router's top-k are no matrix work and are not counted; nor is
what a block computes outside a session or the band.

`shapes` is the check's (`checks/seqrec_packed_step.shapes`).
`held_slots` is the routed (token, expert) pairs the experts held here
computed in one train (`pio_train_seqrec_expert_tokens_total`)."""

from benchmarks.counts.packed_attention_kernel import (layers_of,
                                                       session_pairs)


def counts(shapes: dict, held_slots: float):
    """-> operations of one train."""
    s, band = shapes, shapes["swa"]
    d, hd = s["d_model"], s["head_dim"]
    kinds = layers_of(s)
    positions = s["session_positions"]
    tokens = sum(positions)

    def attention(heads):       # wq, wk and wv, wo
        return d * heads * hd + 2 * d * s["n_kv_heads"] * hd + heads * hd * d

    expert = 3 * d * s["moe_width"]
    per_token = kinds.count("gqa") * attention(s["n_heads"]) \
        + kinds.count("swa") * attention(band["heads"]) \
        + len(kinds) * d * s["n_routed_experts"] + d * s["n_vocab"]
    pairs = kinds.count("gqa") * s["n_heads"] * session_pairs(positions) \
        + kinds.count("swa") * band["heads"] * session_pairs(
            positions, band["window"])
    return 6.0 * (tokens * per_token + held_slots * expert) \
        + 3 * pairs * 2 * 2 * hd
