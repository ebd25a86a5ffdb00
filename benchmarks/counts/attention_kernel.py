"""Count function of `attention_kernel_roofline`: the contract of the
causal attention kernels (`flash_attention_pallas_*`) for the calls one
traced train makes.

A session and head have L (L + 1) / 2 causal (query, key) pairs. A
forward call takes 2 (qk + v) operations a pair (the scores over the
q/k width, the weighted values over the v width); the backward pass
2 (3 qk + 2 v) (five products: `s`, `dq`, `dk` over qk and `dp`, `dv`
over v), however many kernels it is split into and whatever they
compute twice. A layer and step makes one backward and one forward
call, two forward under `remat` (the block is recomputed). Widths are
the published ones (192, never a padded 256). Bytes: q, k, v (and `do`
backward) read once, `o` (`dq`, `dk`, `dv` backward) written once, at
the model's float32."""


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    if not s.get("steps") or s.get("mixer") != "mla":
        return None
    length, heads = s["max_len"], s["n_heads"]
    sessions = s["tokens_per_step"] // length
    qk = s["qk_nope_head_dim"] + s["qk_rope_head_dim"]
    v = s["v_head_dim"]
    pairs = sessions * heads * length * (length + 1) / 2
    forward_calls = 2 if s.get("remat") else 1
    ops = pairs * (forward_calls * 2 * (qk + v) + 2 * (3 * qk + 2 * v))
    rows = sessions * heads * length
    forward_bytes = rows * (2 * qk + 2 * v) * 4.0          # q k v | o
    backward_bytes = rows * (2 * qk + 2 * v + 2 * qk + v) * 4.0
    nbytes = forward_calls * forward_bytes + backward_bytes
    calls = s["steps"] * s["n_layers"]
    return calls * ops, calls * nbytes
