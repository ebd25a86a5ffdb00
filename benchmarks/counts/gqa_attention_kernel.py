"""Count function of `gqa_attention_kernel_roofline`: the contract of the
causal attention kernels (`flash_attention_pallas_*`) under grouped query
heads, for the calls one traced train makes.

A session and query head have L (L + 1) / 2 causal (query, key) pairs. A
forward call takes 2 (qk + v) operations a pair (the scores over the q/k
width, the weighted values over the v width, both `head_dim`); the
backward pass 2 (3 qk + 2 v) (five products: `s`, `dq`, `dk` over qk and
`dp`, `dv` over v), however many kernels it is split into and whatever
they compute twice. A full-attention ("gqa") layer and step makes one
backward and one forward call, two forward under `remat` (the block is
recomputed). Bytes: q (and `do` backward) of the query heads and k, v of
the key/value heads read once, `o` (`dq` of the query heads, `dk`, `dv`
of the key/value heads backward) written once, at the model's float32:
that the kernels write `dk`, `dv` a query head and sum them after is the
implementation's, not the contract's."""


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    mixer = s.get("mixer")
    layers = [mixer] * s.get("n_layers", 0) if isinstance(mixer, str) else [
        mixer[i % len(mixer)] for i in range(s.get("n_layers", 0))]
    if not s.get("steps") or "gqa" not in layers:
        return None
    length, heads, kv_heads = s["max_len"], s["n_heads"], s["n_kv_heads"]
    width = s["head_dim"]
    sessions = s["tokens_per_step"] // length
    pairs = sessions * heads * length * (length + 1) / 2
    forward_calls = 2 if s.get("remat") else 1
    ops = pairs * (forward_calls * 2 * 2 * width + 2 * 5 * width)
    q_rows, kv_rows = sessions * heads * length, sessions * kv_heads * length
    forward_bytes = (2 * q_rows + 2 * kv_rows) * width * 4.0   # q o | k v
    backward_bytes = (3 * q_rows + 4 * kv_rows) * width * 4.0
    nbytes = forward_calls * forward_bytes + backward_bytes
    calls = s["steps"] * layers.count("gqa")
    return calls * ops, calls * nbytes
