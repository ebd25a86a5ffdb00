"""Count function of `window_attention_kernel_roofline`: the contract of
the banded attention kernels (`window_attention_pallas_*`) under grouped
query heads, for the calls one traced train makes.

A query at t sees the keys s with t - W < s <= t, so a session and query
head have W (W + 1) / 2 + (L - W) W (query, key) pairs inside the band
(W the window, the session's length where that is shorter). Operations
a pair and bytes a row are those `counts/gqa_attention_kernel.py` states
for the whole-causal kernels: a forward call takes 2 (qk + v) operations
a pair, the backward pass 2 (3 qk + 2 v), both widths `head_dim`; a
sliding-window ("swa") layer and step makes one backward and one forward
call, two forward under `remat`; q (and `do` backward) of the query heads
and k, v of the key/value heads read once, `o` (`dq`, `dk`, `dv`
backward) written once, at the model's float32. Whatever the blocks
compute outside the band (`window_attention_block_fill_pct` says how
much) is the implementation's, not the contract's."""


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    mixer, band = s.get("mixer"), s.get("swa")
    layers = [mixer] * s.get("n_layers", 0) if isinstance(mixer, str) else [
        mixer[i % len(mixer)] for i in range(s.get("n_layers", 0))]
    if not s.get("steps") or not band or "swa" not in layers:
        return None
    length, heads, kv_heads = s["max_len"], band["heads"], s["n_kv_heads"]
    width, window = s["head_dim"], min(band["window"], s["max_len"])
    sessions = s["tokens_per_step"] // length
    pairs = sessions * heads * (window * (window + 1) / 2
                                + (length - window) * window)
    forward_calls = 2 if s.get("remat") else 1
    ops = pairs * (forward_calls * 2 * 2 * width + 2 * 5 * width)
    q_rows, kv_rows = sessions * heads * length, sessions * kv_heads * length
    forward_bytes = (2 * q_rows + 2 * kv_rows) * width * 4.0   # q o | k v
    backward_bytes = (3 * q_rows + 4 * kv_rows) * width * 4.0
    nbytes = forward_calls * forward_bytes + backward_bytes
    calls = s["steps"] * layers.count("swa")
    return calls * ops, calls * nbytes
