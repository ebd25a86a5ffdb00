"""Count function of `packed_attention_kernel_roofline`: the contract of
causal attention over grouped query heads on PACKED rows, for the calls
one traced train makes, whatever implements it (here the whole-causal
kernels, `flash_attention_pallas_*`, told each position's session).

A query sees the keys of its own session up to itself, so a session of n
positions and a query head have n (n + 1) / 2 (query, key) pairs, and a
train's rows the sum of that over the sessions laid into them
(`shapes["session_positions"]`: checks/seqrec_packed_step.shapes);
nothing a block computes outside a session is counted, nor a row's
padding. Operations a pair and bytes a position are those
counts/gqa_attention_kernel.py states: a forward call takes 2 (qk + v)
operations a pair, the backward pass 2 (3 qk + 2 v), both widths
`head_dim`; a full-attention ("gqa") layer makes one backward and one
forward call a step, two forward under `remat`; q (and `do` backward) of
the query heads and k, v of the key/value heads read once, `o` (`dq`,
`dk`, `dv` backward) written once, at the model's float32, for the
positions that lie in a session."""


def session_pairs(positions, window=None):
    """The (query, key) pairs inside the sessions: causal, under a
    `window` (t - window < s <= t) or none."""
    total = 0
    for n in positions:
        near = n if window is None else min(n, window)
        total += near * (near + 1) // 2 + (n - near) * near
    return total


def layers_of(shapes):
    mixer = shapes.get("mixer")
    n = shapes.get("n_layers", 0)
    return [mixer] * n if isinstance(mixer, str) else [
        mixer[i % len(mixer)] for i in range(n)]


def kernel_counts(shapes, kind, heads, window=None):
    """(operations, bytes) of a train's calls of the layers of `kind`."""
    positions = shapes.get("session_positions")
    layers = layers_of(shapes).count(kind)
    if not shapes.get("steps") or not positions or not layers:
        return None
    kv_heads, width = shapes["n_kv_heads"], shapes["head_dim"]
    pairs = heads * session_pairs(positions, window)
    forward_calls = 2 if shapes.get("remat") else 1
    ops = pairs * (forward_calls * 2 * 2 * width + 2 * 5 * width)
    q_rows, kv_rows = heads * sum(positions), kv_heads * sum(positions)
    forward_bytes = (2 * q_rows + 2 * kv_rows) * width * 4.0   # q o | k v
    backward_bytes = (3 * q_rows + 4 * kv_rows) * width * 4.0
    nbytes = forward_calls * forward_bytes + backward_bytes
    return layers * ops, layers * nbytes


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    return kernel_counts(s, "gqa", s.get("n_heads", 0))
