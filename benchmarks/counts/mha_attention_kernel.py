"""Count function of `mha_attention_kernel_roofline`: the contract of the
causal attention kernels (`flash_attention_pallas_*`) under plain
multi-head attention (one key/value head a query head, q/k = v = d /
heads) in a stack that runs `n_loops` times a step, for the calls one
traced train makes.

A session and head have L (L + 1) / 2 causal (query, key) pairs. A
forward call takes 2 (qk + v) operations a pair; the backward pass
2 (3 qk + 2 v) (five products: `s`, `dq`, `dk` over qk and `dp`, `dv`
over v), however many kernels it is split into and whatever they compute
twice. A layer AND PASS of a step makes one backward and one forward
call, two forward under `remat` (the block is recomputed). Bytes: q, k,
v (and `do` backward) of all heads read once, `o` (`dq`, `dk`, `dv`
backward) written once, at the model's float32."""


def counts(evidence, reader, n_events):
    s = evidence["shapes"]
    if not s.get("steps") or s.get("mixer") != "mha" \
            or s.get("positions") != "rope":
        return None
    length, heads = s["max_len"], s["n_heads"]
    width = s["d_model"] // heads
    sessions = s["tokens_per_step"] // length
    pairs = sessions * heads * length * (length + 1) / 2
    forward_calls = 2 if s.get("remat") else 1
    ops = pairs * (forward_calls * 2 * 2 * width + 2 * 5 * width)
    rows = sessions * heads * length
    forward_bytes = rows * 4 * width * 4.0                 # q k v | o
    backward_bytes = rows * 7 * width * 4.0      # q k v do | dq dk dv
    nbytes = forward_calls * forward_bytes + backward_bytes
    calls = s["steps"] * s["n_layers"] * s.get("n_loops", 1)
    return calls * ops, calls * nbytes
