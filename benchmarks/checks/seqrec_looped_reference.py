"""Plain reference of the `sessionrec` template's sequence model under a
layer spec of a looped decoder: a stack of sandwich-normed
multi-head-attention layers run `n_loops` times over the same weights,
the last norm after every pass, an exit gate after each pass and the
loss an expectation over the passes' exits (the decoder of Ouro-2.6B,
https://huggingface.co/ByteDance/Ouro-2.6B, `config.json`:
`total_ut_steps` 4, `early_exit_threshold` 1; the family's public
description of the looped language model and its entropy-regularised
objective): forward pass, loss and, through `jax.grad` of that loss,
gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one sequence at a time: the
passes a Python loop over the same weight dict; softmax attention by the
full score matrix of a head, a block of queries at a time so that it
fits; the head once a pass; no kernel, no cache, nothing of
`predictionio_tpu`. `recompute` wraps each layer, each head's block of
queries and each block of the loss in `jax.checkpoint`, which changes
what is kept and not what is computed: with it the published widths at
8,192 positions fit one chip.

The equations, for one sequence (rms(x; w) = x / sqrt(mean(x^2) + eps)
w; no bias but the gate's):

  h_0 = E[x]. For pass r = 1..R: u = h_(r-1); for layer i = 1..N, the
  SAME weights in every pass:
    u = u + rms(Attn_i(rms(u; a_i)); b_i)
    u = u + rms(FFN_i(rms(u; c_i)); d_i)
  h_r = rms(u; w_f): the model's last norm, after every pass, and what
  pass r + 1 starts from.

  Attn: [q | k | v] = x W_qkv (H heads of d / H each, one key/value head
  a query head); rotary positions on the whole head of q and k (halves
  pairing); causal softmax attention at (d / H)^-0.5, padding keys
  masked; y = att W_o.  FFN(x) = W_2 (silu(W_1 x) * W_3 x).

  logits_r = h_r W_head (untied). The gate lambda_r = sigmoid(h_r . w_g
  + b_g) a position. Exit distribution: p_1 = lambda_1, p_r = lambda_r
  prod_(j<r) (1 - lambda_j) for 1 < r < R, p_R = prod_(j<R) (1 -
  lambda_j); lambda_R is not read. Loss a target position: sum_r p_r
  CE_r - beta H(p), H(p) = -sum_r p_r log p_r, CE_r the next-item
  cross-entropy of logits_r; the mean over the targets that are no
  padding.

The weights are a release's (`SeqRecModel.params`), by name:
  emb [V, d]; head [d, V]; ln_f {scale}; exit_gate {w [d], b []};
  layers[i]: ln1, post1, ln2, post2 {scale}; wqkv [d, 3 d]; wo [d, d];
  w_gate, w_up [d, w], w_down [w, d].

Departures from the published description (the configuration's
`assumed`):
  * beta and the uniform prior are the family's published objective;
    the config has no coefficient: `exit_entropy_beta`;
  * the last norm lies inside the loop and its output is fed on; the
    gate is one column with a bias on the normed state, trained jointly
    with the model (the family's second, gate-only stage is not run);
  * the fused projection's columns are [q | k | v], each together;
    rotary positions pair dimension i with i + D/2 ("halves");
  * `precision="int8"` is the control, not the model: the operands of
    every matrix product rounded to 8 bits (symmetric, a scale a row of
    the left and a column of the right operand), in the backward pass
    too; the gate, p and the entropy stay float32 elementwise, as the
    configuration's `precision` states;
  * the fault controls are specs of their own: `n_loops` one fewer (a
    pass left out), `post_norm` False, `exit_entropy_beta` 0, and
    `last_pass_only` (every pass but the last outside the gradient: what
    the earlier passes send the shared weights is dropped);
  * `unshared`: the layer list holds `n_loops` x N layers and pass r
    takes its own N: the twin a test holds the shared stack to.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# adamw's first step in numpy is the first sequence reference's; the
# product at the spec's precision (int8: operands rounded, the backward
# pass's too), the RMS norm, rotary positions and the SwiGLU are the
# convolution reference's, which read nothing of a spec but `precision`
from benchmarks.checks.seqrec_conv_reference import mm, rms, rope, swiglu
from benchmarks.checks.seqrec_reference import adamw_first_update

#: rows of queries a head takes at a time, and of the loss, under
#: `recompute`
ROWS = 2048


@dataclasses.dataclass(frozen=True)
class Spec:
    n_heads: int
    rope_theta: float
    norm_eps: float
    n_loops: int
    post_norm: bool
    exit_entropy_beta: float
    learning_rate: float
    precision: str = "highest"       # or "int8", the control
    recompute: bool = False
    last_pass_only: bool = False     # a control
    unshared: bool = False           # the twin of a test

    @classmethod
    def of(cls, algorithm_params: dict, **over) -> "Spec":
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in algorithm_params.items() if k in names}
        return cls(**{**kept, **over})


def attention(layer, x, key_ok, spec: Spec):
    """x [L, d] (normed) of one sequence, key_ok [L] -> [L, d]."""
    l, d = x.shape
    h, hd = spec.n_heads, d // spec.n_heads
    qkv = mm(x, layer["wqkv"], spec)
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(l, h, hd) for i in range(3))
    turn = jax.vmap(lambda t: rope(t, spec.rope_theta, hd), in_axes=1,
                    out_axes=1)
    q, k = turn(q), turn(k)
    rows = ROWS if spec.recompute and l % ROWS == 0 else l
    at = jnp.arange(l)

    def queries(head, first):
        """Rows [first, first + rows) of one head against every key."""
        q_b = jax.lax.dynamic_slice_in_dim(q, first, rows, 0)[:, head]
        allowed = (at[None, :] <= first + jnp.arange(rows)[:, None]) \
            & key_ok[None, :]
        s = mm(q_b, k[:, head].T, spec) / np.sqrt(hd)
        top = jnp.max(jnp.where(allowed, s, -jnp.inf), axis=-1, keepdims=True)
        w = jnp.where(allowed, jnp.exp(s - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(w, axis=-1, keepdims=True)
        # a padding query before the first real key sees nothing: output 0
        return mm(w / jnp.where(total == 0, 1.0, total), v[:, head], spec)

    if spec.recompute:
        queries = jax.checkpoint(queries)
    heads, firsts = np.meshgrid(np.arange(h), np.arange(0, l, rows),
                                indexing="ij")
    out = jax.lax.map(lambda hf: queries(hf[0], hf[1]),
                      (jnp.asarray(heads.ravel()),
                       jnp.asarray(firsts.ravel())))     # [H blocks, rows, hd]
    att = out.reshape(h, l, hd).swapaxes(0, 1).reshape(l, d)
    return mm(att, layer["wo"], spec)


def pass_states(params, seq, spec: Spec):
    """One session's [L] item codes (0 = padding) -> the hidden states
    h_1 .. h_R, [L, d] each (0 at padding): the last norm's output after
    every pass."""
    key_ok = seq != 0
    eps = spec.norm_eps

    def joined(u, y, layer, name):
        return u + (rms(y, layer[name]["scale"], eps) if spec.post_norm
                    else y)

    def block(layer, u):
        u = joined(u, attention(layer, rms(u, layer["ln1"]["scale"], eps),
                                key_ok, spec), layer, "post1")
        return joined(u, swiglu(layer, rms(u, layer["ln2"]["scale"], eps),
                                spec), layer, "post2")

    if spec.recompute:
        block = jax.checkpoint(block)
    layers = params["layers"]
    n = len(layers) // spec.n_loops if spec.unshared else len(layers)
    h, states = params["emb"][seq], []
    for r in range(spec.n_loops):
        first = r * n if spec.unshared else 0
        u = h
        for layer in layers[first:first + n]:        # the same weights
            u = block(layer, u)
        h = rms(u, params["ln_f"]["scale"], eps)
        if spec.last_pass_only and r < spec.n_loops - 1:
            h = jax.lax.stop_gradient(h)
        states.append(jnp.where(key_ok[:, None], h, 0.0))
    return states


def exit_log_probabilities(z):
    """Gate logits z [R, L] -> log p [R, L], the passes one by one."""
    log_stay = jnp.zeros_like(z[0])          # log prod_(j<r) (1 - lambda_j)
    out = []
    for r in range(z.shape[0] - 1):
        out.append(jnp.log(jax.nn.sigmoid(z[r])) + log_stay)
        log_stay = log_stay + jnp.log(1.0 - jax.nn.sigmoid(z[r]))
    return jnp.stack(out + [log_stay])


def sequence_loss(params, seq, target, spec: Spec):
    """One session: seq, target [L] item codes (0 = padding). -> (the
    loss summed over the real targets, [R] each pass's cross-entropy
    summed over them, [R] each pass's exit probability summed over
    them)."""
    states = pass_states(params, seq, spec)
    l = seq.shape[0]
    rows = ROWS if spec.recompute and l % ROWS == 0 else l

    def nll(hid_tgt):
        hid, tgt = hid_tgt
        logits = mm(hid, params["head"], spec)
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        return -jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    if spec.recompute:
        nll = jax.checkpoint(nll)
    ce = jnp.stack([jax.lax.map(nll, (
        hidden.reshape(l // rows, rows, -1),
        target.reshape(l // rows, rows))).reshape(l)
        for hidden in states])                                   # [R, L]
    gate = params["exit_gate"]
    z = jnp.stack([jnp.sum(hidden * gate["w"], axis=-1) + gate["b"]
                   for hidden in states])
    logp = exit_log_probabilities(z)
    p = jnp.exp(logp)
    real = target > 0
    per_target = jnp.sum(p * ce, axis=0) \
        + spec.exit_entropy_beta * jnp.sum(p * logp, axis=0)
    return (jnp.sum(jnp.where(real, per_target, 0.0)),
            jnp.sum(jnp.where(real, ce, 0.0), axis=1),
            jnp.sum(jnp.where(real, p, 0.0), axis=1))


def loss_and_grads(params, seqs, targets, spec: Spec):
    """A batch [B, L]: the loss over the batch's real targets. -> (loss,
    gradients as numpy, {"loop_loss": [R] each pass's own cross-entropy,
    "exit_share": [R] the mean probability of leaving at each pass}).
    One sequence after another; losses and gradients add up, on the
    host."""
    seqs, targets = np.asarray(seqs), np.asarray(targets)
    n_real = max(int((targets > 0).sum()), 1)
    params = jax.tree.map(jnp.asarray, params)     # once, not a sequence

    def part(params, seq, target):
        loss, ce, share = sequence_loss(params, seq, target, spec)
        return loss / n_real, (ce / n_real, share / n_real)

    one = jax.jit(jax.value_and_grad(part, has_aux=True))
    loss, grads, ce, share = 0.0, None, 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for seq, target in zip(seqs, targets):
            (part_loss, (part_ce, part_share)), g = one(params, seq, target)
            g = jax.tree.map(np.asarray, g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
            loss = loss + float(part_loss)
            ce, share = ce + np.asarray(part_ce), share + np.asarray(
                part_share)
    return loss, grads, {"loop_loss": ce, "exit_share": share}


def first_update_norms(params, grads, spec: Spec) -> Dict[str, float]:
    """By parameter group, the norm of theta_1 - theta_0: adamw's first
    step from the gradients `grads` at theta_0 = `params`. Leaf by leaf,
    on the host."""
    squares: Dict[str, float] = {}
    for (path, theta), g in zip(jax.tree_util.tree_leaves_with_path(params),
                                jax.tree.leaves(grads)):
        u = adamw_first_update(theta, g, spec.learning_rate)
        name = grad_group(path)
        squares[name] = squares.get(name, 0.0) + float(
            np.sum(np.square(u), dtype=np.float64))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}


_PARTS = {
    **dict.fromkeys(("wqkv", "wo"), "attention"),
    **dict.fromkeys(("w_gate", "w_up", "w_down"), "ffn"),
    **dict.fromkeys(("ln1", "ln2", "post1", "post2"), "norms")}


def grad_group(path) -> str:
    """The group a parameter is counted in: tables, head and gate by
    name, a layer's parameters by layer and part."""
    names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    if names[0] != "layers":
        return {"emb": "embedding", "ln_f": "final_norm"}.get(names[0],
                                                              names[0])
    return f"layer{names[1]}.{_PARTS[names[2]]}"


def group_norms(tree) -> Dict[str, float]:
    squares: Dict[str, float] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = grad_group(path)
        squares[name] = squares.get(name, 0.0) + float(
            jnp.sum(jnp.asarray(leaf, jnp.float32) ** 2))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}
