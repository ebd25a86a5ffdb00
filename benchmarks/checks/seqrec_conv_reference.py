"""Plain reference of the `sessionrec` template's sequence model under a
layer spec of gated short convolutions and ungated grouped-query
attention, a leading dense SwiGLU layer, then sigmoid-routed experts
behind a selection bias with no shared expert, and a tied head (the
decoder of LFM2-24B-A2B,
https://huggingface.co/LiquidAI/LFM2-24B-A2B, `config.json`, and the
family's public modelling code): forward pass, loss and, through
`jax.grad` of that loss, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one sequence at a time: the
convolution tap by tap; softmax attention by the full score matrix of a
head, a block of queries at a time so that it fits; the experts as a loop
over the experts held here, each over every token under a boolean mask;
no kernel, no cache, nothing of `predictionio_tpu`. The loops are
`lax.scan` / `lax.map` (one body, run in turn). `recompute` wraps each
layer, each head's block of queries, each expert and each block of the
loss in `jax.checkpoint`, which changes what is kept and not what is
computed: with it the published widths at 32,768 positions fit one chip.

The equations, for normed x [L, d] of one sequence (rms(x; w) = x /
sqrt(mean(x^2) + eps) w):

  block i: h += operator_i(rms(h; w1)); h += ffn_i(rms(h; w2)); at the
  end rms(h; wf). No bias anywhere.

  gated short convolution ("conv"): x of a padding position set to 0;
  [B | C | u] = x W_in (three streams of d); a_t = B_t u_t; c_t =
  sum_j w_conv[j] a_{t - (K - 1) + j} a channel, zeros before the
  sequence, no activation; y = (C c) W_out.

  attention ("gqa", no output gate): q = x W_q, k = x W_k, v = x W_v;
  q = rms(q; w_qn), k = rms(k; w_kn) over the head width (one weight for
  all heads); rotary positions on the leading rotary_dim of q and k
  (halves pairing; here the whole head); causal softmax attention at
  head_dim^-0.5, key/value head j serving the query heads [j r,
  (j + 1) r); y = att W_o.

  feed-forward: below `first_dense_layers` W_2 (silu(W_1 x) W_3 x); then
  the expert layer: s = sigmoid(x W_r) over all the router's outputs,
  the k largest of s + b chosen (b the selection bias, outside the
  gradient), gates s_e / (sum of the chosen s + router_norm_eps) times
  routed_scaling_factor; y = sum over the held chosen experts of
  gate_e SwiGLU_e(x). No shared expert.

  head: the item embeddings transposed.

It is given the same share as the program: the router scores all
`n_routed_experts`, the experts `held_experts` = [first, end) add their
part, what the absent ones would add is left out and that partial result
goes on to the next layer; the vocabulary is the slice it is given.

The weights are a release's (`SeqRecModel.params`), by name:
  emb [V, d] (the head too); ln_f {scale}; layers[i]: ln1, ln2 {scale};
  a conv layer's conv_in [d, 3 d], conv_taps [K, d], conv_out [d, d]; a
  gqa layer's wq [d, H hd], wk, wv [d, Hkv hd], q_norm, k_norm {scale
  [hd]}, wo [H hd, d]; a dense layer's w_gate, w_up [d, w], w_down
  [w, d]; an expert layer's router [d, E], router_bias [E], experts
  {w_gate, w_up [held, d, w], w_down [held, w, d]}.

Departures from the published description:
  * the head is tied to the embeddings: the catalog's `config` drops the
    key, the family's checkpoints tie them;
  * the columns of W_in are [B | C | u], each stream together;
  * rotary positions pair dimension i with i + D/2 ("halves"), the
    checkpoint's own `rotate_half`;
  * the selection bias's rate is not in the config: `bias_update_rate`
    0.001 (the Kimi file's), no balance loss;
  * `precision="int8"` is the control, not the model: the operands of
    every matrix product the configuration computes in one bfloat16 pass
    rounded to 8 bits (symmetric, a scale a row of the left and a column
    of the right operand), in the backward pass too; the router's
    projection stays float32, as the configuration's `precision` states;
  * `conv_gate`, `qk_norm` and `held_experts` make the fault controls:
    the convolution's first gate B left out (a = u), the norms of
    queries and keys left out, a held expert left out.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the int8 product (operands rounded, the backward pass's too), adamw's
# first step and the bias's step in numpy are the other sequence
# reference's
from benchmarks.checks.seqrec_reference import (
    _mm_int8, adamw_first_update, bias_after_step,
)


@dataclasses.dataclass(frozen=True)
class Spec:
    mixer: Tuple[str, ...]
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    norm_eps: float
    conv_kernel: int
    first_dense_layers: int
    n_routed_experts: int
    held_experts: Tuple[int, int]
    experts_per_token: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    router_norm_eps: float
    bias_update_rate: float
    learning_rate: float
    precision: str = "highest"       # or "int8", the control
    recompute: bool = False
    conv_gate: bool = True           # a control: False leaves B out
    qk_norm: bool = True             # a control: False leaves them out

    @classmethod
    def of(cls, algorithm_params: dict, **over) -> "Spec":
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in algorithm_params.items() if k in names}
        kept["held_experts"] = tuple(kept["held_experts"])
        mixer = kept["mixer"]
        kept["mixer"] = (mixer,) if isinstance(mixer, str) else tuple(mixer)
        return cls(**{**kept, **over})

    def mixer_of(self, layer: int) -> str:
        return self.mixer[layer % len(self.mixer)]


def mm(a, b, spec: Spec):
    """a [L, n] @ b [n, m], at the spec's precision."""
    return _mm_int8(a, b) if spec.precision == "int8" else a @ b


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta, rotary_dim):
    """x [L, D] at positions 0..L-1: the leading `rotary_dim` dimensions
    rotate, halves pairing within them; the others pass."""
    half = rotary_dim // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    x1, x2, rest = x[:, :half], x[:, half:rotary_dim], x[:, rotary_dim:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang), rest],
                           axis=-1)


def swiglu(w, x, spec: Spec):
    return mm(silu(mm(x, w["w_gate"], spec)) * mm(x, w["w_up"], spec),
              w["w_down"], spec)


def short_conv(layer, x, key_ok, spec: Spec):
    """x [L, d] (normed) of one sequence, key_ok [L] -> [L, d]."""
    l, d = x.shape
    x = jnp.where(key_ok[:, None], x, 0.0)
    bcu = mm(x, layer["conv_in"], spec)
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    a = b * u if spec.conv_gate else u
    taps = spec.conv_kernel
    before = jnp.concatenate([jnp.zeros((taps - 1, d)), a], axis=0)
    mixed = sum(layer["conv_taps"][j] * before[j:j + l] for j in range(taps))
    return mm(c * mixed, layer["conv_out"], spec)


def attention(layer, x, key_ok, spec: Spec):
    """x [L, d] (normed) of one sequence, key_ok [L] -> [L, d]."""
    l = x.shape[0]
    h, hkv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    q = mm(x, layer["wq"], spec).reshape(l, h, hd)
    k = mm(x, layer["wk"], spec).reshape(l, hkv, hd)
    v = mm(x, layer["wv"], spec).reshape(l, hkv, hd)
    if spec.qk_norm:
        q = rms(q, layer["q_norm"]["scale"], spec.norm_eps)
        k = rms(k, layer["k_norm"]["scale"], spec.norm_eps)
    turn = jax.vmap(lambda t: rope(t, spec.rope_theta, spec.rotary_dim),
                    in_axes=1, out_axes=1)
    q, k = turn(q), turn(k)
    rows = 2048 if spec.recompute and l % 2048 == 0 else l
    at = jnp.arange(l)

    def queries(head, first):
        """Rows [first, first + rows) of one head against every key."""
        q_b = jax.lax.dynamic_slice_in_dim(q, first, rows, 0)[:, head]
        k_h, v_h = k[:, head // (h // hkv)], v[:, head // (h // hkv)]
        allowed = (at[None, :] <= first + jnp.arange(rows)[:, None]) \
            & key_ok[None, :]
        s = mm(q_b, k_h.T, spec) / np.sqrt(hd)
        top = jnp.max(jnp.where(allowed, s, -jnp.inf), axis=-1, keepdims=True)
        w = jnp.where(allowed, jnp.exp(s - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(w, axis=-1, keepdims=True)
        # a padding query before the first real key sees nothing: output 0
        return mm(w / jnp.where(total == 0, 1.0, total), v_h, spec)

    if spec.recompute:
        queries = jax.checkpoint(queries)
    heads, firsts = np.meshgrid(np.arange(h), np.arange(0, l, rows),
                                indexing="ij")
    out = jax.lax.map(lambda hf: queries(hf[0], hf[1]),
                      (jnp.asarray(heads.ravel()),
                       jnp.asarray(firsts.ravel())))     # [H blocks, rows, hd]
    att = out.reshape(h, l, hd).swapaxes(0, 1).reshape(l, h * hd)
    return mm(att, layer["wo"], spec)


def expert_layer(layer, x, spec: Spec):
    """x [L, d] (normed) of one sequence -> (y [L, d], the tokens routed
    to each of the router's experts [E])."""
    k = spec.experts_per_token
    lo, hi = spec.held_experts
    with jax.default_matmul_precision("highest"):
        # the router is float32 whatever the model's precision
        scores = jax.nn.sigmoid(x @ layer["router"])               # [L, E]
    chosen = jnp.argsort(-(scores + layer["router_bias"]), axis=-1,
                         stable=True)[:, :k]                       # [L, k]
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(True)         # [L, E]
    gates = jnp.where(picked, scores, 0.0)
    if spec.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + spec.router_norm_eps)
    gates = gates * spec.routed_scaling_factor

    def part(held):
        weights, mine, gate = held
        return jnp.where(mine[:, None], gate[:, None]
                         * swiglu(weights, x, spec), 0.0)

    if spec.recompute:
        part = jax.checkpoint(part)
    y, _ = jax.lax.scan(                 # the experts held here, one by one
        lambda y, held: (y + part(held), None), jnp.zeros_like(x), (
            jax.tree.map(lambda w: w[:hi - lo], layer["experts"]),
            picked[:, lo:hi].T, gates[:, lo:hi].T))
    return y, picked.sum(axis=0)


def hidden_states(params, seq, spec: Spec):
    """One session's [L] item codes (0 = padding) -> ([L, d] final hidden
    states, 0 at padding; [expert layers, E] tokens to each expert)."""
    key_ok = seq != 0
    h = params["emb"][seq]

    def block(layer, h, i):
        x = rms(h, layer["ln1"]["scale"], spec.norm_eps)
        mixer = short_conv if spec.mixer_of(i) == "conv" else attention
        h = h + mixer(layer, x, key_ok, spec)
        x = rms(h, layer["ln2"]["scale"], spec.norm_eps)
        if i < spec.first_dense_layers:
            return h + swiglu(layer, x, spec), None
        y, load = expert_layer(layer, x, spec)
        return h + y, load

    if spec.recompute:
        block = jax.checkpoint(block, static_argnums=2)
    loads = []
    for i, layer in enumerate(params["layers"]):
        h, load = block(layer, h, i)
        if load is not None:
            loads.append(load)
    return jnp.where(key_ok[:, None], rms(
        h, params["ln_f"]["scale"], spec.norm_eps), 0.0), jnp.stack(loads)


def sequence_loss(params, seq, target, spec: Spec):
    """One session: seq, target [L] item codes (0 = padding). -> (summed
    next-item cross-entropy over the real targets, [expert layers, E]
    tokens to each expert)."""
    hidden, loads = hidden_states(params, seq, spec)
    l = seq.shape[0]
    rows = 2048 if spec.recompute and l % 2048 == 0 else l
    head = params["emb"].T                                  # tied

    def nll(hid_tgt):
        hid, tgt = hid_tgt
        logits = mm(hid, head, spec)
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        picked = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(tgt > 0, picked, 0.0))

    if spec.recompute:
        nll = jax.checkpoint(nll)
    return jnp.sum(jax.lax.map(nll, (
        hidden.reshape(l // rows, rows, -1),
        target.reshape(l // rows, rows)))), loads


def loss_and_grads(params, seqs, targets, spec: Spec):
    """A batch [B, L]: loss = cross-entropy over the batch's real
    targets. -> (loss, gradients as numpy, [expert layers, E] tokens to
    each expert over the batch). One sequence after another; losses and
    gradients add up, on the host."""
    seqs, targets = np.asarray(seqs), np.asarray(targets)
    n_real = max(int((targets > 0).sum()), 1)
    params = jax.tree.map(jnp.asarray, params)     # once, not a sequence

    def part(params, seq, target):
        ce, load = sequence_loss(params, seq, target, spec)
        return ce / n_real, load

    one = jax.jit(jax.value_and_grad(part, has_aux=True))
    loss, grads, load = 0.0, None, 0
    with jax.default_matmul_precision("highest"):
        for seq, target in zip(seqs, targets):
            (part_loss, part_load), g = one(params, seq, target)
            g = jax.tree.map(np.asarray, g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
            loss, load = loss + float(part_loss), load + np.asarray(part_load)
    return loss, grads, load


def first_update_norms(params, grads, load, spec: Spec) -> Dict[str, float]:
    """By parameter group, the norm of theta_1 - theta_0: adamw's first
    step from the gradients `grads` at theta_0 = `params`, and for a
    router's selection bias, which adamw leaves alone, its own update
    from the layer's `load` [expert layers, E]. Leaf by leaf, on the
    host."""
    squares: Dict[str, float] = {}
    for (path, theta), g in zip(jax.tree_util.tree_leaves_with_path(params),
                                jax.tree.leaves(grads)):
        if path[-1].key == "router_bias":
            u = bias_after_step(np.zeros(len(theta)), load[
                path[1].idx - spec.first_dense_layers], spec.bias_update_rate)
        else:
            u = adamw_first_update(theta, g, spec.learning_rate)
        name = grad_group(path)
        squares[name] = squares.get(name, 0.0) + float(
            np.sum(np.square(u), dtype=np.float64))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}


_PARTS = {
    **dict.fromkeys(("conv_in", "conv_taps", "conv_out"), "short_conv"),
    **dict.fromkeys(("wq", "wk", "wv", "q_norm", "k_norm", "wo"),
                    "attention"),
    **dict.fromkeys(("w_gate", "w_up", "w_down"), "ffn"),
    "router": "router", "router_bias": "router", "experts": "experts",
    "ln1": "norms", "ln2": "norms"}


def grad_group(path) -> str:
    """The group a parameter is counted in: the table by name, a layer's
    parameters by layer and part."""
    names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    if names[0] != "layers":
        return {"emb": "embedding", "ln_f": "final_norm"}[names[0]]
    return f"layer{names[1]}.{_PARTS[names[2]]}"


def group_norms(tree) -> Dict[str, float]:
    squares: Dict[str, float] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = grad_group(path)
        squares[name] = squares.get(name, 0.0) + float(
            jnp.sum(jnp.asarray(leaf, jnp.float32) ** 2))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}
