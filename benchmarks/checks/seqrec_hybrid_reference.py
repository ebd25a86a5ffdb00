"""Plain reference of the `sessionrec` template's sequence model under a
layer spec of gated-delta-rule linear attention and gated grouped-query
attention in one period, softmax-routed experts and a gated shared expert
(the decoder of Qwen3-Next-80B-A3B,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, `config.json`;
the delta rule's equations: Gated DeltaNet, arXiv:2412.06464): forward
pass, loss and, through `jax.grad` of that loss, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one sequence at a time: the
delta rule position by position, a state of [dk, dv] a head; softmax
attention by the full score matrix of a head, a block of queries at a
time so that it fits; the experts as a loop over the experts held here,
each over every token under a boolean mask; no kernel, no chunked form,
no cache, nothing of `predictionio_tpu`. The loops are `lax.scan` /
`lax.map` (one body, run in turn). `recompute` wraps each layer, each
head's block of queries, each stretch of 128 positions of the rule, each
expert and each block of the loss in `jax.checkpoint`, which changes what
is kept and not what is computed: with it the published widths at 16,384
positions fit one chip.

The equations, for normed x [L, d] of one sequence (zn(x; w) = x /
sqrt(mean(x^2) + eps) (1 + w), the zero-centred RMS norm):

  block i of a period: h += mixer_i(zn(h; w1)); h += experts(zn(h; w2));
  at the end zn(h; wf).

  linear attention ("gdn"): x of a padding position set to 0;
  [q | k | v | z] = x W_qkvz, [b | a] = x W_ba; c = [q | k | v],
  u_t = silu(sum_j w_conv[j] c_{t - (K - 1) + j}) a channel, zeros before
  the sequence; q, k, v from u; a head's q = q / |q| dk^-0.5, k = k / |k|
  (x rsqrt(sum x^2 + 1e-6)); key head j serves the value heads [j r,
  (j + 1) r); beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias) a
  value head; S_0 = 0, S' = exp(g_t) S_{t-1}, S_t = S' + k_t (beta_t
  (v_t - S'^T k_t))^T, o_t = S_t^T q_t; y = concat_h(o_t / sqrt(mean
  (o_t^2) + eps) w_o_norm silu(z_t)) W_out.

  gated attention ("gqa"): [q | gate] = x W_q, k = x W_k, v = x W_v;
  q = zn(q; w_qn), k = zn(k; w_kn) over the head width; rotary positions
  on the leading rotary_dim of q and k (halves pairing within them);
  causal softmax attention at head_dim^-0.5, key/value head j serving
  the query heads [j r, (j + 1) r); y = (att sigmoid(gate)) W_o.

  experts: p = softmax(x W_r) over all the router's outputs, the k
  largest chosen, gates p_e / sum of the chosen; y = sum over the held
  chosen experts of gate_e SwiGLU_e(x) + sigmoid(x w_sg) SwiGLU_shared(x).

It is given the same share as the program: the router scores all
`n_routed_experts`, the experts `held_experts` = [first, end) add their
part, what the absent ones would add is left out and that partial result
goes on to the next layer; the vocabulary is the slice it is given.

The weights are a release's (`SeqRecModel.params`), by name:
  emb [V, d]; head [d, V]; ln_f {scale}; layers[i]: ln1, ln2 {scale};
  a gdn layer's w_qkvz [d, 2 Hk dk + 2 Hv dv], w_ba [d, 2 Hv], conv
  [K, 2 Hk dk + Hv dv], A_log, dt_bias [Hv], o_norm {scale [dv]}, w_out
  [Hv dv, d]; a gqa layer's wq_gate [d, 2 H hd], wk, wv [d, Hkv hd],
  q_norm, k_norm {scale [hd]}, wo [H hd, d]; router [d, E], experts
  {w_gate, w_up [held, d, w], w_down [held, w, d]}, shared {w_gate,
  w_up, w_down}, shared_gate [d, 1]. A release's `router_bias` (a
  selection bias the program keeps at 0 under this spec) is not read.

Departures from the published description:
  * the columns of W_qkvz and W_ba are [q | k | v | z] and [b | a], all
    of one kind together, and W_q's are [q | gate]: fixed permutations of
    the checkpoint's layouts grouped by key head and by head; with seeded
    weights either is the model;
  * the multi-token-prediction layer the model card mentions is not in
    `config.json` and is left out;
  * no balance term: the config names no coefficient for one;
  * `precision="int8"` is the control, not the model: the operands of
    every matrix product the configuration computes in one bfloat16 pass
    rounded to 8 bits (symmetric, a scale a row of the left and a column
    of the right operand), in the backward pass too; the router's and
    the decay gates' projections stay float32, as the configuration's
    `precision` states them;
  * `zero_decay_layer`, `attention_gate` and `held_experts` make the
    fault controls: one linear layer's decay left out (g = 0), the
    attention's output gate left out, a held expert left out.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the int8 product (operands rounded, the backward pass's too) and
# adamw's first step in numpy are the other sequence reference's
from benchmarks.checks.seqrec_reference import _mm_int8, adamw_first_update


@dataclasses.dataclass(frozen=True)
class Spec:
    mixer: Tuple[str, ...]
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    norm_eps: float
    linear_key_heads: int
    linear_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel: int
    n_routed_experts: int
    held_experts: Tuple[int, int]
    experts_per_token: int
    learning_rate: float
    precision: str = "highest"       # or "int8", the control
    recompute: bool = False
    zero_decay_layer: int = -1       # a control: this layer's g = 0
    attention_gate: bool = True      # a control: False leaves it out

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


def zn(x, w, eps):
    """The zero-centred RMS norm over the last axis."""
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


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


def delta_rule(q, k, v, g, beta, spec: Spec):
    """One head, position by position: q, k [L, dk], v [L, dv], g, beta
    [L] -> o [L, dv], from S_0 = 0."""
    l = q.shape[0]

    def position(s, x):
        q_t, k_t, v_t, g_t, beta_t = x
        s = jnp.exp(g_t) * s
        held = mm(k_t[None, :], s, spec)[0]                 # S'^T k_t
        s = s + mm(k_t[:, None], (beta_t * (v_t - held))[None, :], spec)
        return s, mm(q_t[None, :], s, spec)[0]              # S_t^T q_t

    s0 = jnp.zeros((q.shape[1], v.shape[1]), jnp.float32)
    stretch = 128
    if not spec.recompute or l % stretch:
        return jax.lax.scan(position, s0, (q, k, v, g, beta))[1]

    @jax.checkpoint
    def positions(s, xs):
        return jax.lax.scan(position, s, xs)

    _, o = jax.lax.scan(positions, s0, jax.tree.map(
        lambda t: t.reshape(l // stretch, stretch, *t.shape[1:]),
        (q, k, v, g, beta)))
    return o.reshape(l, -1)


def linear_attention(layer, x, key_ok, spec: Spec, decay: bool = True):
    """x [L, d] (normed) of one sequence, key_ok [L] -> [L, d]."""
    l = x.shape[0]
    hk, hv = spec.linear_key_heads, spec.linear_value_heads
    dk, dv = spec.linear_key_head_dim, spec.linear_value_head_dim
    taps = spec.linear_conv_kernel
    x = jnp.where(key_ok[:, None], x, 0.0)
    qkvz = mm(x, layer["w_qkvz"], spec)
    c, z = qkvz[:, :2 * hk * dk + hv * dv], qkvz[:, 2 * hk * dk + hv * dv:]
    with jax.default_matmul_precision("highest"):
        # the gates' projection is float32 whatever the model's precision
        ba = x @ layer["w_ba"]
    beta = jax.nn.sigmoid(ba[:, :hv])                           # [L, Hv]
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        ba[:, hv:] + layer["dt_bias"])
    if not decay:
        g = jnp.zeros_like(g)
    before = jnp.concatenate([jnp.zeros((taps - 1, c.shape[1])), c], axis=0)
    u = silu(sum(layer["conv"][j] * before[j:j + l] for j in range(taps)))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q = unit(u[:, :hk * dk].reshape(l, hk, dk)) * dk ** -0.5
    k = unit(u[:, hk * dk:2 * hk * dk].reshape(l, hk, dk))
    v = u[:, 2 * hk * dk:].reshape(l, hv, dv)
    serves = np.arange(hv) // (hv // hk)       # a value head's key head
    o = jax.vmap(lambda q_h, k_h, v_h, g_h, beta_h: delta_rule(
        q_h, k_h, v_h, g_h, beta_h, spec), in_axes=1, out_axes=1)(
        q[:, serves], k[:, serves], v, g, beta)             # [L, Hv, dv]
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + spec.norm_eps) \
        * layer["o_norm"]["scale"] * silu(z.reshape(l, hv, dv))
    return mm(o.reshape(l, -1), layer["w_out"], spec)


def gated_attention(layer, x, key_ok, spec: Spec):
    """x [L, d] (normed) of one sequence, key_ok [L] -> [L, d]."""
    l = x.shape[0]
    h, hkv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
    qg = mm(x, layer["wq_gate"], spec)
    q = zn(qg[:, :h * hd].reshape(l, h, hd), layer["q_norm"]["scale"],
           spec.norm_eps)
    k = zn(mm(x, layer["wk"], spec).reshape(l, hkv, hd),
           layer["k_norm"]["scale"], spec.norm_eps)
    v = mm(x, layer["wv"], spec).reshape(l, hkv, hd)
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
    if spec.attention_gate:
        att = att * jax.nn.sigmoid(qg[:, h * hd:])
    return mm(att, layer["wo"], spec)


def expert_layer(layer, x, spec: Spec):
    """x [L, d] (normed) of one sequence -> (y [L, d], the tokens routed
    to each of the router's experts [E])."""
    k = spec.experts_per_token
    lo, hi = spec.held_experts
    with jax.default_matmul_precision("highest"):
        # the router is float32 whatever the model's precision
        p = jax.nn.softmax(x @ layer["router"], axis=-1)           # [L, E]
    chosen = jnp.argsort(-p, axis=-1, stable=True)[:, :k]          # [L, k]
    picked = jnp.zeros(p.shape, bool).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(True)         # [L, E]
    gates = jnp.where(picked, p, 0.0)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)

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
    y = y + jax.nn.sigmoid(mm(x, layer["shared_gate"], spec)) \
        * swiglu(layer["shared"], x, spec)
    return y, picked.sum(axis=0)


def hidden_states(params, seq, spec: Spec):
    """One session's [L] item codes (0 = padding) -> ([L, d] final hidden
    states, 0 at padding; [layers, E] tokens to each expert)."""
    key_ok = seq != 0
    h = params["emb"][seq]

    def block(layer, h, i):
        x = zn(h, layer["ln1"]["scale"], spec.norm_eps)
        if spec.mixer_of(i) == "gdn":
            h = h + linear_attention(layer, x, key_ok, spec,
                                     decay=i != spec.zero_decay_layer)
        else:
            h = h + gated_attention(layer, x, key_ok, spec)
        y, load = expert_layer(
            layer, zn(h, layer["ln2"]["scale"], spec.norm_eps), spec)
        return h + y, load

    if spec.recompute:
        block = jax.checkpoint(block, static_argnums=2)
    loads = []
    for i, layer in enumerate(params["layers"]):
        h, load = block(layer, h, i)
        loads.append(load)
    return jnp.where(key_ok[:, None], zn(
        h, params["ln_f"]["scale"], spec.norm_eps), 0.0), jnp.stack(loads)


def sequence_loss(params, seq, target, spec: Spec):
    """One session: seq, target [L] item codes (0 = padding). -> (summed
    next-item cross-entropy over the real targets, [layers, E] tokens to
    each expert)."""
    hidden, loads = hidden_states(params, seq, spec)
    l = seq.shape[0]
    rows = 2048 if spec.recompute and l % 2048 == 0 else l

    def nll(hid_tgt):
        hid, tgt = hid_tgt
        logits = mm(hid, params["head"], spec)
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
    targets. -> (loss, gradients as numpy, [layers, E] tokens to each
    expert over the batch). One sequence after another; losses and
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


def first_update_norms(params, grads, spec: Spec) -> Dict[str, float]:
    """By parameter group, the norm of theta_1 - theta_0: adamw's first
    step from the gradients `grads` at theta_0 = `params`, leaf by leaf,
    on the host. A release's `router_bias` is no parameter of this
    model: it stays where it is."""
    squares: Dict[str, float] = {}
    for (path, theta), g in zip(jax.tree_util.tree_leaves_with_path(params),
                                jax.tree.leaves(grads)):
        name = grad_group(path)
        u = 0.0 if path[-1].key == "router_bias" else adamw_first_update(
            theta, g, spec.learning_rate)
        squares[name] = squares.get(name, 0.0) + float(
            np.sum(np.square(u), dtype=np.float64))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}


_PARTS = {
    **dict.fromkeys(("w_qkvz", "w_ba", "conv", "A_log", "dt_bias", "o_norm",
                     "w_out"), "linear_attention"),
    **dict.fromkeys(("wq_gate", "wk", "wv", "q_norm", "k_norm", "wo"),
                    "attention"),
    "router": "router", "router_bias": "router", "experts": "experts",
    "shared": "shared_expert", "shared_gate": "shared_expert",
    "ln1": "norms", "ln2": "norms"}


def grad_group(path) -> str:
    """The group a parameter is counted in: tables and head by name, a
    layer's parameters by layer and part."""
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
