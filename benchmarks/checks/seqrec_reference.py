"""Plain reference of the `sessionrec` template's sequence model under a
layer spec of latent attention and sigmoid-routed experts (the decoder of
Kimi-VL-A3B, https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct,
`config.json`; equations: DeepSeek-V2 and DeepSeek-V3 reports): forward
pass, loss and, through `jax.grad` of that loss, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: dense attention with a
materialised mask, one head and one sequence at a time; the experts as a
loop over the experts held here, each over every token under a boolean
mask; no kernel, no cache, nothing of `predictionio_tpu`. The loops over
heads and over experts are `lax.map` / `lax.scan` (one body, run in
turn: unrolled in Python the published widths take the chip's compiler
five minutes). `recompute` wraps each layer, each head and the loss of a
sequence in `jax.checkpoint`, which changes what is kept and not what is
computed: with it the published widths fit one chip.

It is given the same share as the program: the router scores all
`n_routed_experts`, the experts `held_experts` = [first, end) add their
part, what the absent ones would add is left out and that partial result
goes on to the next layer; the vocabulary is the slice it is given.

The weights are a release's (`SeqRecModel.params`), by name:
  emb [V, d]; head [d, V] (untied); ln_f {scale}; layers[i]: ln1, ln2
  {scale}; wq [d, H (nope + rope)]; wkva [d, rank + rope]; kv_norm
  {scale}; wkvb [rank, H (nope + v)]; wo [H v, d]; a dense layer's
  w_gate, w_up [d, w], w_down [w, d]; an expert layer's router [d, E],
  router_bias [E], experts {w_gate, w_up [held, d, w], w_down [held, w,
  d]}, shared {w_gate, w_up, w_down}.

Departures from the published description:
  * the vision tower (MoonViT and its projector) is absent: the catalog
    row gives it no sizes and a session has no images;
  * rotary positions pair dimension i with i + D/2 ("halves"); the
    checkpoint's interleaved pairing is a fixed permutation of wq's and
    wkva's rotary columns, and with seeded weights either is the model;
  * the balance terms' rates are not in the config: `bias_update_rate`
    (gamma) and `balance_loss_alpha` are the family's defaults, 0.001;
  * `precision="int8"` is the control, not the model: every matrix
    product's operands rounded to 8 bits (symmetric, a scale a row of the
    left and a column of the right operand), in the backward pass too.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Spec:
    n_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    rope_theta: float
    norm_eps: float
    first_dense_layers: int
    n_routed_experts: int
    held_experts: Tuple[int, int]
    experts_per_token: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    balance_loss_alpha: float
    bias_update_rate: float
    learning_rate: float
    precision: str = "highest"       # or "int8", the control
    recompute: bool = False

    @classmethod
    def of(cls, algorithm_params: dict, **over) -> "Spec":
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in algorithm_params.items() if k in names}
        kept["held_experts"] = tuple(kept["held_experts"])
        return cls(**{**kept, **over})


def _to_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _mm_int8(a, b):
    """a [L, n] @ b [n, m] with both operands rounded to int8, and the
    two products of its backward pass likewise (rounding alone has no
    gradient: a low-precision train rounds the operands of every product
    it computes, the backward ones too)."""
    return _to_int8(a, -1) @ _to_int8(b, 0)


def _mm_int8_bwd(operands, d_out):
    a, b = operands
    return (_to_int8(d_out, -1) @ _to_int8(b.T, 0),
            _to_int8(a.T, -1) @ _to_int8(d_out, 0))


_mm_int8.defvjp(lambda a, b: (_mm_int8(a, b), (a, b)), _mm_int8_bwd)


def mm(a, b, spec: Spec):
    """a [L, n] @ b [n, m], at the spec's precision."""
    return _mm_int8(a, b) if spec.precision == "int8" else a @ b


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x [L, D] at positions 0..L-1, halves pairing."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freq
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def swiglu(w, x, spec: Spec):
    gate = mm(x, w["w_gate"], spec)
    return mm(gate * jax.nn.sigmoid(gate) * mm(x, w["w_up"], spec),
              w["w_down"], spec)


def attention(layer, x, key_ok, spec: Spec):
    """Latent attention of one sequence. x [L, d] (normed), key_ok [L]."""
    l = x.shape[0]
    h, nope, rot = spec.n_heads, spec.qk_nope_head_dim, spec.qk_rope_head_dim
    q = mm(x, layer["wq"], spec).reshape(l, h, nope + rot)
    kva = mm(x, layer["wkva"], spec)
    latent = rms_norm(kva[:, :spec.kv_lora_rank], layer["kv_norm"]["scale"],
                      spec.norm_eps)
    k_rot = rope(kva[:, spec.kv_lora_rank:], spec.rope_theta)   # all heads'
    kv = mm(latent, layer["wkvb"], spec).reshape(l, h, nope + spec.v_head_dim)
    allowed = jnp.tril(jnp.ones((l, l), bool)) & key_ok[None, :]

    def head(q_kv):
        q_h, kv_h = q_kv
        q_h = jnp.concatenate([q_h[:, :nope],
                               rope(q_h[:, nope:], spec.rope_theta)], -1)
        k_h = jnp.concatenate([kv_h[:, :nope], k_rot], -1)
        s = mm(q_h, k_h.T, spec) / np.sqrt(nope + rot)
        top = jnp.max(jnp.where(allowed, s, -jnp.inf), axis=-1, keepdims=True)
        w = jnp.where(allowed, jnp.exp(s - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(w, axis=-1, keepdims=True)
        # a padding query before the first real key sees nothing: output 0
        w = w / jnp.where(total == 0, 1.0, total)
        return mm(w, kv_h[:, nope:], spec)

    if spec.recompute:
        head = jax.checkpoint(head)
    out = jax.lax.map(head, (q.swapaxes(0, 1), kv.swapaxes(0, 1)))  # [H, L, v]
    return mm(out.swapaxes(0, 1).reshape(l, -1), layer["wo"], spec)


def expert_layer(layer, x, spec: Spec):
    """x [L, d] (normed) of one sequence -> (y [L, d], the tokens routed
    to each of the router's experts [E], the sequence's balance loss)."""
    e, k = spec.n_routed_experts, spec.experts_per_token
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
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    gates = gates * spec.routed_scaling_factor

    def add_expert(y, held):         # the experts held here, one by one
        weights, mine, gate = held
        return y + jnp.where(mine[:, None], gate[:, None]
                             * swiglu(weights, x, spec), 0.0), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jax.tree.map(lambda w: w[:hi - lo], layer["experts"]),
        picked[:, lo:hi].T, gates[:, lo:hi].T))
    if "shared" in layer:
        y = y + swiglu(layer["shared"], x, spec)
    load = picked.sum(axis=0)
    f = load * (e / (k * x.shape[0]))
    p = (scores / scores.sum(-1, keepdims=True)).mean(axis=0)
    return y, load, (jax.lax.stop_gradient(f) * p).sum()


def sequence_loss(params, seq, target, spec: Spec):
    """One session: seq, target [L] item codes (0 = padding). ->
    (summed next-item cross-entropy over the real targets, summed balance
    loss of the expert layers, [expert layers, E] tokens to each expert)."""
    key_ok = seq != 0
    h = params["emb"][seq]
    loads, balance = [], 0.0

    def block(layer, h, dense):
        h = h + attention(layer, rms_norm(h, layer["ln1"]["scale"],
                                          spec.norm_eps), key_ok, spec)
        x = rms_norm(h, layer["ln2"]["scale"], spec.norm_eps)
        if dense:
            return h + swiglu(layer, x, spec), None, 0.0
        y, load, bal = expert_layer(layer, x, spec)
        return h + y, load, bal

    def nll(h, head):
        hidden = jnp.where(key_ok[:, None], rms_norm(
            h, params["ln_f"]["scale"], spec.norm_eps), 0.0)
        logits = mm(hidden, head, spec)
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        picked = jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(target > 0, picked, 0.0))

    if spec.recompute:
        block = jax.checkpoint(block, static_argnums=2)
        nll = jax.checkpoint(nll)
    for i, layer in enumerate(params["layers"]):
        h, load, bal = block(layer, h, i < spec.first_dense_layers)
        if load is not None:
            loads.append(load)
            balance = balance + bal
    return nll(h, params["head"]), balance, jnp.stack(loads)


def loss_and_grads(params, seqs, targets, spec: Spec):
    """A batch [B, L]: loss = cross-entropy over the batch's real targets
    + alpha x (balance loss summed over layers, mean over sequences).
    -> (loss, gradients as numpy, [expert layers, E] tokens to each
    expert over the batch). One sequence after another; losses and
    gradients add up, on the host."""
    seqs, targets = np.asarray(seqs), np.asarray(targets)
    n_real = max(int((targets > 0).sum()), 1)
    params = jax.tree.map(jnp.asarray, params)     # once, not a sequence

    def part(params, seq, target, share):
        ce, balance, load = sequence_loss(params, seq, target, spec)
        return ce * share[0] + balance * share[1], load

    one = jax.jit(jax.value_and_grad(part, has_aux=True))
    share = np.asarray([1.0 / n_real, spec.balance_loss_alpha / len(seqs)],
                       np.float32)
    loss, grads, load = 0.0, None, 0
    with jax.default_matmul_precision("highest"):
        for seq, target in zip(seqs, targets):
            (part_loss, part_load), g = one(params, seq, target, share)
            g = jax.tree.map(np.asarray, g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
            loss, load = loss + float(part_loss), load + np.asarray(part_load)
    return loss, grads, load


def bias_after_step(bias, load, rate):
    """The router's selection bias after a step that routed `load`
    tokens to each expert."""
    load = np.asarray(load, np.float64)
    return np.asarray(bias) + rate * np.sign(load.mean() - load)


def adamw_first_update(theta, grad, learning_rate, b1=0.9, b2=0.999,
                       eps=1e-8, weight_decay=1e-4):
    """What adamw's first step adds to theta (Loshchilov and Hutter,
    arXiv:1711.05101; the constants are optax's defaults, which the
    program leaves alone). Both moments start at 0, so their bias-corrected
    values after one step are g and g^2."""
    g = np.asarray(grad, np.float32)
    m = (1 - b1) * g
    v = (1 - b2) * g * g
    m_hat, v_hat = m / (1 - b1), v / (1 - b2)
    return -learning_rate * (m_hat / (np.sqrt(v_hat) + eps)
                             + weight_decay * np.asarray(theta, np.float32))


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


def grad_group(path) -> str:
    """The group a parameter is counted in: tables and head by name, a
    layer's parameters by layer and part."""
    names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    if names[0] != "layers":
        return {"emb": "embedding", "ln_f": "final_norm"}.get(names[0],
                                                              names[0])
    part = {"router": "router", "router_bias": "router", "experts": "experts",
            "shared": "shared_expert", "ln1": "norms", "ln2": "norms"}.get(
        names[2], "attention" if names[2] in ("wq", "wkva", "kv_norm",
                                              "wkvb", "wo") else "ffn")
    return f"layer{names[1]}.{part}"


def group_norms(tree) -> Dict[str, float]:
    squares: Dict[str, float] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = grad_group(path)
        squares[name] = squares.get(name, 0.0) + float(
            jnp.sum(jnp.asarray(leaf, jnp.float32) ** 2))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}
