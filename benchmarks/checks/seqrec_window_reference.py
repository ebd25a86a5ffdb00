"""Plain reference of the `sessionrec` template's sequence model under a
layer spec of sliding-window and full softmax attention in one period,
each kind with its own head count and rotary table, a per-head output
gate, a leading dense layer and sigmoid-routed experts beside a shared
one (the decoder of Laguna-XS.2,
https://huggingface.co/poolside/Laguna-XS.2, `config.json`; YaRN:
arXiv:2309.00071, as transformers' `_compute_yarn_parameters`): forward
pass, loss and, through `jax.grad` of that loss, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one sequence at a time:
attention of either kind by the dense [L, L] mask and score matrix of a
head, a block of queries at a time against EVERY key so that it fits (a
windowed head too: the band is a mask here, never a skipped block); the
experts as a loop over the experts held here, each over every token
under a boolean mask; no kernel, no pair table, no cache, nothing of
`predictionio_tpu`. The loops are `lax.scan` / `lax.map` (one body, run
in turn). `recompute` wraps each layer, each head's block of queries,
each expert and each block of the loss in `jax.checkpoint`, which
changes what is kept and not what is computed: with it the published
widths at 16,384 positions fit one chip.

The equations, for normed u [L, d] of one sequence (rms(x; w) = x /
sqrt(mean(x^2) + eps) w):

  layer i: h = x + mixer_i(rms(x; w1)); y = h + ffn_i(rms(h; w2)); at
  the end rms(y; wf).

  attention, both kinds, H query heads of hd over Hkv key/value heads:
  q = u Wq, k = u Wk, v = u Wv, g = sigmoid(u Wg) [L, H]; q and k
  turned by the kind's rotary table (halves pairing within the rotary
  part, the other columns pass); a = softmax(q k^T / sqrt(hd) + mask) v,
  query head i reading key/value head i // (H / Hkv); out =
  concat_h(g_h a_h) Wo.
    full ("gqa"): mask causal and no padding key. Rotary on the leading
    `rotary_dim` columns at `rope_theta`, the inverse frequencies
    YaRN's: with d the rotary width, pos_j = theta^(2j/d), interp_j =
    1 / (factor pos_j), extrap_j = 1 / pos_j, dim(r) = d ln(original /
    (2 pi r)) / (2 ln theta), low = max(floor(dim(beta_fast)), 0), high
    = min(ceil(dim(beta_slow)), d - 1), ramp_j = clip((j - low) /
    (high - low), 0, 1), inv_freq_j = interp_j ramp_j + extrap_j (1 -
    ramp_j); cos and sin times `attention_factor`.
    sliding ("swa"): query t sees keys s with t - window < s <= t that
    are no padding; its own heads, rotary base and width, no scaling.

  feed-forward: the first `first_dense_layers` layers a SwiGLU of
  `ffn_width`; after them s = sigmoid(u Wr) (256 logits, float32), the
  top k of s + b (b a selection bias, not trained), gates s_e / sum of
  the chosen s x `routed_scaling_factor`; y = sum over the held chosen
  experts of gate_e SwiGLU_e(u) + SwiGLU_shared(u).

It is given the same share as the program: the router scores all
`n_routed_experts`, the experts `held_experts` = [first, end) add their
part, what the absent ones would add is left out and that partial result
goes on to the next layer; the vocabulary is the slice it is given.

The weights are a release's (`SeqRecModel.params`), by name:
  emb [V, d]; head [d, V]; ln_f {scale}; layers[i]: ln1, ln2 {scale};
  a full layer's wq [d, H hd], w_head_gate [d, H], wk, wv [d, Hkv hd],
  wo [H hd, d]; a sliding layer's the same five under "swa" at its own
  H; the dense layer's w_gate, w_up [d, w], w_down [w, d]; router
  [d, E], router_bias [E], experts {w_gate, w_up [held, d, w], w_down
  [held, w, d]}, shared {w_gate, w_up, w_down}.

Departures from the published description:
  * rotary columns pair by halves, a fixed permutation of a
    checkpoint's layout; with seeded weights either is the model;
  * no balance term and the selection bias left where it is: the config
    names no coefficient for either;
  * `precision="int8"` is the control, not the model: the operands of
    every matrix product the configuration computes in one bfloat16 pass
    rounded to 8 bits (symmetric, a scale a row of the left and a column
    of the right operand), in the backward pass too; the router's
    projection stays float32, as the configuration's `precision` states;
  * the `fault` names make the fault controls, one new mechanism broken
    each: "window_ignored" (the sliding layers whole causal),
    "window_plus_one" / "window_minus_one" (the band's trailing edge off
    by one either way), "tables_swapped" (each kind turned by the other's
    rotary table), "yarn_ramp_left_out" (the full layers' frequencies
    unscaled, the amplitude kept), "attention_factor_left_out", "gate_
    left_out", "full_heads_everywhere" (a sliding layer reads the first
    `n_heads` of its heads and its other heads' output is 0),
    "scaling_factor_left_out"; `learning_rate` and `expert_not_updated`
    (one held expert left where it is by the first update) are the
    optimizer's two.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the int8 product (operands rounded, the backward pass's too) and
# adamw's first step in numpy are the other sequence reference's
from benchmarks.checks.seqrec_reference import _mm_int8, adamw_first_update

FAULTS = ("window_ignored", "window_plus_one", "window_minus_one",
          "tables_swapped", "yarn_ramp_left_out",
          "attention_factor_left_out", "gate_left_out",
          "full_heads_everywhere", "scaling_factor_left_out")


@dataclasses.dataclass(frozen=True)
class Spec:
    mixer: Tuple[str, ...]
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    rope_scaling: Tuple[Tuple[str, float], ...]
    swa: Tuple[Tuple[str, float], ...]
    norm_eps: float
    first_dense_layers: int
    n_routed_experts: int
    held_experts: Tuple[int, int]
    experts_per_token: int
    routed_scaling_factor: float
    learning_rate: float
    precision: str = "highest"       # or "int8", the control
    recompute: bool = False
    fault: Optional[str] = None      # one of FAULTS
    #: a control of the optimizer: (expert layer, held expert) whose
    #: matrices the first update leaves where they are
    expert_not_updated: Optional[Tuple[int, int]] = None

    @classmethod
    def of(cls, algorithm_params: dict, **over) -> "Spec":
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in algorithm_params.items() if k in names}
        kept["held_experts"] = tuple(kept["held_experts"])
        kept["mixer"] = tuple(kept["mixer"])
        for record in ("rope_scaling", "swa"):
            kept[record] = tuple(sorted(kept[record].items()))
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


def swiglu(w, x, spec: Spec):
    return mm(silu(mm(x, w["w_gate"], spec)) * mm(x, w["w_up"], spec),
              w["w_down"], spec)


def yarn_inv_freq(theta: float, width: int, scaling: dict,
                  ramp: bool = True) -> np.ndarray:
    """[width / 2] inverse frequencies of a rotary part `width` wide
    (float64 arithmetic, rounded once to float32)."""
    j = np.arange(width // 2, dtype=np.float64)
    pos = theta ** (2.0 * j / width)
    if not ramp:
        return (1.0 / pos).astype(np.float32)

    def dim(turns):
        return width * math.log(scaling["original_max_len"]
                                / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim(scaling["beta_slow"])), width - 1)
    blend = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    return (blend / (scaling["factor"] * pos)
            + (1.0 - blend) / pos).astype(np.float32)


def rotary_table(spec: Spec, kind: str):
    """(rotary width, inverse frequencies [width / 2], amplitude) of the
    layers of this kind."""
    if spec.fault == "tables_swapped":
        kind = "swa" if kind == "gqa" else "gqa"
    if kind == "swa":
        own = dict(spec.swa)
        width = int(own["rotary_dim"])
        j = np.arange(width // 2, dtype=np.float64)
        return width, (own["rope_theta"] ** (-2.0 * j / width)).astype(
            np.float32), 1.0
    scaling = dict(spec.rope_scaling)
    amplitude = 1.0 if spec.fault == "attention_factor_left_out" \
        else scaling["attention_factor"]
    return spec.rotary_dim, yarn_inv_freq(
        spec.rope_theta, spec.rotary_dim, scaling,
        ramp=spec.fault != "yarn_ramp_left_out"), amplitude


def rope(x, width: int, inv_freq, amplitude: float):
    """x [L, D] at positions 0..L-1: the leading `width` dimensions
    rotate, halves pairing within them; the others pass."""
    half = width // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    x1, x2, rest = x[:, :half], x[:, half:width], x[:, width:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention(layer, x, key_ok, spec: Spec, kind: str):
    """x [L, d] (normed) of one sequence, key_ok [L] -> [L, d]; `kind`
    "gqa" (full) or "swa" (sliding)."""
    l = x.shape[0]
    hkv, hd = spec.n_kv_heads, spec.head_dim
    window = None
    if kind == "swa":
        own = dict(spec.swa)
        window = int(own["window"]) + {"window_plus_one": 1,
                                       "window_minus_one": -1}.get(
                                           spec.fault, 0)
        if spec.fault == "window_ignored":
            window = None
    h = layer["wq"].shape[1] // hd
    q = mm(x, layer["wq"], spec).reshape(l, h, hd)
    k = mm(x, layer["wk"], spec).reshape(l, hkv, hd)
    v = mm(x, layer["wv"], spec).reshape(l, hkv, hd)
    table = rotary_table(spec, kind)
    turn = jax.vmap(lambda t: rope(t, *table), in_axes=1, out_axes=1)
    q, k = turn(q), turn(k)
    rows = 2048 if spec.recompute and l % 2048 == 0 else l
    at = jnp.arange(l)

    def queries(head, first):
        """Rows [first, first + rows) of one head against every key."""
        q_b = jax.lax.dynamic_slice_in_dim(q, first, rows, 0)[:, head]
        k_h, v_h = k[:, head // (h // hkv)], v[:, head // (h // hkv)]
        t = first + jnp.arange(rows)[:, None]
        allowed = (at[None, :] <= t) & key_ok[None, :]
        if window is not None:
            allowed = allowed & (at[None, :] > t - window)
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
    att = out.reshape(h, l, hd).swapaxes(0, 1)                 # [L, H, hd]
    if spec.fault == "full_heads_everywhere" and kind == "swa":
        att = att * (jnp.arange(h) < spec.n_heads)[None, :, None]
    if spec.fault != "gate_left_out":
        att = att * jax.nn.sigmoid(
            mm(x, layer["w_head_gate"], spec))[:, :, None]
    return mm(att.reshape(l, h * hd), layer["wo"], spec)


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
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    if spec.fault != "scaling_factor_left_out":
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
    return y + swiglu(layer["shared"], x, spec), picked.sum(axis=0)


def hidden_states(params, seq, spec: Spec):
    """One session's [L] item codes (0 = padding) -> ([L, d] final hidden
    states, 0 at padding; [expert layers, E] tokens to each expert)."""
    key_ok = seq != 0
    h = params["emb"][seq]

    def block(layer, h, i):
        x = rms(h, layer["ln1"]["scale"], spec.norm_eps)
        kind = spec.mixer_of(i)
        h = h + attention(layer["swa"] if kind == "swa" else layer, x,
                          key_ok, spec, kind)
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


def first_update_norms(params, grads, spec: Spec):
    """(by parameter group, the norm of theta_1 - theta_0: adamw's first
    step from the gradients `grads` at theta_0 = `params`, leaf by leaf,
    on the host; [expert layer, held expert] the same of each held
    expert's own matrices). A release's `router_bias` is no parameter of
    this model: it stays where it is."""
    squares: Dict[str, float] = {}
    by_expert: Dict[str, np.ndarray] = {}
    for (path, theta), g in zip(jax.tree_util.tree_leaves_with_path(params),
                                jax.tree.leaves(grads)):
        name = grad_group(path)
        u = 0.0 if path[-1].key == "router_bias" else adamw_first_update(
            theta, g, spec.learning_rate)
        squares[name] = squares.get(name, 0.0) + float(
            np.sum(np.square(u), dtype=np.float64))
        if name.endswith(".experts"):
            by_expert[name] = by_expert.get(name, 0.0) + np.sum(
                np.square(u), axis=(1, 2), dtype=np.float64)
    by_expert = np.stack([by_expert[name] for name in sorted(
        by_expert, key=lambda n: int(n[5:].split(".")[0]))])
    if spec.expert_not_updated is not None:
        layer, expert = spec.expert_not_updated
        name = sorted((n for n in squares if n.endswith(".experts")),
                      key=lambda n: int(n[5:].split(".")[0]))[layer]
        squares[name] -= by_expert[layer, expert]
        by_expert[layer, expert] = 0.0
    return ({name: float(np.sqrt(v)) for name, v in squares.items()},
            np.sqrt(by_expert))


_PARTS = {
    **dict.fromkeys(("wq", "w_head_gate", "wk", "wv", "wo"), "attention"),
    "swa": "window_attention",
    **dict.fromkeys(("w_gate", "w_up", "w_down"), "ffn"),
    "router": "router", "router_bias": "router", "experts": "experts",
    "shared": "shared_expert", "ln1": "norms", "ln2": "norms"}


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
