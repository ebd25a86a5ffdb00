"""Plain reference of the `sessionrec` template's sequence model under a
layer spec of sliding-window and full softmax attention in one period
over grouped query heads, and softmax-routed experts in every layer (the
decoder of Mellum2-12B-A2.5B,
https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct,
`config.json`, `model_type: mellum`; YaRN: arXiv:2309.00071, as
transformers' `_compute_yarn_parameters`), for a program that trains on
PACKED rows: forward pass, loss and, through `jax.grad` of that loss,
gradients.

**It never packs.** It is handed the sessions of a step's rows one by
one and runs every session ALONE, from position 0, and adds up the
cross-entropies, the gradients and the routed counts. So a key of the
session before, a position that did not restart, a target across a
boundary or a band one key off all move what is compared. (Sessions of
like length share a compiled shape: a session is padded BEHIND its last
item to its bucket's length; a padding key lies in every real query's
future and is masked besides.)

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: attention of either kind by
the dense [L, L] mask and score matrix of a head, one head after another
(a windowed head too: the band is a mask here, never a skipped block);
the experts as a loop over the experts held here, each over every token
under a boolean mask; no kernel, no pair table, no session ids, nothing
of `predictionio_tpu`. The loops are `lax.scan` / `lax.map` (one body,
run in turn). `recompute` wraps each session, each layer, each head and
each expert in `jax.checkpoint`, which changes what is kept and not what
is computed.

The equations, for normed u [L, d] of one session at positions t = 0 ..
L - 1 (rms(x; w) = x / sqrt(mean(x^2) + eps) w, eps 1e-6):

  layer i: h = x + mixer_i(rms(x; w1)); y = h + moe_i(rms(h; w2)); at
  the end rms(y; wf), an untied head, no bias anywhere.

  attention, both kinds, H = 32 query heads of hd = 128 over Hkv = 4
  key/value heads: q = u Wq, k = u Wk, v = u Wv; q and k turned by the
  kind's rotary table on all hd columns (halves pairing: column j with
  j + hd / 2, angle t x inv_freq_j); a = softmax(q k^T / sqrt(hd) +
  mask) v, query head i reading key/value head i // (H / Hkv); out =
  concat_h(a_h) Wo. No gate, no norm on q or k.
    sliding ("swa", layers 0-2 of a period): query t sees keys s with
    t - window < s <= t, window 1,024; inv_freq_j = theta^(-2j/hd),
    theta 500,000, unscaled.
    full ("gqa", the period's last): causal; theta 500,000 under YaRN:
    pos_j = theta^(2j/hd), dim(r) = hd ln(original / (2 pi r)) / (2 ln
    theta), low = max(floor(dim(beta_fast)), 0), high = min(ceil(dim(
    beta_slow)), hd - 1), ramp_j = clip((j - low) / (high - low), 0, 1),
    inv_freq_j = ramp_j / (factor pos_j) + (1 - ramp_j) / pos_j (factor
    16 over original 8,192, beta_fast 32, beta_slow 1); cos and sin
    times `attention_factor` 1.2772588722239782 = 0.1 ln 16 + 1.

  feed-forward, every layer: s = softmax(u Wr) over the 64 logits
  (float32 at the highest precision), the top 8, gates s_e / sum of the
  chosen s (`norm_topk_prob`), no scaling factor, no bias; y = sum over
  the held chosen experts of gate_e SwiGLU_e(u), SwiGLU(u) = (silu(u
  Wg) * (u Wu)) Wd at width 896. No shared expert, no dense layer.

It is given the same share as the program: the router scores all
`n_routed_experts`, the experts `held_experts` = [first, end) add their
part, what the absent ones would add is left out and that partial result
goes on to the next layer; the vocabulary is the slice it is given.

The weights are a release's (`SeqRecModel.params`), by name:
  emb [V, d]; head [d, V]; ln_f {scale}; layers[i]: ln1, ln2 {scale};
  a full layer's wq [d, H hd], wk, wv [d, Hkv hd], wo [H hd, d]; a
  sliding layer's the same four under "swa"; router [d, E], router_bias
  [E] (0: no parameter of this model), experts {w_gate, w_up [held, d,
  w], w_down [held, w, d]}.

Departures from the published description:
  * rotary columns pair by halves, a fixed permutation of a
    checkpoint's layout; with seeded weights either is the model;
  * no multi-token-prediction head: `config.json` has no key for one;
  * no balance term: the config names no coefficient;
  * `precision="int8"` is the control, not the model: the operands of
    every matrix product the configuration computes in one bfloat16 pass
    rounded to 8 bits (symmetric, a scale a row of the left and a column
    of the right operand), in the backward pass too; the router's
    projection stays float32, as the configuration's `precision` states;
  * the `fault` names make the fault controls that can be planted in a
    reference that sees one session at a time, one mechanism broken
    each: "window_512" (the band half as wide), "yarn_factor_64",
    "yarn_on_sliding" (the sliding layers turned by the full layers'
    table), "sigmoid_scores" (each logit's sigmoid in the softmax's
    place), "gates_not_normalised"; `learning_rate` and
    `expert_not_updated` are the optimizer's two. They are NUMBERS the
    compiled reference is handed (`knobs`), not other programs: one
    compilation serves them all. What breaks a session's boundary or the
    restart of positions cannot be planted here (there is no boundary
    here): tools/seqrec_packed_probe.py plants those in the program's
    own inputs.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the int8 product (operands rounded, the backward pass's too) and
# adamw's first step in numpy are the other sequence reference's
from benchmarks.checks.seqrec_reference import _mm_int8, adamw_first_update

FAULTS = ("window_512", "yarn_factor_64", "yarn_on_sliding",
          "sigmoid_scores", "gates_not_normalised")

#: a session alone is padded behind its last item to the next of these
#: lengths (powers of two from the first): sessions of like length share
#: one compiled shape
FIRST_BUCKET = 512


@dataclasses.dataclass(frozen=True)
class Spec:
    mixer: Tuple[str, ...]
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    rope_scaling: Tuple[Tuple[str, float], ...]
    swa: Tuple[Tuple[str, float], ...]
    norm_eps: float
    n_routed_experts: int
    held_experts: Tuple[int, int]
    experts_per_token: int
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

    def compiled(self) -> "Spec":
        """What of the spec a compiled reference depends on: the fault
        and the optimizer's controls are numbers it is handed, or read
        after it."""
        return dataclasses.replace(self, fault=None, learning_rate=0.0,
                                   expert_not_updated=None)

    def knobs(self) -> Dict[str, np.ndarray]:
        """The numbers a fault turns, as the compiled reference takes
        them."""
        own, scaling = dict(self.swa), dict(self.rope_scaling)
        fault = self.fault
        return {
            "window": np.int32(512 if fault == "window_512"
                               else own["window"]),
            "yarn_factor": np.float32(64.0 if fault == "yarn_factor_64"
                                      else scaling["factor"]),
            "yarn_on_sliding": np.bool_(fault == "yarn_on_sliding"),
            "sigmoid_scores": np.bool_(fault == "sigmoid_scores"),
            "gates_normalised": np.bool_(fault != "gates_not_normalised")}


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


def yarn_inv_freq(theta: float, width: int, scaling: dict, factor):
    """[width / 2] float32 inverse frequencies of a head `width` wide
    under YaRN at `factor` (a number or a traced one)."""
    j = np.arange(width // 2, dtype=np.float64)
    pos = theta ** (2.0 * j / width)

    def dim(turns):
        return width * math.log(scaling["original_max_len"]
                                / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim(scaling["beta_slow"])), width - 1)
    blend = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    return jnp.asarray(blend / pos, jnp.float32) / factor \
        + jnp.asarray((1.0 - blend) / pos, jnp.float32)


def rotary_table(spec: Spec, kind: str, knobs):
    """(inverse frequencies [hd / 2], amplitude) of the layers of this
    kind."""
    scaling = dict(spec.rope_scaling)
    hd = spec.head_dim
    full = (yarn_inv_freq(spec.rope_theta, hd, scaling,
                          knobs["yarn_factor"]),
            jnp.float32(scaling["attention_factor"]))
    if kind == "gqa":
        return full
    j = np.arange(hd // 2, dtype=np.float64)
    plain = (jnp.asarray(dict(spec.swa)["rope_theta"] ** (-2.0 * j / hd),
                         jnp.float32), jnp.float32(1.0))
    return tuple(jnp.where(knobs["yarn_on_sliding"], a, b)
                 for a, b in zip(full, plain))


def rope(x, inv_freq, amplitude):
    """x [L, hd] at positions 0 .. L - 1: halves pairing over the whole
    head."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(layer, x, key_ok, spec: Spec, kind: str, knobs):
    """x [L, d] (normed) of one session alone, key_ok [L] -> [L, d];
    `kind` "gqa" (full) or "swa" (sliding)."""
    l = x.shape[0]
    hkv, hd = spec.n_kv_heads, spec.head_dim
    h = layer["wq"].shape[1] // hd
    q = mm(x, layer["wq"], spec).reshape(l, h, hd)
    k = mm(x, layer["wk"], spec).reshape(l, hkv, hd)
    v = mm(x, layer["wv"], spec).reshape(l, hkv, hd)
    table = rotary_table(spec, kind, knobs)
    turn = jax.vmap(lambda t: rope(t, *table), in_axes=1, out_axes=1)
    q, k = turn(q), turn(k)
    at = jnp.arange(l)
    # (a padding position behind the session sees nothing, as a packed
    # row's tail)
    allowed = (at[None, :] <= at[:, None]) & key_ok[None, :] & key_ok[:, None]
    if kind == "swa":
        allowed = allowed & (at[None, :] > at[:, None] - knobs["window"])

    def head(i):
        """One query head against every key of its key/value head."""
        k_h, v_h = k[:, i // (h // hkv)], v[:, i // (h // hkv)]
        s = mm(q[:, i], k_h.T, spec) / np.sqrt(hd)
        top = jnp.max(jnp.where(allowed, s, -jnp.inf), axis=-1, keepdims=True)
        w = jnp.where(allowed, jnp.exp(s - jnp.where(
            jnp.isfinite(top), top, 0.0)), 0.0)
        total = jnp.sum(w, axis=-1, keepdims=True)
        return mm(w / jnp.where(total == 0, 1.0, total), v_h, spec)

    if spec.recompute:
        head = jax.checkpoint(head)
    att = jax.lax.map(head, jnp.arange(h)).swapaxes(0, 1)      # [L, H, hd]
    return mm(att.reshape(l, h * hd), layer["wo"], spec)


def expert_layer(layer, x, spec: Spec, knobs):
    """x [L, d] (normed) of one session -> (y [L, d], [L, E] whether the
    router chose the expert for the token)."""
    k = spec.experts_per_token
    lo, hi = spec.held_experts
    with jax.default_matmul_precision("highest"):
        # the router is float32 whatever the model's precision
        logits = x @ layer["router"]                               # [L, E]
    scores = jnp.where(knobs["sigmoid_scores"], jax.nn.sigmoid(logits),
                       jax.nn.softmax(logits, axis=-1))
    chosen = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]     # [L, k]
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(True)         # [L, E]
    gates = jnp.where(picked, scores, 0.0)
    gates = jnp.where(knobs["gates_normalised"],
                      gates / (gates.sum(-1, keepdims=True) + 1e-20), gates)

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
    return y, picked


def session_loss(params, seq, target, spec: Spec, knobs):
    """One session alone: seq, target [L] item codes from position 0 (0 =
    padding, behind the session). -> (summed next-item cross-entropy
    over its targets, [expert layers, E] its positions' tokens to each
    expert; of a `seq` of padding alone, that position's)."""
    key_ok = seq != 0
    h = params["emb"][seq]

    def block(layer, h, i):
        x = rms(h, layer["ln1"]["scale"], spec.norm_eps)
        kind = spec.mixer_of(i)
        h = h + attention(layer["swa"] if kind == "swa" else layer, x,
                          key_ok, spec, kind, knobs)
        x = rms(h, layer["ln2"]["scale"], spec.norm_eps)
        y, picked = expert_layer(layer, x, spec, knobs)
        counted = key_ok | ~key_ok.any()
        return h + y, jnp.sum(picked & counted[:, None], axis=0)

    if spec.recompute:
        block = jax.checkpoint(block, static_argnums=2)
    loads = []
    for i, layer in enumerate(params["layers"]):
        h, load = block(layer, h, i)
        loads.append(load)
    hidden = rms(h, params["ln_f"]["scale"], spec.norm_eps)
    logits = mm(hidden, params["head"], spec)
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.where(target > 0, picked, 0.0)), jnp.stack(loads)


def bucket(n: int) -> int:
    """The length a session of n positions is padded to."""
    return max(FIRST_BUCKET, 1 << (n - 1).bit_length())


def _bucket_grads(spec: Spec):
    """The jitted (loss, gradients, loads) of the sessions [N, L] of one
    bucket, each alone, one after another."""
    def total(params, seqs, targets, knobs):
        one = lambda st: session_loss(params, st[0], st[1], spec, knobs)
        if spec.recompute:
            one = jax.checkpoint(one)
        ce, loads = jax.lax.map(one, (seqs, targets))
        return ce.sum(), loads.sum(axis=0)

    return jax.jit(jax.value_and_grad(total, has_aux=True))


_COMPILED: Dict[Spec, object] = {}


def loss_and_grads(params, sessions: Sequence[Tuple[np.ndarray, np.ndarray]],
                   spec: Spec, n_positions: Optional[int] = None):
    """The sessions of a step's rows, each (inputs, targets) [n] of its
    own, from position 0: loss = the cross-entropy summed over every
    session's targets over their number. -> (loss, gradients as numpy,
    [expert layers, E] tokens to each expert). `n_positions`: the
    positions of the step's rows, padding and all: the program routes
    its rows' padding positions too (and cuts their output), so the
    loads compared hold them; here a padding position is counted that
    many times (it reads the table's row 0 and sees no key, whatever
    stands beside it)."""
    n_real = max(sum(int((t > 0).sum()) for _, t in sessions), 1)
    params = jax.tree.map(jnp.asarray, params)
    fn = _COMPILED.setdefault(spec.compiled(), _bucket_grads(spec.compiled()))
    knobs = spec.knobs()
    by_bucket: Dict[int, List[Tuple[np.ndarray, np.ndarray]]] = {}
    for inputs, targets in sessions:
        by_bucket.setdefault(bucket(len(inputs)), []).append(
            (inputs, targets))
    loss, grads, load = 0.0, None, 0
    with jax.default_matmul_precision("highest"):
        for length, members in sorted(by_bucket.items()):
            seqs, targets = (np.stack([np.pad(s[n], (0, length - len(s[n])))
                                       for s in members]).astype(np.int32)
                             for n in (0, 1))
            (ce, part_load), g = fn(params, seqs, targets, knobs)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            loss += float(ce)
            load = load + np.asarray(part_load)
    real = sum(len(s) for s, _ in sessions)
    if n_positions is not None and n_positions > real:
        load = load + _padding_load(params, spec, knobs) * (n_positions
                                                            - real)
    grads = jax.tree.map(lambda g: np.asarray(g) / n_real, grads)
    return loss / n_real, grads, np.asarray(load)


def _padding_load(params, spec: Spec, knobs) -> np.ndarray:
    """[expert layers, E] the experts ONE padding position is routed to:
    it reads the table's row 0, sees no key and passes every layer as
    itself plus what its experts add, whatever stands beside it."""
    with jax.default_matmul_precision("highest"):
        _, load = session_loss(params, jnp.zeros((1,), jnp.int32),
                               jnp.zeros((1,), jnp.int32),
                               dataclasses.replace(spec, recompute=False),
                               knobs)
    return np.asarray(load)


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
    layers = sorted(by_expert, key=lambda n: int(n[5:].split(".")[0]))
    by_expert = np.stack([by_expert[name] for name in layers])
    if spec.expert_not_updated is not None:
        layer, expert = spec.expert_not_updated
        squares[layers[layer]] -= by_expert[layer, expert]
        by_expert[layer, expert] = 0.0
    return ({name: float(np.sqrt(v)) for name, v in squares.items()},
            np.sqrt(by_expert))


_PARTS = {
    **dict.fromkeys(("wq", "wk", "wv", "wo"), "attention"),
    "swa": "window_attention",
    "router": "router", "router_bias": "router", "experts": "experts",
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
            np.sum(np.square(np.asarray(leaf, np.float32)),
                   dtype=np.float64))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}
