"""Plain reference of the `sessionrec` template's sequence model under a
layer spec of single-sub-layer layers -- Mamba-2 state-space layers,
grouped-query attention without positions, sigmoid-routed two-matrix
experts in a latent beside a shared expert -- with a
multi-token-prediction module (the hybrid decoder of
NVIDIA-Nemotron-3-Super-120B-A12B,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
`config.json`, `model_type` nemotron_h; the scan's equations: Mamba-2,
arXiv:2405.21060; the module's: DeepSeek-V3's report, section 2.2):
forward pass, loss and, through `jax.grad` of that loss, gradients.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`, one sequence at a time: the
state-space recurrence position by position, a state of [P, N] a head,
no chunks; softmax attention by the full score matrix of a head, a block
of queries at a time so that it fits; the experts as a loop over the
experts held here, each over every token under a boolean mask; no
kernel, no cache, nothing of `predictionio_tpu`. The loops are
`lax.scan` / `lax.map` (one body, run in turn). `recompute` wraps each
layer, each head's block of queries, each stretch of 128 positions of
the recurrence, each expert and each block of the loss in
`jax.checkpoint`, which changes what is kept and not what is computed:
with it the published widths at 8,192 positions fit one chip.

The equations, for one sequence (rms(x; w) = x / sqrt(mean(x^2) + eps)
w; no bias but the convolution's). Every layer is ONE sub-layer,
h <- h + f(rms(h; w)), and a last norm rms(h; w_f) before the head:

  M, state space: u of a padding position set to 0; [z | xBC] = u W_in
  (widths H P | H P + 2 G N), dt = u W_dt (H); xBC <- silu(sum_j
  w_conv[j] xBC_(t - (K - 1) + j) + b_conv) a channel, zeros before the
  sequence; x [H, P], B, C [G, N] from it, head h reading group h // (H
  / G); dt <- softplus(dt + dt_bias), 0 at padding; a = exp(-exp(A_log)
  dt); S_0 = 0, S_t = a_t S_(t-1) + dt_t x_t B_t^T, y_t = S_t C_t + D
  x_t; y <- rms over each group's H P / G columns of y silu(z), times w;
  y W_out.

  *, attention: q = u W_q, k = u W_k, v = u W_v, heads of `head_dim`,
  key/value head j serving the query heads [j r, (j + 1) r); causal
  softmax at head_dim^-0.5, padding keys masked; NO rotary positions,
  no q/k norm, no gate; att W_o.

  E, latent experts: s = sigmoid(u W_r) over all the router's outputs,
  the k largest of s + b chosen, gates s_e / (sum of the chosen + 1e-20)
  times the scaling factor; v = u W_dn; r = sum over the held chosen
  experts of gate_e relu(v W1_e)^2 W2_e; y = r W_up + relu(u Ws1)^2 Ws2.

  the module: h' = [rms(E[item_(t+1)]; w_e) | rms(h_t; w_h)] W_eh with
  h_t the stack's state BEFORE its last norm; the module's own layers
  (`mtp_layers`) and last norm; the MODEL's head; position t scored
  against item t + 2, a session's last position masked. loss = CE_main
  + mtp_loss_weight CE_module, each the mean over its own targets.

It is given the same share as the program: the weights it reads are the
held ones (`tensor_ways`: the query heads with their key/value head,
the state-space heads with their group, the shared expert's columns;
`held_experts` of the router's `n_routed_experts`), each held part's
output projection gives this chip's partial sum, what the absent parts
would add is left out and that partial result goes on to the next
layer; the vocabulary is the slice it is given.

The weights are a release's (`SeqRecModel.params`), by name:
  emb [V, d]; head [d, V]; ln_f {scale}; layers[i], by its kind: ln1
  {scale} and ssm {w_in [d, 2 H P + 2 G N], w_dt [d, H], conv [K, H P +
  2 G N], conv_bias, A_log, dt_bias, D [H], norm {scale [H P]}, w_out
  [H P, d]}; ln1 and wq [d, Hq hd], wk, wv [d, Hkv hd], wo [Hq hd, d];
  ln2, router [d, E], latent {w_dn [d, c], w_up [c, d]}, experts {w_up
  [held, c, w], w_down [held, w, c]}, shared {w_up [d, ws], w_down [ws,
  d]}; mtp {norm_e, norm_h {scale}, w_eh [2 d, d], layers[...], ln_f}.
  A release's `router_bias` (kept at 0: `bias_update_rate` 0) is read
  as the selection bias it is.

Departures from the published description (the configuration's
`assumed` has each with its reason): W_in's columns are [z | x | B | C]
with dt's as a matrix of its own, all of one kind together; attention
carries no positions; the router reads the full state and the gates
apply in the latent; `[embedding | state]` in W_eh; no clamp on dt; no
balance term. `precision="int8"` is the control, not the model: the
operands of every matrix product the configuration computes in one
bfloat16 pass rounded to 8 bits, in the backward pass too; the router's
and dt's projections and the decays stay float32, as the
configuration's `precision` states them. The fault controls are fields
of the spec: `decay_one` (a = 1), `skip_left_out` (no D x),
`norm_gate_left_out` (rms(y) for rms(y silu(z))), `latent_as_slice`
(W_dn, W_up replaced by the first c columns), `dropped_head` (one held
state-space head's output 0), `relu_plain` (relu for relu^2),
`mtp_loss_weight` 0, `mtp_wrong_item` (the module scored against item
t + 1, the main head's target), `expert_not_updated` (one held expert
of one layer left where it is by adamw's first step).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the int8 product (operands rounded, the backward pass's too) and
# adamw's first step in numpy are the first sequence reference's
from benchmarks.checks.seqrec_reference import _mm_int8, adamw_first_update

#: rows of queries a head takes at a time, and of the loss, and the
#: positions of a stretch of the recurrence, under `recompute`
ROWS = 2048
STRETCH = 128


@dataclasses.dataclass(frozen=True)
class Spec:
    sublayers: Tuple[str, ...]
    head_dim: int
    #: (heads, head_dim, groups, state) as PUBLISHED; the held counts
    #: follow from `tensor_ways`
    ssm: Tuple[int, int, int, int]
    tensor_ways: int
    norm_eps: float
    n_routed_experts: int
    held_experts: Tuple[int, int]
    experts_per_token: int
    routed_scaling_factor: float
    mtp_layers: Tuple[str, ...]
    mtp_loss_weight: float
    learning_rate: float
    precision: str = "highest"       # or "int8", the control
    recompute: bool = False
    decay_one: bool = False          # the fault controls
    skip_left_out: bool = False
    norm_gate_left_out: bool = False
    latent_as_slice: bool = False
    dropped_head: int = -1
    relu_plain: bool = False
    mtp_wrong_item: bool = False
    #: (expert layer, held expert) whose first update is left out
    expert_not_updated: Tuple[int, int] = (-1, -1)

    @classmethod
    def of(cls, algorithm_params: dict, **over) -> "Spec":
        names = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in algorithm_params.items() if k in names}
        for key in ("sublayers", "held_experts", "mtp_layers"):
            kept[key] = tuple(kept[key])
        kept["ssm"] = tuple(kept["ssm"][key] for key in (
            "heads", "head_dim", "groups", "state"))
        return cls(**{**kept, **over})

    def held_state_space(self) -> Tuple[int, int, int, int]:
        heads, width, groups, state = self.ssm
        ways = self.tensor_ways
        return heads // ways, width, groups // ways, state


def mm(a, b, spec: Spec):
    """a [L, n] @ b [n, m], at the spec's precision."""
    return _mm_int8(a, b) if spec.precision == "int8" else a @ b


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def relu2(x, spec: Spec):
    x = jnp.maximum(x, 0.0)
    return x if spec.relu_plain else x * x


def recurrence(x, dt, rate, b, c, skip, spec: Spec):
    """One head, position by position: x [L, P], dt [L], rate and skip
    scalars, b, c [L, N] -> y [L, P], from S_0 = 0."""
    l = x.shape[0]

    def position(s, at):
        x_t, dt_t, b_t, c_t = at
        a_t = 1.0 if spec.decay_one else jnp.exp(-rate * dt_t)
        s = a_t * s + mm((dt_t * x_t)[:, None], b_t[None, :], spec)
        y_t = mm(s, c_t[:, None], spec)[:, 0]
        return s, y_t if spec.skip_left_out else y_t + skip * x_t

    s0 = jnp.zeros((x.shape[1], b.shape[1]), jnp.float32)
    if not spec.recompute or l % STRETCH:
        return jax.lax.scan(position, s0, (x, dt, b, c))[1]

    @jax.checkpoint
    def positions(s, xs):
        return jax.lax.scan(position, s, xs)

    _, y = jax.lax.scan(positions, s0, jax.tree.map(
        lambda t: t.reshape(l // STRETCH, STRETCH, *t.shape[1:]),
        (x, dt, b, c)))
    return y.reshape(l, -1)


def state_space(w, u, key_ok, spec: Spec):
    """u [L, d] (normed) of one sequence, key_ok [L] -> [L, d]."""
    l = u.shape[0]
    h, p, g, n = spec.held_state_space()
    taps = w["conv"].shape[0]
    u = jnp.where(key_ok[:, None], u, 0.0)
    zxbc = mm(u, w["w_in"], spec)
    z, xbc = zxbc[:, :h * p], zxbc[:, h * p:]
    with jax.default_matmul_precision("highest"):
        # the step's projection is float32 whatever the model's precision
        dt = jax.nn.softplus(u @ w["w_dt"] + w["dt_bias"])
    dt = jnp.where(key_ok[:, None], dt, 0.0)                    # [L, H]
    before = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc], 0)
    xbc = silu(sum(w["conv"][j] * before[j:j + l] for j in range(taps))
               + w["conv_bias"])
    x = xbc[:, :h * p].reshape(l, h, p)
    b = xbc[:, h * p:h * p + g * n].reshape(l, g, n)
    c = xbc[:, h * p + g * n:].reshape(l, g, n)
    reads = np.arange(h) // (h // g)            # a head's group
    y = jax.vmap(
        lambda x_h, dt_h, rate_h, b_h, c_h, skip_h: recurrence(
            x_h, dt_h, rate_h, b_h, c_h, skip_h, spec),
        in_axes=(1, 1, 0, 1, 1, 0), out_axes=1)(
        x, dt, jnp.exp(w["A_log"]), b[:, reads], c[:, reads], w["D"])
    if spec.dropped_head >= 0:
        y = y.at[:, spec.dropped_head].set(0.0)
    y = y.reshape(l, h * p)
    if not spec.norm_gate_left_out:
        y = y * silu(z)
    y = y.reshape(l, g, -1)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + spec.norm_eps)
    return mm(y.reshape(l, h * p) * w["norm"]["scale"], w["w_out"], spec)


def attention(layer, u, key_ok, spec: Spec):
    """u [L, d] (normed) of one sequence, key_ok [L] -> [L, d]: the held
    query heads over the key/value heads they read, no positions."""
    l, hd = u.shape[0], spec.head_dim
    q = mm(u, layer["wq"], spec).reshape(l, -1, hd)
    k = mm(u, layer["wk"], spec).reshape(l, -1, hd)
    v = mm(u, layer["wv"], spec).reshape(l, -1, hd)
    h, serves = q.shape[1], q.shape[1] // k.shape[1]
    rows = ROWS if spec.recompute and l % ROWS == 0 else l
    at = jnp.arange(l)

    def queries(head, first):
        """Rows [first, first + rows) of one head against every key."""
        q_b = jax.lax.dynamic_slice_in_dim(q, first, rows, 0)[:, head]
        k_h, v_h = k[:, head // serves], v[:, head // serves]
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


def expert_layer(layer, u, spec: Spec):
    """u [L, d] (normed) of one sequence -> (y [L, d], the tokens routed
    to each of the router's experts [E])."""
    k = spec.experts_per_token
    lo, hi = spec.held_experts
    with jax.default_matmul_precision("highest"):
        # the router is float32 whatever the model's precision
        s = jax.nn.sigmoid(u @ layer["router"])                    # [L, E]
    chosen = jnp.argsort(-(s + layer["router_bias"]), axis=-1,
                         stable=True)[:, :k]                       # [L, k]
    picked = jnp.zeros(s.shape, bool).at[
        jnp.arange(u.shape[0])[:, None], chosen].set(True)         # [L, E]
    gates = jnp.where(picked, s, 0.0)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) \
        * spec.routed_scaling_factor
    latent = layer["latent"]
    width = latent["w_dn"].shape[1]
    v = u[:, :width] if spec.latent_as_slice else mm(u, latent["w_dn"], spec)

    def part(held):
        weights, mine, gate = held
        out = mm(relu2(mm(v, weights["w_up"], spec), spec),
                 weights["w_down"], spec)
        return jnp.where(mine[:, None], gate[:, None] * out, 0.0)

    if spec.recompute:
        part = jax.checkpoint(part)
    r, _ = jax.lax.scan(                 # the experts held here, one by one
        lambda r, held: (r + part(held), None), jnp.zeros_like(v), (
            jax.tree.map(lambda w: w[:hi - lo], layer["experts"]),
            picked[:, lo:hi].T, gates[:, lo:hi].T))
    y = jnp.pad(r, ((0, 0), (0, u.shape[1] - width))) \
        if spec.latent_as_slice else mm(r, latent["w_up"], spec)
    shared = layer["shared"]
    y = y + mm(relu2(mm(u, shared["w_up"], spec), spec), shared["w_down"],
               spec)
    return y, picked.sum(axis=0)


def layers_of(layers, kinds, h, key_ok, spec: Spec):
    """The single-sub-layer layers in turn -> (h, [expert layers, E]
    tokens to each expert)."""
    def block(layer, h, kind):
        if kind == "moe":
            y, load = expert_layer(layer, rms(
                h, layer["ln2"]["scale"], spec.norm_eps), spec)
            return h + y, load
        u = rms(h, layer["ln1"]["scale"], spec.norm_eps)
        if kind == "ssm":
            return h + state_space(layer["ssm"], u, key_ok, spec), None
        return h + attention(layer, u, key_ok, spec), None

    if spec.recompute:
        block = jax.checkpoint(block, static_argnums=2)
    loads = []
    for layer, kind in zip(layers, kinds):
        h, load = block(layer, h, kind)
        if load is not None:
            loads.append(load)
    return h, loads


def sequence_loss(params, seq, target, spec: Spec):
    """One session: seq, target [L] item codes (0 = padding). -> (summed
    next-item cross-entropy over the real targets, the module's summed
    over its own, [expert layers, E] tokens to each expert, the module's
    layers after the stack's)."""
    key_ok = seq != 0
    l = seq.shape[0]
    kinds = [spec.sublayers[i % len(spec.sublayers)]
             for i in range(len(params["layers"]))]
    before, loads = layers_of(params["layers"], kinds, params["emb"][seq],
                              key_ok, spec)
    rows = ROWS if spec.recompute and l % ROWS == 0 else l

    def nll(hid_tgt):
        hid, tgt = hid_tgt
        logits = mm(hid, params["head"], spec)
        logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
        picked = jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(tgt > 0, picked, 0.0))

    if spec.recompute:
        nll = jax.checkpoint(nll)

    def summed_nll(h, w, tgt):
        hidden = jnp.where(key_ok[:, None], rms(h, w, spec.norm_eps), 0.0)
        return jnp.sum(jax.lax.map(nll, (hidden.reshape(l // rows, rows, -1),
                                         tgt.reshape(l // rows, rows))))

    main = summed_nll(before, params["ln_f"]["scale"], target)
    module = params["mtp"]
    h = mm(jnp.concatenate([
        rms(params["emb"][target], module["norm_e"]["scale"], spec.norm_eps),
        rms(before, module["norm_h"]["scale"], spec.norm_eps)], axis=-1),
        module["w_eh"], spec)
    h, module_loads = layers_of(module["layers"], spec.mtp_layers, h, key_ok,
                                spec)
    later = jnp.where(key_ok, jnp.concatenate(
        [target[1:], jnp.zeros_like(target[:1])]), 0)
    if spec.mtp_wrong_item:              # at the same positions
        later = jnp.where(later > 0, target, 0)
    return main, summed_nll(h, module["ln_f"]["scale"], later), \
        jnp.stack(loads + module_loads)


def loss_and_grads(params, seqs, targets, spec: Spec):
    """A batch [B, L]: loss = the main cross-entropy over the batch's
    real targets + mtp_loss_weight x the module's over its own. -> (loss,
    gradients as numpy, {"load": [expert layers, E] tokens to each expert
    over the batch, "mtp_loss": the module's own}). One sequence after
    another; losses and gradients add up, on the host."""
    seqs, targets = np.asarray(seqs), np.asarray(targets)
    n_main = max(int((targets > 0).sum()), 1)
    n_module = max(int(((targets[:, 1:] > 0) & (seqs[:, :-1] != 0)).sum()), 1)
    params = jax.tree.map(jnp.asarray, params)     # once, not a sequence

    def part(params, seq, target):
        main, module, load = sequence_loss(params, seq, target, spec)
        return main / n_main + spec.mtp_loss_weight * module / n_module, \
            (module / n_module, load)

    one = jax.jit(jax.value_and_grad(part, has_aux=True))
    loss, module, grads, load = 0.0, 0.0, None, 0
    with jax.default_matmul_precision("highest"):
        for seq, target in zip(seqs, targets):
            (part_loss, (part_module, part_load)), g = one(params, seq, target)
            g = jax.tree.map(np.asarray, g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
            loss, module = loss + float(part_loss), module + float(part_module)
            load = load + np.asarray(part_load)
    return loss, grads, {"load": load, "mtp_loss": module}


def first_update_norms(params, grads, spec: Spec
                       ) -> Tuple[Dict[str, float], np.ndarray]:
    """By parameter group, the norm of theta_1 - theta_0: adamw's first
    step from the gradients `grads` at theta_0 = `params`, leaf by leaf,
    on the host; and the "experts" groups' expert by expert, [expert
    layer, held expert], the module's layers after the stack's. A
    selection bias is no parameter of adamw's and its own update's rate
    is 0: it stays where it is. `expert_not_updated` (layer, expert) is
    a fault control: that held expert's matrices stay where they are."""
    squares: Dict[str, float] = {}
    by_expert: Dict[str, np.ndarray] = {}
    for (path, theta), g in zip(jax.tree_util.tree_leaves_with_path(params),
                                jax.tree.leaves(grads)):
        name = grad_group(path)
        u = 0.0 if path[-1].key == "router_bias" else adamw_first_update(
            theta, g, spec.learning_rate)
        if name.endswith(".experts"):
            each = np.sum(np.square(u), axis=(1, 2), dtype=np.float64)
            by_expert.setdefault(name, np.zeros_like(each))
            layer, expert = spec.expert_not_updated
            if list(by_expert).index(name) == layer:
                each[expert] = 0.0
            by_expert[name] += each
            u = np.sqrt(each)
        squares[name] = squares.get(name, 0.0) + float(
            np.sum(np.square(u), dtype=np.float64))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}, \
        np.sqrt(np.stack(list(by_expert.values())))


_PARTS = {
    "ssm": "state_space", "latent": "latent_projection",
    **dict.fromkeys(("wq", "wk", "wv", "wo"), "attention"),
    "router": "router", "router_bias": "router", "experts": "experts",
    "shared": "shared_expert", "ln1": "norms", "ln2": "norms"}


def grad_group(path) -> str:
    """The group a parameter is counted in: tables and head by name, a
    layer's parameters by layer and part, the module's layers likewise
    ("mtp0.attention") and its norms and projection together ("mtp")."""
    names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    prefix = "layer"
    if names[:2] == ["mtp", "layers"]:
        names, prefix = names[1:], "mtp"
    if names[0] != "layers":
        return {"emb": "embedding", "ln_f": "final_norm"}.get(names[0],
                                                              names[0])
    return f"{prefix}{names[1]}.{_PARTS[names[2]]}"


def group_norms(tree) -> Dict[str, float]:
    squares: Dict[str, float] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = grad_group(path)
        squares[name] = squares.get(name, 0.0) + float(
            jnp.sum(jnp.asarray(leaf, jnp.float32) ** 2))
    return {name: float(np.sqrt(v)) for name, v in squares.items()}
