"""Session-based sequence recommendation: a causal decoder over each user's
event stream, trained to predict the next item (the `sessionrec` template).

The reference has no sequence models — its closest notion is the MarkovChain
top-N transition engine (e2/.../engine/MarkovChain.scala:25-87, first-order
only). This model family is the long-context upgrade of that component: the
per-user ordered event sequence IS the long axis, a stack of layers replaces
the transition matrix, and the same DASE Engine surface serves it.

The stack is a spec (`SeqRecParams`): each layer a mixer then a feed-forward,
or one of the two alone, every kind of either one record of one table
(`KINDS`: seven mixers, three feed-forwards). The default is the SASRec block
this began with; the benchmark's seven sequence configurations (latent
attention with routed experts, the gated delta rule, gated short
convolutions, a looped stack with exit gates, state-space layers with latent
experts and a multi-token-prediction module, sliding-window layers beside
full ones with head counts and rotary tables of their own, the same two
kinds over rows packed from many sessions) are specs of the same table.

TPU-native design: all shapes static (a row of max_len positions is one
session, left-padded or cut to its last max_len items, or under `packing`
several whole sessions one after another, attention and positions kept
inside each; id 0 = padding); one jitted train step with donated state
(adamw), its layers
on the routes `ops/` choose from the device and the shapes; batch over the
mesh's "data" axis, table rows and projections over "model" (`shard_params`),
and ring attention over a "seq" axis where the mesh has one.
"""

from __future__ import annotations

import dataclasses
import functools
import operator
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from predictionio_tpu.core.params import Params
from predictionio_tpu.obs import jax_stats, train_stats
from predictionio_tpu.obs.tracing import span
from predictionio_tpu.ops import linear_attention, moe, state_space
from predictionio_tpu.ops.attention import (
    YarnScaling, attention_layout, band_pairs, blockwise_attention,
    grouped_attention, ring_attention_traced, rope, rotary_attention,
    routes_into, session_pairs, split_heads,
)


@dataclasses.dataclass
class SeqRecParams(Params):
    """Hyperparameters; json keys camelCase per engine.json convention.

    The layer spec (`mixer` .. `first_dense_layers`) says what a block is
    made of. Its defaults are the block this model began with: pre-LN,
    fused multi-head attention, a 4d GELU feed-forward, learned positions
    and a tied softmax."""

    d_model: int = 64
    n_heads: int = 2
    n_layers: int = 2
    max_len: int = 32
    learning_rate: float = 1e-3
    batch_size: int = 128
    epochs: int = 10
    seed: int = 7

    # -- the layer spec -------------------------------------------------
    #: one kind for every layer, or one period of kinds repeated over
    #: the layers (layer i has mixer[i % len(mixer)]): a mixer of `KINDS`,
    #: whose record says what it is and which of the fields below it reads
    mixer: Union[str, Sequence[str]] = "mha"
    #: a feed-forward of `KINDS`; the routed one after
    #: `first_dense_layers` layers of dense swiglu
    ffn: str = "gelu"
    #: "layer" (scale and bias), "rms" (scale) or "rms_zero_centered"
    #: (1 + a weight drawn 0)
    norm: str = "layer"
    norm_eps: float = 1e-6
    #: "learned" (a table of max_len rows added to the embeddings),
    #: "rope" (rotary, in attention; halves pairing, ops/attention.rope)
    #: or "none" (attention reads no position: the order is carried by
    #: the recurrent mixers beside it)
    positions: str = "learned"
    rope_theta: float = 10000.0
    #: how the "gqa" mixer's rotary table is stretched past the length it
    #: was trained on, as ONE record (`ops/attention.YarnScaling`'s
    #: fields: factor, original_max_len, beta_fast, beta_slow,
    #: attention_factor); None where it is not
    rope_scaling: Optional[Dict[str, float]] = None
    #: softmax over the item embeddings, or over a head matrix of its own
    tied_head: bool = True
    #: width of a dense feed-forward; 0 = 4 d_model
    ffn_width: int = 0
    first_dense_layers: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    kv_lora_rank: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    rotary_dim: int = 0
    #: whether a "gqa" or "swa" mixer's output is gated: True, by a
    #: sigmoid of a second query projection, element by element
    #: (`wq_gate`); "head", by one column a query head (`wq` and
    #: `w_head_gate`); False, not (`wq` alone)
    attention_gate: Union[bool, str] = True
    #: whether a "gqa" mixer norms its queries and keys over the head
    qk_norm: bool = True
    conv_kernel: int = 0
    linear_key_heads: int = 0
    linear_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 0
    #: the "ssm" mixer's sizes as ONE record, the published counts
    #: (`StateSpaceMixer`'s fields: heads, head_dim, groups, state,
    #: conv_kernel, chunk); None where no layer is one
    ssm: Optional[Dict[str, int]] = None
    #: the "swa" mixer's own sizes as ONE record (`WindowAttention`:
    #: heads, window, rope_theta, rotary_dim; its key/value heads, head
    #: width, gate and q/k norm are the "gqa" mixer's fields above);
    #: None where no layer is one
    swa: Optional[Dict[str, float]] = None
    #: a layer of ONE sub-layer each (one norm, one residual) in place of
    #: a mixer and then a feed-forward: the kinds in order, mixers and
    #: feed-forwards alike ("gqa", "moe", "ssm", ...), one period of
    #: them repeated over the layers as `mixer`'s is; a feed-forward's
    #: kind is then its layer's own. () = every layer is `mixer` then
    #: `ffn`
    sublayers: Sequence[str] = ()
    #: the tensor-parallel share held here is one of this many. n_heads,
    #: n_kv_heads, the ssm record's heads and groups and the shared
    #: expert's width stay the published counts; a kind that is told its
    #: share says what it holds of them (its record's `held`; the
    #: weights are drawn at the held sizes: which rank's they are is
    #: the loader's to say, no step reads it). The held
    #: part's output projection gives this chip's partial sum, and that
    #: partial result goes on to the next layer: nothing stands in for
    #: the other ranks or their all-reduce (as `held_experts` for the
    #: routed experts)
    tensor_ways: int = 1
    #: the router's width: every expert of a layer, wherever it lives
    n_routed_experts: int = 0
    #: [first, end) of them are held (and trained) here; the others lie
    #: on other chips and what they would add is left out (ops/moe.py)
    held_experts: Sequence[int] = (0, 0)
    experts_per_token: int = 0
    moe_width: int = 0
    #: what an expert (routed or shared) computes (`moe.EXPERT_KINDS`):
    #: "swiglu", three matrices, or "relu2", relu(x W_up)^2 W_down
    expert_act: str = "swiglu"
    #: the routed experts live in a latent of this width between a down-
    #: and an up-projection of the layer's own (the router and the shared
    #: expert read the full state; the gates apply in the latent); 0 =
    #: they read and write d_model
    moe_latent_size: int = 0
    n_shared_experts: int = 0
    #: the shared expert's output times a sigmoid of x . w (one column)
    shared_expert_gate: bool = False
    #: the router's affinities: each output's "sigmoid", or the "softmax"
    #: over all of them
    router_scoring: str = "sigmoid"
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    #: added to the chosen scores' sum where the gates are normalised
    router_norm_eps: float = 1e-20
    #: after each step b += rate * sign(mean load - load) on the router's
    #: selection bias (auxiliary-loss-free balancing); 0 leaves it at 0
    bias_update_rate: float = 0.0
    #: coefficient of the sequence-wise balance loss, summed over layers
    balance_loss_alpha: float = 0.0
    #: the step records the routed experts' part of the update expert by
    #: expert (`expert_update_norm`), for a reader that weighs it by the
    #: expert's tokens; of "relu2" experts it always does
    expert_update_by_expert: bool = False
    #: the layer stack runs this many times a step over the same weights,
    #: the last norm applied after every pass and its output fed on
    n_loops: int = 1
    #: a second norm on each sub-layer's OUTPUT before it joins the
    #: residual: h + norm(mixer(norm(h))) (sandwich norms)
    post_norm: bool = False
    #: an exit gate after each pass (a sigmoid of one column of the
    #: pass's hidden state and a bias) and the loss an expectation over
    #: the passes' exits: per target sum_r p_r CE_r - exit_entropy_beta
    #: H(p), p_r = gate_r prod_(j<r) (1 - gate_j), the last pass taking
    #: what is left. Serving answers from the last pass
    exit_gate: bool = False
    exit_entropy_beta: float = 0.0
    #: a multi-token-prediction module after the stack (training only):
    #: its sub-layers' kinds, as `sublayers` names them. Position t's
    #: [rms(Emb(item t+1)) | rms(the stack's state before its last norm)]
    #: through a projection of 2 d -> d, these layers with weights of
    #: their own and a last norm, then the MODEL's head, scored against
    #: item t + 2; loss = main + mtp_loss_weight x the module's. Serving
    #: answers from the main head alone
    mtp_layers: Sequence[str] = ()
    mtp_loss_weight: float = 0.0

    #: a training row holds several whole sessions one after another
    #: (`pack_sessions`: first-fit over the sessions in decreasing length)
    #: instead of one session left-padded to max_len: a query sees the
    #: keys of its own session alone and rotary positions restart at each
    #: session, so a packed session is trained as the
    #: same session alone; `batch_size` then counts rows. For the mixers
    #: whose record says it `packs` (rotary positions, or none)
    packing: bool = False

    #: draw the initial weights on the device (jax.random) instead of on
    #: the host in numpy: the same seed gives the same weights either way,
    #: but not the same as the other way
    device_init: bool = False

    #: memory for time: recompute each block in the backward pass
    #: instead of keeping what it computed, and run feed-forwards and the
    #: softmax loss `TOKEN_BLOCK` tokens at a time. It changes what is
    #: kept, never what is computed (`MEMORY_FIELDS`: no part of a run's
    #: identity)
    remat: bool = False

    def mixer_kind(self, layer: int) -> Optional[str]:
        """The layer's mixer; None where the layer is a feed-forward
        alone (`sublayers`)."""
        if self.sublayers:
            return _sub_layer(self.sublayers[layer % len(self.sublayers)])[0]
        period = (self.mixer,) if isinstance(self.mixer, str) \
            else tuple(self.mixer)
        return period[layer % len(period)]

    def mixer_kinds(self) -> Tuple[str, ...]:
        """Each layer's mixer, of the layers that have one."""
        kinds = (self.mixer_kind(i) for i in range(self.n_layers))
        return tuple(kind for kind in kinds if kind)

    def ffn_kind(self, layer: int) -> Optional[str]:
        """The layer's feed-forward; None where the layer is a mixer
        alone (`sublayers`)."""
        if self.sublayers:
            return _sub_layer(self.sublayers[layer % len(self.sublayers)])[1]
        record = KINDS[self.ffn]
        if record.routed and layer < self.first_dense_layers:
            return record.first_dense
        return self.ffn

    def mtp_kinds(self) -> Tuple[Tuple[Optional[str], Optional[str]], ...]:
        """(mixer, feed-forward) of the multi-token-prediction module's
        layers."""
        return tuple(map(_sub_layer, self.mtp_layers))

    def layer_kinds(self) -> Tuple[Tuple[Optional[str], Optional[str]], ...]:
        """(mixer, feed-forward) of every layer a train step runs: the
        stack's, then the module's."""
        return tuple((self.mixer_kind(i), self.ffn_kind(i))
                     for i in range(self.n_layers)) + self.mtp_kinds()

    def mixers(self) -> set:
        """The mixers of the stack's layers and the module's."""
        return {mixer for mixer, _ in self.layer_kinds() if mixer}

    def has_experts(self) -> bool:
        return any(ffn and KINDS[ffn].routed for _, ffn in self.layer_kinds())

    def held_kind(self, kind: str):
        """The kind's record (`KINDS`) at the sizes held here."""
        record = KINDS[kind].of(self)
        return record.held(self.tensor_ways) if record.share else record

    def state_space(self) -> "StateSpaceMixer":
        """The "ssm" mixer's record at the sizes held here (the probes'
        name for `held_kind`)."""
        return self.held_kind("ssm")

    def held(self, count: int) -> int:
        """How many of `count` heads or columns this tensor share
        holds; of fewer key/value heads than ranks, the one its query
        heads read."""
        return max(1, count // self.tensor_ways)

    def dense_width(self) -> int:
        return self.ffn_width or 4 * self.d_model

    def spec_key(self, memory: bool = True) -> Tuple:
        """Every hyperparameter that shapes a train's program (epochs
        excluded: training further is the resume use case), hashable;
        without `memory`, those that shape its trajectory: a run resumed
        under another memory setting is the same run."""
        spec = dataclasses.asdict(self)
        unset = tuple(f.name for f in dataclasses.fields(self)
                      if f.name in LATER_FIELDS and spec[f.name] == f.default)
        for name in ("epochs",) + unset + (() if memory else MEMORY_FIELDS):
            del spec[name]
        return tuple(sorted(
            (k, tuple(v) if isinstance(v, (list, tuple))
             else tuple(sorted(v.items())) if isinstance(v, dict) else v)
            for k, v in spec.items()))

    def check(self) -> None:
        """What crosses kinds; a kind's own sizes are its record's to
        refuse (`KINDS`)."""
        if not self.mixer:
            raise ValueError("mixer names no kind")
        for name in ("sublayers", "mtp_layers"):
            unknown = set(getattr(self, name)) - set(KINDS)
            if unknown:
                raise ValueError(f"unknown {name} {sorted(unknown)}: "
                                 f"expected among {MIXERS + FFNS}")
        for name, kinds in (("ffn", FFNS),
                            ("norm", ("layer", "rms", "rms_zero_centered")),
                            ("positions", ("learned", "rope", "none")),
                            ("router_scoring", ("sigmoid", "softmax")),
                            ("expert_act", moe.EXPERT_KINDS)):
            if getattr(self, name) not in kinds:
                raise ValueError(f"unknown {name} {getattr(self, name)!r}: "
                                 f"expected one of {kinds}")
        mixers = self.mixers()
        unknown = mixers - set(MIXERS)
        if unknown:
            raise ValueError(f"unknown mixer {sorted(unknown)}: expected "
                             f"among {MIXERS}")
        for name, defined in (("norm", "norms"), ("positions", "positions")):
            # what a mixer is not defined with: the later ones' norms are
            # RMS norms and their only positions rotary, the
            # convolution's or the state's
            value = getattr(self, name)
            against = sorted(kind for kind in mixers
                             if value not in getattr(KINDS[kind], defined))
            if against and value == "none":
                carry = " and ".join(kind for kind in MIXERS
                                     if "none" in KINDS[kind].positions)
                raise ValueError(f"positions 'none' goes with the mixers "
                                 f"{carry}, not {against}")
            if against:
                raise ValueError(f"{name} {value!r} does not go with the "
                                 f"mixers {against}")
        used = mixers | {ffn for _, ffn in self.layer_kinds()}
        records = {kind: record.of(self) for kind, record in KINDS.items()
                   if kind in used}
        for record in records.values():
            record.check()
        ways = self.tensor_ways
        if ways < 1:
            raise ValueError(f"tensor_ways {ways} must be >= 1")
        if ways > 1:
            # who is told its share says so (`share`: what must divide)
            untold = [kind for kind, record in records.items()
                      if not record.share]
            uneven = [record.share for record in records.values()
                      if record.share and not record.divides(ways)]
            if untold or uneven:
                raise ValueError(
                    f"a tensor share of {ways} ways: {sorted(untold)} hold "
                    f"no share of their own, {uneven} do not divide")
        if self.mtp_layers and self.n_loops > 1:
            raise ValueError("mtp_layers does not go with n_loops > 1")
        if self.packing:
            # a session's boundary is taught to one kernel family: the
            # other mixers carry a state or taps across it
            packs = lambda kind: getattr(KINDS[kind], "packs", False)
            whole = sorted(kind for kind in mixers if not packs(kind))
            if whole:
                raise ValueError(
                    f"packing goes with the mixers "
                    f"{[k for k in MIXERS if packs(k)]}, not {whole}")
            if self.mtp_layers:
                raise ValueError("packing does not go with mtp_layers (the "
                                 "module's targets cross a session's end)")
        if self.moe_latent_size < 0 or self.mtp_loss_weight < 0:
            raise ValueError("moe_latent_size and mtp_loss_weight must be "
                             ">= 0")
        if self.shared_expert_gate and not (self.has_experts()
                                            and self.n_shared_experts):
            raise ValueError("shared_expert_gate without a shared expert")
        if self.n_loops < 1:
            raise ValueError(f"n_loops must be >= 1: {self.n_loops}")
        if self.exit_gate and self.n_loops == 1:
            raise ValueError("exit_gate without a second pass to leave "
                             "before (n_loops 1)")
        if self.post_norm and self.norm == "layer":
            raise ValueError("post_norm does not go with norm 'layer'")
        if self.has_experts():
            lo, hi = self.held_experts
            if not 0 <= lo < hi <= self.n_routed_experts:
                raise ValueError(
                    f"held_experts {tuple(self.held_experts)} is no range "
                    f"of the {self.n_routed_experts} routed experts")
            if not 0 < self.experts_per_token <= self.n_routed_experts:
                raise ValueError("experts_per_token must be 1.."
                                 "n_routed_experts")


# -- the layer kinds ------------------------------------------------------
# A kind is one frozen record, and `KINDS` below the one table of them.
# Its sizes are the spec's flat fields it owns (`of`: the published
# counts; `held` a tensor share's, where `share` names what a share must
# divide and is None where the kind holds none). `check` refuses its own
# bad sizes. `init` draws its weights from the caller's draws
# (`dense(n_in, n_out, experts=())` N(0, 1/n_in), `uniform(shape, hi)`
# U(0, hi), `norm(width)` the spec's norm): leaf names, nesting and the
# order of the draws are a release's and a checkpoint's. `apply` is its
# layer function on the normed state (a mixer's gives y, a feed-forward's
# y and its balance numbers or None), `scope` the one of `STEP_SCOPES` it
# runs under, `grad_groups` where each leaf's gradient norm is recorded,
# `columns` and `rows` the leaves `shard_params` splits over "model". A
# mixer names the `norms` and `positions` it is defined with, whether it
# runs over a mesh's "seq" axis (`ring`), whether it takes a row of
# several sessions (`packs`: its `apply` then takes the `positions` inside
# a session, beside session ids in the key mask's place) and the `family`
# its tokens are counted under; a feed-forward whether it is `routed`.


@dataclasses.dataclass(frozen=True)
class MultiHeadAttention:
    """The "mha" mixer: fused q/k/v of d_model / n_heads a head."""

    heads: int = 0
    rotary: bool = False

    role, scope, family = "mixer", "seqrec_attention", "attention"
    grad_groups = dict.fromkeys(("wqkv", "wo"), "attention")
    columns, rows, share, ring = ("wqkv",), (), None, True
    norms = ("layer", "rms", "rms_zero_centered")
    positions = ("learned", "rope")

    @classmethod
    def of(cls, p: SeqRecParams) -> "MultiHeadAttention":
        return cls(p.n_heads, p.positions == "rope")

    def check(self) -> None:
        """(every count goes as far as the head split's reshape)"""

    def init(self, d: int, dense, uniform, norm) -> Dict:
        return {"wqkv": dense(d, 3 * d), "wo": dense(d, d)}

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh):
        qkv = x @ w["wqkv"]                                     # MXU
        if self.rotary and not _rings(mesh):
            # the projection whole: where the kernels read a head as a
            # block of its columns nothing between the two products is
            # relaid. The product above runs at the default precision,
            # and so do its two backward products: where that is one
            # bfloat16 pass they round this call's gradient themselves,
            # and it may be written so (a product at a higher precision
            # here would have to take float32 back: `_qkv_grad_dtype`).
            return rotary_attention(
                qkv, self.heads, p.rope_theta, block_k=ATTENTION_BLOCK,
                causal=True, key_mask=key_mask,
                devices=1 if mesh is None else mesh.size,
                grad_dtype=_qkv_grad_dtype()) @ w["wo"]
        # the ring's shards, and heads without rotary positions
        q, k, v = split_heads(
            qkv, self.heads, jnp.arange(x.shape[1]) if self.rotary else None,
            p.rope_theta)
        return _attend(q, k, v, None, w["wo"], key_mask, mesh)


@dataclasses.dataclass(frozen=True)
class LatentAttention:
    """The "mla" mixer, latent attention: per-head queries of qk_nope +
    qk_rope (_head_dim), keys and values expanded from one shared latent
    of kv_lora_rank, one rotary key of qk_rope shared by all heads,
    values of v_head_dim."""

    heads: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    kv_rank: int = 0

    role, scope, family = "mixer", "seqrec_attention", "attention"
    grad_groups = dict.fromkeys(("wq", "wkva", "kv_norm", "wkvb", "wo"),
                                "attention")
    columns, rows, share, ring = (), (), None, True
    norms = ("layer", "rms", "rms_zero_centered")
    positions = ("learned", "rope")

    @classmethod
    def of(cls, p: SeqRecParams) -> "LatentAttention":
        return cls(p.n_heads, p.qk_nope_head_dim, p.qk_rope_head_dim,
                   p.v_head_dim, p.kv_lora_rank)

    def check(self) -> None:
        """(every size goes as far as the products' shapes)"""

    def init(self, d: int, dense, uniform, norm) -> Dict:
        h = self.heads
        return {"wq": dense(d, h * (self.nope_dim + self.rope_dim)),
                "wkva": dense(d, self.kv_rank + self.rope_dim),
                "kv_norm": norm(self.kv_rank),
                "wkvb": dense(self.kv_rank, h * (self.nope_dim + self.v_dim)),
                "wo": dense(h * self.v_dim, d)}

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh):
        b, l, _ = x.shape
        h, nope, rot = self.heads, self.nope_dim, self.rope_dim
        positions = jnp.arange(l)
        q = (x @ w["wq"]).reshape(b, l, h, nope + rot)
        latent, k_rot = jnp.split(x @ w["wkva"], [self.kv_rank], -1)
        kv = (_rms_norm(latent, w["kv_norm"]["scale"], p.norm_eps)
              @ w["wkvb"]).reshape(b, l, h, -1)
        k_nope, v = jnp.split(kv, [nope], axis=-1)
        # one rotary key for all heads; the queries' rotary part per head
        k_rot = rope(k_rot[:, :, None, :], positions, p.rope_theta)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], positions, p.rope_theta)], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rot, (b, l, h, rot))], -1)
        return _attend(q, k, v, None, w["wo"], key_mask, mesh)


@dataclasses.dataclass(frozen=True)
class GroupedQueryAttention:
    """The "gqa" mixer: n_heads query heads of head_dim over n_kv_heads
    key/value heads, queries and keys normed over the head width
    (`qk_norm`), rotary positions at base `theta` on the leading
    rotary_dim of it (None where the spec's positions are not rotary),
    the table stretched by `scaling` where there is one, the output
    gated by a sigmoid of a projection of the input (`attention_gate`:
    element by element, or one column a head), causal over the whole
    session (the "swa" kind below is this one under a `window`). Without
    `qk_norm`, at heads of whole lane tiles on the kernels' route
    (`attention_layout`), nothing between the products holds a
    head axis (`grouped_attention`); elsewhere heads are rows of [B, L,
    H, D] (`_attend`)."""

    heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    rotary_dim: Optional[int] = None
    gate: Union[bool, str] = True
    qk_norm: bool = True
    theta: float = 10000.0
    scaling: Optional[YarnScaling] = None
    window: Optional[int] = None

    role, scope, family = "mixer", "seqrec_attention", "attention"
    grad_groups = dict.fromkeys(("wq_gate", "wq", "w_head_gate", "wk", "wv",
                                 "q_norm", "k_norm", "wo"), "attention")
    # the ring takes one key/value head a query head; a row of several
    # sessions is this kind's (and "swa"'s) to take: `apply`'s `positions`
    columns, rows, ring, packs = ("wq_gate",), (), False, True
    share = "n_heads or n_kv_heads"
    norms = ("rms", "rms_zero_centered")
    positions = ("rope", "none")

    @classmethod
    def of(cls, p: SeqRecParams) -> "GroupedQueryAttention":
        return cls(p.n_heads, p.n_kv_heads, p.head_dim,
                   p.rotary_dim if p.positions == "rope" else None,
                   p.attention_gate, p.qk_norm, p.rope_theta,
                   YarnScaling(**p.rope_scaling) if p.rope_scaling else None)

    def check(self, kind: str = "gqa", heads: str = "n_heads") -> None:
        if self.head_dim <= 0 or self.kv_heads <= 0 \
                or self.heads % self.kv_heads:
            raise ValueError(
                f"{kind} needs head_dim > 0 and n_kv_heads a divisor of "
                f"{heads}: {self.head_dim}, {self.kv_heads}, {self.heads}")
        if self.rotary_dim is not None and (
                not 0 < self.rotary_dim <= self.head_dim
                or self.rotary_dim % 2):
            raise ValueError(f"rotary_dim {self.rotary_dim} is no even "
                             f"part of head_dim {self.head_dim}")
        if self.gate not in (True, False, "head"):
            raise ValueError(f"attention_gate {self.gate!r}: expected "
                             f"True, False or 'head'")

    def divides(self, ways: int) -> bool:
        return not (self.heads % ways or (self.kv_heads % ways
                                          and ways % self.kv_heads))

    def held(self, ways: int) -> "GroupedQueryAttention":
        """What one of `ways` tensor ranks holds: its query heads with
        the key/value heads they read; of fewer key/value heads than
        ranks, the one."""
        return dataclasses.replace(self, heads=self.heads // ways,
                                   kv_heads=max(1, self.kv_heads // ways))

    def init(self, d: int, dense, uniform, norm) -> Dict:
        h, kv = self.heads, self.kv_heads
        # without the gate the query projection has no gate's half; a
        # gate of one column a head is a matrix of its own
        wq = {"wq_gate": dense(d, 2 * h * self.head_dim)} \
            if self.gate is True else {"wq": dense(d, h * self.head_dim)}
        if self.gate == "head":
            wq["w_head_gate"] = dense(d, h)
        qk_norms = {"q_norm": norm(self.head_dim),
                    "k_norm": norm(self.head_dim)} if self.qk_norm else {}
        return {**wq,
                "wk": dense(d, kv * self.head_dim),
                "wv": dense(d, kv * self.head_dim),
                **qk_norms,
                "wo": dense(h * self.head_dim, d)}

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh, positions=None):
        """`positions` [B, L] (a packed batch): each position's place in
        its own session, beside which `key_mask` holds its session's id;
        None: a row is one session and its positions are its indices."""
        b, l, _ = x.shape
        packed = positions is not None
        if not packed:
            positions = jnp.arange(l)
        gate = None
        if self.gate is True:
            q, gate = jnp.split(x @ w["wq_gate"], 2, axis=-1)
        else:
            q = x @ w["wq"]
        if self.gate == "head":
            gate = (x @ w["w_head_gate"])[..., None]        # [B, L, H, 1]
        devices = 1 if mesh is None else mesh.size
        if not self.qk_norm and attention_layout(
                None, l, l, self.head_dim, self.head_dim, ATTENTION_BLOCK,
                devices=devices, window=self.window) == "rows":
            # the three products' outputs where they lie, as the "mha"
            # mixer's one: nothing between them and `@ wo` holds a head
            # axis (a gate of a column a head goes in, an elementwise one
            # is flat already), and what the products around it alone
            # read, the three gradients and the gated output, may be
            # written in the type they read it in (`_qkv_grad_dtype`). A
            # head's own norm would have to run in the pass in front of
            # the kernels: a layer with one keeps its heads apart, below
            att = grouped_attention(
                q, x @ w["wk"], x @ w["wv"], self.head_dim,
                gate=gate[..., 0] if self.gate == "head" else None,
                theta=None if self.rotary_dim is None else self.theta,
                rotary_dim=self.rotary_dim, scaling=self.scaling,
                window=self.window, block_k=ATTENTION_BLOCK,
                key_mask=key_mask, operand_dtype=_qkv_grad_dtype(),
                positions=positions if packed else None)
            if self.gate is True:
                att = att * jax.nn.sigmoid(gate)
            return att @ w["wo"]

        def head_rows(t, norm_name):
            t = t.reshape(b, l, -1, self.head_dim)
            if self.qk_norm:
                t = _norm(t, w[norm_name], p)
            if self.rotary_dim is not None:
                t = rope(t, positions, self.theta, self.rotary_dim,
                         self.scaling)
            return t

        q, k = (head_rows(t, name) for t, name in (
            (q, "q_norm"), (x @ w["wk"], "k_norm")))
        v = (x @ w["wv"]).reshape(b, l, -1, self.head_dim)
        return _attend(q, k, v, gate, w["wo"], key_mask, mesh, self.window,
                       packed)


@dataclasses.dataclass(frozen=True)
class WindowAttention(GroupedQueryAttention):
    """The "swa" mixer, sliding-window attention: the "gqa" mixer's layer
    function with a query seeing its own key and the `window` - 1 before
    it (t - window < s <= t; positions are indices, so a left-padded
    session is the unpadded one), and with sizes of its own beside a
    model's full layers: `SeqRecParams.swa` holds its query heads, its
    window and its rotary base and width as a dict of `heads`, `window`,
    `rope_theta`, `rotary_dim` (no scaling: a band never reaches past the
    trained length); key/value heads, head width, gate and q/k norm are
    the spec's, as the full layers'. Its weights lie under the layer's
    "swa"."""

    scope = "seqrec_window_attention"
    grad_groups = {"swa": "window_attention"}
    columns, share = (), None
    positions = ("rope",)

    @classmethod
    def of(cls, p: SeqRecParams) -> "WindowAttention":
        own = dict(p.swa or {})
        return cls(kv_heads=p.n_kv_heads, head_dim=p.head_dim,
                   gate=p.attention_gate, qk_norm=p.qk_norm,
                   theta=own.pop("rope_theta", 10000.0), **own)

    def check(self) -> None:
        if not isinstance(self.window, int) or self.window < 1:
            raise ValueError(f"swa needs a window >= 1: {self.window}")
        super().check("swa", "its heads")

    def init(self, d: int, dense, uniform, norm) -> Dict:
        return {"swa": super().init(d, dense, uniform, norm)}

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh, positions=None):
        return super().apply(w["swa"], x, key_mask, p, mesh, positions)


@dataclasses.dataclass(frozen=True)
class GatedDeltaNet:
    """The "gdn" mixer: linear attention by the gated delta rule
    (`_linear_attention`) behind a causal convolution of
    linear_conv_kernel taps, linear_key_heads key heads of
    linear_key_head_dim serving linear_value_heads value heads of
    linear_value_head_dim, the output normed a head and gated."""

    key_heads: int = 0
    value_heads: int = 0
    key_dim: int = 0
    value_dim: int = 0
    conv_kernel: int = 0

    role, scope = "mixer", "seqrec_linear_attention"
    family = "linear_attention"
    grad_groups = dict.fromkeys(("w_qkvz", "w_ba", "conv", "A_log",
                                 "dt_bias", "o_norm", "w_out"),
                                "linear_attention")
    columns, rows, share, ring = ("w_qkvz",), ("w_out",), None, False
    norms = ("rms", "rms_zero_centered")
    positions = ("rope",)

    @classmethod
    def of(cls, p: SeqRecParams) -> "GatedDeltaNet":
        return cls(p.linear_key_heads, p.linear_value_heads,
                   p.linear_key_head_dim, p.linear_value_head_dim,
                   p.linear_conv_kernel)

    def check(self) -> None:
        sizes = dataclasses.astuple(self)
        if min(sizes) <= 0 or self.value_heads % self.key_heads:
            raise ValueError(
                f"gdn needs its five linear_* sizes > 0 and "
                f"linear_key_heads a divisor of linear_value_heads: "
                f"{sizes}")

    def init(self, d: int, dense, uniform, norm) -> Dict:
        keys = self.key_heads * self.key_dim
        values = self.value_heads * self.value_dim
        return {"w_qkvz": dense(d, 2 * keys + 2 * values),
                "w_ba": dense(d, 2 * self.value_heads),
                "conv": dense(self.conv_kernel, 2 * keys + values),
                # the decay's rate a head: log of U(0, 16)
                "A_log": jnp.log(uniform((self.value_heads,), 16.0)),
                "dt_bias": jnp.ones((self.value_heads,), jnp.float32),
                "o_norm": {"scale": jnp.ones((self.value_dim,),
                                             jnp.float32)},
                "w_out": dense(values, d)}

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh):
        return _linear_attention(w, x, key_mask, p,
                                 1 if mesh is None else mesh.size)


@dataclasses.dataclass(frozen=True)
class ShortConvolution:
    """The "conv" mixer: a gated short convolution (`_short_conv`) — one
    projection to three streams of d_model, the first times the third
    through a depthwise causal convolution of conv_kernel taps without
    activation, times the second, an output projection."""

    kernel: int = 0

    role, scope, family = "mixer", "seqrec_short_conv", "short_conv"
    grad_groups = dict.fromkeys(("conv_in", "conv_taps", "conv_out"),
                                "short_conv")
    columns, rows, share, ring = ("conv_in",), ("conv_out",), None, False
    norms = ("rms", "rms_zero_centered")
    positions = ("rope",)

    @classmethod
    def of(cls, p: SeqRecParams) -> "ShortConvolution":
        return cls(p.conv_kernel)

    def check(self) -> None:
        if self.kernel < 1:
            raise ValueError(f"conv needs conv_kernel >= 1: {self.kernel}")

    def init(self, d: int, dense, uniform, norm) -> Dict:
        return {"conv_in": dense(d, 3 * d),
                "conv_taps": dense(self.kernel, d),
                "conv_out": dense(d, d)}

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh):
        return _short_conv(w, x, key_mask, 1 if mesh is None else mesh.size)


@dataclasses.dataclass(frozen=True)
class StateSpaceMixer:
    """The "ssm" mixer (a Mamba-2 layer around `ops/state_space.scan`).
    `SeqRecParams.ssm` holds the published sizes as a dict of these
    fields; its weights lie under the layer's "ssm".

    [z | x | B | C] = u W_in (widths H P, H P, G N, G N), dt = u W_dt
    (H, at the highest precision: a decay compounds over a session);
    [x | B | C] <- silu(causal_conv(.) + b_conv), depthwise; dt <-
    softplus(dt + dt_bias); the scan at rate exp(A_log) with the skip D;
    y <- rms(y silu(z)) w over each group's H P / G columns; y W_out."""

    heads: int = 0
    head_dim: int = 0
    #: B/C pairs, each read by heads / groups heads in a row, and the
    #: groups the output norm is taken over
    groups: int = 0
    state: int = 0
    conv_kernel: int = 0
    chunk: int = state_space.CHUNK

    role, scope, family = "mixer", "seqrec_state_space", None
    grad_groups = {"ssm": "state_space"}
    # (`w_out` by its name alone, as the linear attention's: the layout
    # this kind was given with it)
    columns, rows, ring = (), ("w_out",), False
    share = "the ssm record's heads or groups"
    norms = ("rms", "rms_zero_centered")
    positions = ("rope", "none")

    @classmethod
    def of(cls, p: SeqRecParams) -> "StateSpaceMixer":
        return cls(**(p.ssm or {}))

    def check(self) -> None:
        if min(dataclasses.astuple(self)) <= 0 or self.heads % self.groups:
            raise ValueError(f"ssm needs its six sizes > 0 and groups a "
                             f"divisor of heads: {self}")

    def divides(self, ways: int) -> bool:
        return not (self.heads % ways or self.groups % ways)

    def held(self, ways: int) -> "StateSpaceMixer":
        """What one of `ways` tensor ranks holds: whole groups with
        their heads."""
        return dataclasses.replace(self, heads=self.heads // ways,
                                   groups=self.groups // ways)

    def widths(self) -> Tuple[int, int]:
        """(the heads' columns H P, a B or C's columns G N)."""
        return self.heads * self.head_dim, self.groups * self.state

    def init(self, d: int, dense, uniform, norm) -> Dict:
        """The convolution's bias as one more row of its taps, A_log =
        log U(1, 16), D 1, dt_bias the inverse softplus of a log-uniform
        step in [0.001, 0.1] floored at 1e-4."""
        hp, gn = self.widths()
        step = jnp.maximum(jnp.exp(jnp.log(0.001) + uniform(
            (self.heads,), 1.0) * (jnp.log(0.1) - jnp.log(0.001))), 1e-4)
        return {"ssm": {
            "w_in": dense(d, 2 * hp + 2 * gn),
            "w_dt": dense(d, self.heads),
            "conv": dense(self.conv_kernel, hp + 2 * gn),
            "conv_bias": dense(self.conv_kernel, hp + 2 * gn)[0],
            "A_log": jnp.log(1.0 + uniform((self.heads,), 15.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "D": jnp.ones((self.heads,), jnp.float32),
            "norm": {"scale": jnp.ones((hp,), jnp.float32)},
            "w_out": dense(hp, d)}}

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh):
        """Normed x [B, L, D] -> [B, L, D] (this share's partial sum). A
        padding position's input is 0 and its step dt is 0: it neither
        decays the state nor writes into it, and a left-padded session
        is the unpadded one."""
        w = w["ssm"]
        b, l, _ = x.shape
        hp, gn = self.widths()
        x = jnp.where(key_mask[..., None], x, 0.0)
        z, xbc = jnp.split(x @ w["w_in"], [hp], axis=-1)
        dt = jax.nn.softplus(jnp.dot(
            x, w["w_dt"], precision=jax.lax.Precision.HIGHEST) + w["dt_bias"])
        xbc = jax.nn.silu(linear_attention.causal_conv(
            xbc, w["conv"], activation=None) + w["conv_bias"])
        xs, bs, cs = jnp.split(xbc, [hp, hp + gn], axis=-1)
        y = state_space.scan(
            xs.reshape(b, l, self.heads, self.head_dim),
            jnp.where(key_mask[..., None], dt, 0.0), jnp.exp(w["A_log"]),
            bs.reshape(b, l, self.groups, self.state),
            cs.reshape(b, l, self.groups, self.state), w["D"], self.chunk)
        y = (y.reshape(b, l, hp) * jax.nn.silu(z)).reshape(
            b, l, self.groups, -1)
        y = y * jax.lax.rsqrt((y * y).mean(-1, keepdims=True) + p.norm_eps)
        return (y.reshape(b, l, hp) * w["norm"]["scale"]) @ w["w_out"]


def _swiglu_weights(dense, d: int, width: int, experts=()) -> Dict:
    return {"w_gate": dense(d, width, experts),
            "w_up": dense(d, width, experts),
            "w_down": dense(width, d, experts)}


@dataclasses.dataclass(frozen=True)
class GeluFeedForward:
    """The "gelu" feed-forward: two matrices of ffn_width."""

    width: int = 0

    role, scope, routed = "ffn", "seqrec_ffn", False
    grad_groups = dict.fromkeys(("w1", "w2"), "ffn")
    columns, rows, share = ("w1",), ("w2",), None

    @classmethod
    def of(cls, p: SeqRecParams) -> "GeluFeedForward":
        return cls(p.dense_width())

    def check(self) -> None:
        """(a width of 0 is 4 d_model: `dense_width`)"""

    def init(self, d: int, dense, uniform, norm) -> Dict:
        return {"w1": dense(d, self.width), "w2": dense(self.width, d)}

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh):
        return _by_tokens(lambda t: jax.nn.gelu(t @ w["w1"]) @ w["w2"],
                          p, x, self.scope), None


@dataclasses.dataclass(frozen=True)
class SwigluFeedForward:
    """The "swiglu" feed-forward: three matrices of ffn_width."""

    width: int = 0

    role, scope, routed = "ffn", "seqrec_ffn", False
    grad_groups = dict.fromkeys(("w_gate", "w_up", "w_down"), "ffn")
    columns, rows, share = (), (), None

    @classmethod
    def of(cls, p: SeqRecParams) -> "SwigluFeedForward":
        return cls(p.dense_width())

    def check(self) -> None:
        """(a width of 0 is 4 d_model: `dense_width`)"""

    def init(self, d: int, dense, uniform, norm) -> Dict:
        return _swiglu_weights(dense, d, self.width)

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh):
        return _by_tokens(lambda t: _swiglu(w, t), p, x, self.scope), None


@dataclasses.dataclass(frozen=True)
class ExpertLayer:
    """The "moe" feed-forward (`_moe`): a router over n_routed_experts,
    of which `held_experts` are held here, each `expert_act`'s matrices
    of moe_width, in a latent of moe_latent_size where that is not 0,
    plus one shared expert of n_shared_experts x moe_width (0 = none),
    gated or not."""

    n_routed: int = 0
    held_experts: Tuple[int, int] = (0, 0)
    width: int = 0
    act: str = "swiglu"
    latent: int = 0
    shared_width: int = 0
    shared_gate: bool = False

    role, scope, routed = "ffn", "seqrec_experts", True
    #: the kind in its place in the spec's first `first_dense_layers`
    first_dense = "swiglu"
    grad_groups = {"router": "router", "router_bias": "router",
                   "latent": "latent_projection", "experts": "experts",
                   "shared": "shared_expert", "shared_gate": "shared_expert"}
    columns, rows, share = (), (), "the shared expert's width"

    @classmethod
    def of(cls, p: SeqRecParams) -> "ExpertLayer":
        return cls(p.n_routed_experts, tuple(p.held_experts), p.moe_width,
                   p.expert_act, p.moe_latent_size,
                   p.n_shared_experts * p.moe_width, p.shared_expert_gate)

    def check(self) -> None:
        """(the held range and the top-k: `SeqRecParams.check`, last)"""

    def divides(self, ways: int) -> bool:
        return not self.shared_width % ways

    def held(self, ways: int) -> "ExpertLayer":
        """What one of `ways` tensor ranks holds: its columns of the
        shared expert (the routed experts' share is `held_experts`)."""
        return dataclasses.replace(self,
                                   shared_width=self.shared_width // ways)

    def _expert(self, dense, d: int, width: int, experts=()) -> Dict:
        """An expert's matrices, routed or shared: `act`'s."""
        if self.act == "swiglu":
            return _swiglu_weights(dense, d, width, experts)
        return {"w_up": dense(d, width, experts),
                "w_down": dense(width, d, experts)}

    def init(self, d: int, dense, uniform, norm) -> Dict:
        lo, hi = self.held_experts
        out = {"router": dense(d, self.n_routed),
               "router_bias": jnp.zeros((self.n_routed,), jnp.float32)}
        if self.latent:
            out["latent"] = {"w_dn": dense(d, self.latent),
                             "w_up": dense(self.latent, d)}
        out["experts"] = self._expert(dense, self.latent or d, self.width,
                                      (hi - lo,))
        if self.shared_width:
            out["shared"] = self._expert(dense, d, self.shared_width)
        if self.shared_gate:
            out["shared_gate"] = dense(d, 1)
        return out

    def apply(self, w, x, key_mask, p: SeqRecParams, mesh):
        return _moe(w, x, p, 1 if mesh is None else mesh.size)


#: the one table of layer kinds: a kind's name in a spec -> its record
KINDS = {"mha": MultiHeadAttention, "mla": LatentAttention,
         "gqa": GroupedQueryAttention, "swa": WindowAttention,
         "gdn": GatedDeltaNet,
         "conv": ShortConvolution, "ssm": StateSpaceMixer,
         "gelu": GeluFeedForward, "swiglu": SwigluFeedForward,
         "moe": ExpertLayer}
MIXERS = tuple(name for name, kind in KINDS.items() if kind.role == "mixer")
FFNS = tuple(name for name, kind in KINDS.items() if kind.role == "ffn")


def _sub_layer(kind: str) -> Tuple[Optional[str], Optional[str]]:
    """A layer of one sub-layer of this kind as (mixer, feed-forward)."""
    role = KINDS[kind].role
    return (kind if role == "mixer" else None,
            kind if role == "ffn" else None)


#: settings that change where a train's work lies and what it keeps in
#: memory, not what it computes
MEMORY_FIELDS = ("remat",)
#: fields the spec gained after runs had taken checkpoints under it: part
#: of a run's identity (`spec_key`) only where they are set, so that an
#: older run keeps the identity it had
LATER_FIELDS = ("rope_scaling", "swa", "expert_update_by_expert", "packing")

#: query and key block of the attention where it runs as a scan of XLA
#: operations (`attention_route`: off a v5e, in a step sharded over a
#: mesh and for shapes the Pallas kernels do not tile; the kernels bring
#: blocks of their own) and the multiple a session's length is padded to
#: on either route; the tokens a feed-forward or the loss takes at a time
#: under `remat`. Constants, from one chip run each at 16,384 tokens a
#: step of 8,192-token sessions (PERF.md section 6, PR 27, the scan on
#: the chip): a step took 1.27 s with attention blocks of 512, 1.35 at
#: 1024, 1.32 at 2048; 1.35, 1.35, 1.36 with token blocks of 1024, 2048,
#: 4096; the compiler counted the same temporaries for all of them.
ATTENTION_BLOCK = 512
TOKEN_BLOCK = 2048
#: key heads of a linear-attention layer taken at a time under `remat`
#: (with the value heads they serve) where the delta rule runs as a scan
#: (`linear_attention.gated_delta_rule_route`: off a v5e, under a mesh):
#: there the chain around the rule is XLA's and a group's internals are
#: recomputed in the backward pass. On the kernels' route all heads go at
#: once: `gated_delta_chain`'s backward pass keeps the projection's
#: output, q, k, v at the key heads, the rule's output and the chunks'
#: states and inverses (2.3 GB a layer at the sizes below, alive through
#: that layer's backward pass) and recomputes nothing. A constant, from
#: chip runs of the scan's step at 16,384 positions, 16
#: key and 32 value heads of 128 (PERF.md section 6, PR 31): a step took
#: 1.109 s at 2, 1.053 at 4, 0.964 at 8; the compiler counted 5.11, 5.97
#: and 8.25 GiB of temporaries (10.97 with all 16 at once) beside 6.99
#: GiB of weights and moments, and 8 leaves a 16 GB chip under 0.2 GB.
LINEAR_KEY_HEADS = 4
#: passes of a looped stack (`n_loops`) the compiled step holds side by
#: side: 1 is a `while` loop around one copy of the stack, True the stack
#: written out a pass after another. A constant, from one chip run each
#: at 8,192 positions through six layers of width 2048 four times
#: (PERF.md section 6, PR 38): a step took 1.224 s as a loop and 1.235 s
#: written out, a process's first step 34.7 s against 62.2 s, and the
#: allocator reserved 6.14 GB of temporaries against 9.52 GB (a weight's
#: gradient adds up in the loop's carry; written out, the passes' four
#: parts of it are alive at once).
LOOP_UNROLL = 1


def init_params(rng: np.random.Generator, n_items: int, p: SeqRecParams,
                vocab_multiple: int = 1) -> Dict:
    """Weights as a pytree. Vocabulary row 0 is the padding item; the table
    is padded up to a multiple of the tp axis size so it shards evenly
    (dead rows never appear as targets and are masked at predict time).
    Matrices are N(0, 1/n_in), the tables N(0, 1/d); with `device_init`
    the draws are jax.random's from the seed, leaf by leaf."""
    p.check()
    d, v = p.d_model, n_items + 1
    v = -(-v // vocab_multiple) * vocab_multiple
    key = jax.random.key(p.seed)
    drawn = 0

    def normal(shape, std):
        nonlocal drawn
        drawn += 1
        if p.device_init:
            return jax.random.normal(jax.random.fold_in(key, drawn), shape,
                                     jnp.float32) * jnp.float32(std)
        return jnp.asarray(rng.normal(size=shape) * std, jnp.float32)

    def norm(width=d):
        if p.norm == "rms_zero_centered":
            return {"scale": jnp.zeros((width,), jnp.float32)}
        w = {"scale": jnp.ones((width,), jnp.float32)}
        if p.norm == "layer":
            w["bias"] = jnp.zeros((width,), jnp.float32)
        return w

    def uniform(shape, hi):
        nonlocal drawn
        drawn += 1
        if p.device_init:
            return jax.random.uniform(jax.random.fold_in(key, drawn), shape,
                                      jnp.float32, 0.0, hi)
        return jnp.asarray(rng.uniform(0.0, hi, size=shape), jnp.float32)

    def dense(n_in, n_out, experts=()):
        return normal((*experts, n_in, n_out), n_in ** -0.5)

    def layer_of(mixer_kind, ffn_kind):
        # a norm is a leaf of its own: a step donates them. A layer of
        # one sub-layer has that sub-layer's norms alone
        names = [name for name, kind in (("ln1", mixer_kind),
                                         ("ln2", ffn_kind)) if kind]
        if p.post_norm:
            names += [name.replace("ln", "post") for name in names]
        layer = {name: norm() for name in names}
        # draws in this order: the host path's are the original block's
        for kind in (mixer_kind, ffn_kind):
            if kind:
                layer.update(p.held_kind(kind).init(d, dense, uniform, norm))
        return layer

    layers = [layer_of(*kinds) for kinds in p.layer_kinds()[:p.n_layers]]
    params = {"emb": normal((v, d), d ** -0.5)}
    if p.positions == "learned":
        params["pos"] = normal((p.max_len, d), d ** -0.5)
    params["ln_f"] = norm()
    params["layers"] = layers
    if not p.tied_head:
        params["head"] = dense(d, v)
    if p.exit_gate:
        params["exit_gate"] = {"w": jnp.zeros((d,), jnp.float32),
                               "b": jnp.zeros((), jnp.float32)}
    if p.mtp_layers:
        params["mtp"] = {
            "norm_e": norm(), "norm_h": norm(), "w_eh": dense(2 * d, d),
            "layers": [layer_of(*kinds) for kinds in p.mtp_kinds()],
            "ln_f": norm()}
    return params


#: the `jax.named_scope`s of the train step, one a layer of the model or
#: part of the step: the step's compiled program publishes which of its
#: instructions belongs to which (`ops/fn_cache.mesh_cached_fn`), and a
#: capture's device time reads by these names (`pio profile`)
STEP_SCOPES = (
    "seqrec_embed", "seqrec_norm", "seqrec_attention",
    "seqrec_linear_attention", "seqrec_short_conv", "seqrec_router",
    "seqrec_experts", "seqrec_shared_expert", "seqrec_ffn",
    "seqrec_head_loss", "seqrec_optimizer", "seqrec_record",
    "seqrec_state_space", "seqrec_latent_projection", "seqrec_mtp",
    "seqrec_window_attention")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _norm(x, w, p: SeqRecParams):
    if p.norm == "rms":
        return _rms_norm(x, w["scale"], p.norm_eps)
    if p.norm == "rms_zero_centered":
        return _rms_norm(x, 1.0 + w["scale"], p.norm_eps)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + p.norm_eps) * w["scale"] + w["bias"]


def _by_token_blocks(fn, p: SeqRecParams, *arrays):
    """fn over [T, ...] arrays; under `remat`, `TOKEN_BLOCK` rows at a
    time with each block's internals recomputed in the backward pass
    (all at once when that does not divide T)."""
    t, block = arrays[0].shape[0], TOKEN_BLOCK
    if not p.remat or t <= block or t % block:
        return fn(*arrays)
    out = jax.lax.map(
        jax.checkpoint(lambda xs: fn(*xs)),
        tuple(a.reshape(t // block, block, *a.shape[1:]) for a in arrays))
    return jax.tree.map(lambda o: o.reshape(t, *o.shape[2:]), out)


def _swiglu(w, x):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _relu2(w, x):
    return jnp.square(jax.nn.relu(x @ w["w_up"])) @ w["w_down"]


def _short_conv(layer, x, key_mask, devices: int = 1):
    """The "conv" mixer on normed x [B, L, D] -> [B, L, D], in a program
    traced for `devices` devices: [b | c | u] = x W_in; y = (c * conv(b *
    u)) W_out, the convolution depthwise, causal, without activation. A
    padding position's input is 0, so it adds nothing to the taps' sums,
    and a left-padded session is the unpadded one. Everything between
    the two products is `linear_attention.gated_short_conv`, on its
    route (`gated_short_conv_route`): fused passes over the projection's
    columns where they lie, or the elementwise chain XLA fuses. The
    passes write the projection's gradient in the type its two backward
    products read it in (`_qkv_grad_dtype`: XLA's chain rounds its halves
    to bfloat16 on the way to those products too)."""
    x = jnp.where(key_mask[..., None], x, 0.0)
    return linear_attention.gated_short_conv(
        x @ layer["conv_in"], layer["conv_taps"], devices,
        grad_dtype=_qkv_grad_dtype()) @ layer["conv_out"]


def _linear_attention(layer, x, key_mask, p: SeqRecParams, devices: int):
    """The "gdn" mixer on normed x [B, L, D] -> [B, L, D], in a program
    traced for `devices` devices. A padding position's input is 0: it
    writes nothing into the state, and a left-padded session is the
    unpadded one. Heads are independent from the projection to the
    output matrix. Everything between the two projections is
    `linear_attention.gated_delta_chain`, on the rule's route
    (`gated_delta_rule_route`). Where the rule runs as a scan the chain
    around it is XLA's (convolution, SiLU, unit length, the head norm and
    gate), and under `remat` the heads are taken `LINEAR_KEY_HEADS` key
    heads (and the value heads they serve) at a time, each group's
    internals recomputed in the backward pass. On the kernels' route all
    heads go at once through fused passes with a backward pass of their
    own, which keeps the projection's output, q, k, v, the rule's output
    and the chunks' states and inverses from the forward pass it follows
    and recomputes nothing; under `remat` that forward pass is the
    block's recomputation, so the chain and the rule's forward kernel run
    twice a step and what is kept lives through one layer's backward
    pass."""
    hk, hv = p.linear_key_heads, p.linear_value_heads
    dk, dv = p.linear_key_head_dim, p.linear_value_head_dim
    x = jnp.where(key_mask[..., None], x, 0.0)
    # the decay compounds over a session: its projection at the highest
    # precision, as the router's
    ba = jnp.dot(x, layer["w_ba"], precision=jax.lax.Precision.HIGHEST)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        ba[..., hv:] + layer["dt_bias"])
    return linear_attention.gated_delta_chain(
        x @ layer["w_qkvz"], layer["conv"], g, beta, layer["o_norm"]["scale"],
        (hk, hv, dk, dv), p.norm_eps, devices,
        LINEAR_KEY_HEADS if p.remat and hk % LINEAR_KEY_HEADS == 0 else None) \
        @ layer["w_out"]


def _qkv_grad_dtype():
    """What the "mha" mixer lets `rotary_attention` round the gradient of
    `x @ wqkv` to where the kernels' route writes it token-first, the
    "gqa" and "swa" mixers `grouped_attention` those of `x @ wq`, `x @
    wk`, `x @ wv` (and the gated output, which `@ wo` rounds likewise), and
    `_short_conv` lets `gated_short_conv` round that of `x @ conv_in` to:
    bfloat16, the type that product's two backward products read it in
    anyway, BECAUSE the mixers multiply at the default precision and
    only while the default is the TPU's one bfloat16 pass; None (float32)
    under `jax.default_matmul_precision` of anything higher
    (tests/test_seqrec_looped.py and tests/test_seqrec_conv.py hold the
    product and this together). Half the bytes of that array: 14 ms of a
    1,139 ms step in ouro-2.6b-pp8.train, 14 of 839 in lfm2-a2b-ep8.train
    (PERF.md section 6, PRs 41 and 42)."""
    ambient = jax.config.jax_default_matmul_precision
    return jnp.bfloat16 if ambient in (None, "default", "bfloat16") else None


def _rings(mesh: Optional[Mesh]) -> bool:
    """Whether attention runs as a ring over the mesh's "seq" axis; the
    blockwise route everywhere else (serving: no mesh)."""
    return mesh is not None and "seq" in mesh.axis_names


def _attend(q, k, v, gate, wo, key_mask, mesh, window=None, packed=False):
    """What the softmax-attention mixers share: q, k, v [B, L, heads, .]
    -> [B, L, D]. The key mask keeps padding out of the softmax. `gate`:
    None, the output's own shape flat [B, L, heads x .], or one column a
    head [B, L, heads, 1]. Under a `window` a query sees that many keys
    up to its own (no ring takes one: `WindowAttention.ring`); `packed`,
    the keys of its own session alone (key_mask the session ids; no ring
    either)."""
    b, l = q.shape[:2]
    if _rings(mesh):
        att = ring_attention_traced(q, k, v, mesh, axis="seq", causal=True,
                                    key_mask=key_mask)
    else:
        att = blockwise_attention(q, k, v, block_k=ATTENTION_BLOCK,
                                  causal=True, key_mask=key_mask,
                                  devices=1 if mesh is None else mesh.size,
                                  window=window, packed=packed)
    if gate is not None and gate.ndim == 4:
        att = att * jax.nn.sigmoid(gate)
    att = att.reshape(b, l, -1)
    if gate is not None and gate.ndim == 3:
        att = att * jax.nn.sigmoid(gate)
    return att @ wo


def _attention(layer, x, key_mask, p: SeqRecParams, kind, mesh, use_ring):
    """A softmax-attention mixer on normed x [B, L, D] -> [B, L, D] (the
    probes' name for its record's `apply`; the mesh says whether it
    rings)."""
    return p.held_kind(kind).apply(layer, x, key_mask, p, mesh)


def _by_tokens(fn, p: SeqRecParams, x, scope: str):
    """A dense feed-forward's fn over normed x [B, L, D] as rows."""
    b, l, _ = x.shape
    with jax.named_scope(scope):
        y = _by_token_blocks(fn, p, x.reshape(b * l, -1))
    return y.reshape(b, l, -1)


def _moe(layer, x, p: SeqRecParams, devices: int = 1):
    """The expert layer on normed x [B, L, D] -> ([B, L, D], balance
    numbers), in a program traced for `devices` devices. Every position
    is routed, padding too (its output is cut at the end of the forward
    pass)."""
    b, l, d = x.shape
    flat = x.reshape(b * l, d)
    with jax.named_scope("seqrec_router"):
        routing = moe.route(flat, layer["router"], layer["router_bias"],
                            p.experts_per_token, p.routed_scaling_factor,
                            p.norm_topk_prob, p.router_scoring,
                            p.router_norm_eps)
    rows = flat
    if "latent" in layer:      # the routed experts' own, narrower space
        with jax.named_scope("seqrec_latent_projection"):
            rows = flat @ layer["latent"]["w_dn"]
    with jax.named_scope("seqrec_experts"):
        ex = layer["experts"]
        y, held_tokens, dropped = moe.held_experts(
            rows, ex.get("w_gate"), ex["w_up"], ex["w_down"], routing,
            p.held_experts[0], pass_rows=b * l, devices=devices)
    if "latent" in layer:
        with jax.named_scope("seqrec_latent_projection"):
            y = y @ layer["latent"]["w_up"]
    if "shared" in layer:
        def shared(t):
            out = (_swiglu if p.expert_act == "swiglu" else _relu2)(
                layer["shared"], t)
            if "shared_gate" in layer:
                out = out * jax.nn.sigmoid(t @ layer["shared_gate"])
            return out

        with jax.named_scope("seqrec_shared_expert"):
            y = y + _by_token_blocks(shared, p, flat)
    with jax.named_scope("seqrec_router"):
        stats = {"load": moe.expert_load(routing.experts,
                                         p.n_routed_experts),
                 "held_tokens": held_tokens, "dropped": dropped,
                 "balance": moe.sequence_balance_loss(routing, b)}
    return y.reshape(b, l, d), stats


def _forward(params: Dict, seqs: jax.Array, p: SeqRecParams,
             mesh: Optional[Mesh] = None,
             next_items: Optional[jax.Array] = None,
             packed: Optional[Tuple[jax.Array, jax.Array]] = None
             ) -> Tuple[Sequence[jax.Array], List[Dict], Dict[str, int],
                        Optional[jax.Array]]:
    """[B, L] int32 item ids (0 = pad) -> (the [B, L, D] hidden states of
    each pass of the stack, the last norm's output, the last pass last
    (one entry at `n_loops` 1, a [n_loops, B, L, D] array otherwise), the
    balance numbers of each expert layer run, pass by pass, the layer
    passes run by mixer, the multi-token-prediction module's state or
    None). With `next_items` [B, L] (each position's next item: the
    targets) under `mtp_layers` the module runs too: its last norm's
    output [B, L, D] is the fourth value, its expert layers' numbers come
    after the stack's. `packed` (`pack_sessions`' rows): (each position's
    session id [B, L], 0 = padding and rising by one along a row; its
    position inside its session [B, L]): a mixer's query then sees the
    keys of its own session alone, at positions that restart with it."""
    b, l = seqs.shape
    ids, positions = packed or (None, None)
    with jax.named_scope("seqrec_embed"):
        h = params["emb"][seqs]
        if "pos" in params:
            h = h + params["pos"][None, :l]
    pad = (seqs == 0)[..., None]
    # left-padding sits in the causal PAST; a packed row's (its tail) in
    # no session
    key_mask = seqs != 0 if packed is None else ids
    in_session = {} if packed is None else {"positions": positions}

    def joined(h, y, layer, name):
        """The residual h + y, y through its own norm first under
        `post_norm`."""
        if name in layer:
            with jax.named_scope("seqrec_norm"):
                y = _norm(y, layer[name], p)
        return h + y

    def block(h, layer, mixer, kind):
        """A layer: its mixer, then its feed-forward; either may be None
        (a layer of one sub-layer)."""
        stats = None
        if mixer is not None:
            record = p.held_kind(mixer)
            with jax.named_scope("seqrec_norm"):
                x = _norm(h, layer["ln1"], p)
            with jax.named_scope(record.scope):
                h = joined(h, record.apply(layer, x, key_mask, p, mesh,
                                           **in_session), layer, "post1")
        if kind is not None:
            with jax.named_scope("seqrec_norm"):
                x = _norm(h, layer["ln2"], p)
            y, stats = p.held_kind(kind).apply(layer, x, key_mask, p, mesh)
            h = joined(h, y, layer, "post2")
        return h, stats

    if p.remat:
        block = jax.checkpoint(block, static_argnums=(2, 3))

    def layers_of(h, layers, kinds):
        """The layers in turn -> (h, the expert layers' numbers)."""
        expert_layers = []
        for layer, (mixer, kind) in zip(layers, kinds):
            h, stats = block(h, layer, mixer, kind)
            if stats is not None:
                expert_layers.append(stats)
        return h, expert_layers

    def stack(h):
        """One pass: every layer, then the last norm; beside it the
        state the norm read."""
        before, expert_layers = layers_of(h, params["layers"],
                                          p.layer_kinds())
        with jax.named_scope("seqrec_norm"):
            h = _norm(before, params["ln_f"], p)
        return h, expert_layers, before

    with_mtp = bool(p.mtp_layers) and next_items is not None
    mixers: Dict[str, int] = {}
    for mixer in p.mixer_kinds():
        mixers[mixer] = mixers.get(mixer, 0) + p.n_loops
    if p.n_loops == 1:
        h, expert_layers, before = stack(h)
        passes, module_state = (jnp.where(pad, 0.0, h),), None
        if with_mtp:
            module = params["mtp"]
            with jax.named_scope("seqrec_mtp"):
                h = jnp.concatenate(
                    [_norm(params["emb"][next_items], module["norm_e"], p),
                     _norm(before, module["norm_h"], p)], -1) @ module["w_eh"]
            h, module_experts = layers_of(h, module["layers"],
                                          p.mtp_kinds())
            with jax.named_scope("seqrec_mtp"):
                h = _norm(h, module["ln_f"], p)
            for mixer, _ in p.mtp_kinds():
                if mixer:
                    mixers[mixer] = mixers.get(mixer, 0) + 1
            module_state = jnp.where(pad, 0.0, h)
            expert_layers = expert_layers + module_experts
        return passes, expert_layers, mixers, module_state

    def one_pass(h, _):
        # the weights are the body's constants: the program holds the
        # stack once, and the scan's backward pass adds a weight's
        # gradient up over the passes
        h, expert_layers, _ = stack(h)
        return h, (jnp.where(pad, 0.0, h), expert_layers)

    _, (passes, by_pass) = jax.lax.scan(one_pass, h, None, length=p.n_loops,
                                        unroll=LOOP_UNROLL)
    expert_layers = [jax.tree.map(lambda t: t[r], stats)
                     for r in range(p.n_loops) for stats in by_pass]
    return passes, expert_layers, mixers, None


def forward(params: Dict, seqs: jax.Array, p: SeqRecParams,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """[B, L] int32 item ids (0 = pad) -> [B, L, D] hidden states.

    A mesh with a "seq" axis runs the attention sequence-parallel
    (ring_attention_traced): each device holds L/p of
    the sequence and K/V blocks rotate via ppermute — exact, O(L/p) HBM
    per device. Under `n_loops` the last pass's states: a position
    leaves at the first pass whose cumulative exit probability reaches
    the threshold, and at a threshold of 1 that is the last."""
    return _forward(params, seqs, p, mesh)[0][-1]


def head_matrix(params: Dict) -> jax.Array:
    """[D, V]: the head the spec names, the item embeddings when tied."""
    return params["head"] if "head" in params else params["emb"].T


def exit_distribution(z: jax.Array) -> jax.Array:
    """Gate logits z [R, ...] of the R passes -> log p [R, ...], the
    distribution over the pass a position leaves at: p_r = s(z_r)
    prod_(j<r) (1 - s(z_j)) and the last pass what is left, prod_(j<R)
    (1 - s(z_j)); its own gate is not read."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-z[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(z[:1]), stay[:-1]])
    return jnp.concatenate([jax.nn.log_sigmoid(z[:-1]) + before, stay[-1:]])


def _loss_fn(params, seqs, targets, p: SeqRecParams, mesh=None, packed=None):
    """Next-item softmax cross-entropy, pad-masked, plus the expert
    layers' balance loss; under `exit_gate` the cross-entropy of every
    pass weighed by the probability of leaving there, less
    `exit_entropy_beta` times that distribution's entropy. -> (loss,
    (the expert layers' balance numbers, the layer passes run by mixer,
    under `exit_gate` each pass's own loss `loop_loss` [R] and its mean
    exit probability `exit_share` [R], under `mtp_layers` the module's
    own loss `mtp_loss`, which joins the loss `mtp_loss_weight` times).
    `packed`: `_forward`'s (a packed row's targets are each session's own
    shift, so the loss is the same sum over the same targets)."""
    passes, expert_layers, mixers, module_state = _forward(
        params, seqs, p, mesh, targets, packed)
    head = head_matrix(params)

    def nll_of(hid, tgt):
        logits = hid @ head                           # [T, V] MXU
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return nll * (tgt > 0)

    exits = {}
    with jax.named_scope("seqrec_head_loss"):
        if not p.exit_gate:
            hidden = passes[-1]
            nll = _by_token_blocks(nll_of, p,
                                   hidden.reshape(-1, hidden.shape[-1]),
                                   targets.reshape(-1))
            loss = nll.sum() / jnp.maximum((targets > 0).sum(), 1)
        else:
            # a pass after another through the head, so that a block's
            # logits are one pass's: [R x T] rows, TOKEN_BLOCK at a time
            r, d = p.n_loops, passes.shape[-1]
            nll = _by_token_blocks(
                nll_of, p, passes.reshape(-1, d),
                jnp.tile(targets.reshape(-1), r)).reshape(r, -1)
            gate = params["exit_gate"]
            # float32 elementwise: no rounded product under the gate
            logp = exit_distribution(
                (passes.reshape(r, -1, d) * gate["w"]).sum(-1) + gate["b"])
            prob = jnp.exp(logp)
            real = targets.reshape(-1) > 0
            n_targets = jnp.maximum(real.sum(), 1)
            per_target = (prob * nll).sum(0) \
                + p.exit_entropy_beta * (prob * logp).sum(0)
            loss = (per_target * real).sum() / n_targets
            exits = {"loop_loss": nll.sum(-1) / n_targets,
                     "exit_share": (prob * real).sum(-1) / n_targets}
    if p.mtp_layers:
        # position t's module state is scored against item t + 2, the
        # next position's target; a session's last position has none
        # (and a padding position in front of a session neither)
        later = jnp.where(seqs != 0, jnp.concatenate(
            [targets[:, 1:], jnp.zeros_like(targets[:, :1])], axis=1), 0)
        with jax.named_scope("seqrec_mtp"):
            nll = _by_token_blocks(
                nll_of, p, module_state.reshape(-1, module_state.shape[-1]),
                later.reshape(-1))
            exits["mtp_loss"] = nll.sum() / jnp.maximum((later > 0).sum(), 1)
            loss = loss + p.mtp_loss_weight * exits["mtp_loss"]
    if p.balance_loss_alpha and expert_layers:
        loss = loss + p.balance_loss_alpha * sum(
            s["balance"] for s in expert_layers)
    return loss, (expert_layers, {kind: jnp.asarray(n, jnp.int32)
                                  for kind, n in mixers.items()}, exits)


#: a layer's leaf -> the part its gradient norm is recorded under: the
#: norms, and what each kind's record says of its own
_GRAD_GROUPS = {
    **dict.fromkeys(("ln1", "ln2", "post1", "post2"), "norms"),
    **{leaf: group for kind in KINDS.values()
       for leaf, group in kind.grad_groups.items()}}


def grad_group(path) -> str:
    """The group a parameter's gradient norm is recorded under: tables
    and head by name, a layer's parameters by layer and part."""
    names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
    prefix = "layer"
    if names[:2] == ["mtp", "layers"]:
        # the module's layers by their own parts ("mtp0.attention"), its
        # norms and projection together as "mtp"
        names, prefix = names[1:], "mtp"
    if names[0] != "layers":
        return {"emb": "embedding", "pos": "positions",
                "ln_f": "final_norm"}.get(names[0], names[0])
    part = _GRAD_GROUPS[names[2]]
    return f"{prefix}{names[1]}.{part}"


def _group_norms(grads) -> Dict[str, jax.Array]:
    squares: Dict[str, jax.Array] = {}
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        name = grad_group(path)
        squares[name] = squares.get(name, 0.0) + (g.astype(
            jnp.float32) ** 2).sum()
    return {name: jnp.sqrt(v) for name, v in squares.items()}


def _expert_norms(experts: Dict) -> jax.Array:
    """[held expert]: the norm of each expert's own matrices in a layer's
    stacked experts."""
    return jnp.sqrt(sum((w.astype(jnp.float32) ** 2).sum(
        tuple(range(1, w.ndim))) for w in jax.tree.leaves(experts)))


def make_optimizer(p: SeqRecParams):
    """adamw; a router's selection bias is no trained parameter (it
    takes no gradient and must not decay)."""
    import optax

    if not p.has_experts():
        return optax.adamw(p.learning_rate)
    return optax.adamw(
        p.learning_rate, mask=lambda params:
        jax.tree_util.tree_map_with_path(
            lambda path, _: getattr(path[-1], "key", None) != "router_bias",
            params))


def make_train_step(mesh: Optional[Mesh], p: SeqRecParams, optimizer):
    """One donated jitted step -> (params, opt_state, the step's numbers:
    loss, by group the gradient's norm and the norm of what the step
    added to the parameters, and per expert layer the tokens routed to
    each expert, to each held expert, and dropped; five constants of
    the trace: `mixer_layers`, the layers it ran by mixer,
    `attention_pallas`, whether `blockwise_attention` folded every
    softmax-attention layer's blocks with the Pallas kernels,
    `attention_rows`, present and True where those kernels read every
    such layer's heads token-first, where the projections wrote them,
    `short_conv_pallas`, present where a "conv" layer ran: whether
    `gated_short_conv` ran every one as the fused passes,
    `linear_attention_pallas`, whether `gated_delta_rule` ran every
    linear-attention layer's recurrence as Pallas kernels, and
    `expert_product_pallas`, whether `held_experts` multiplied every
    expert layer's groups with the Pallas kernels); under `n_loops` a
    sixth, `layer_passes`, the stack's layers run in the `first` pass
    and in the `repeat`s, and `mixer_layers` counts a layer once a pass;
    under `exit_gate` `loop_loss` and `exit_share` [pass]: each pass's
    own cross-entropy and the mean probability of leaving there; under
    `sublayers` or `mtp_layers` `layer_passes` too, every layer run
    counted `first`, the module's among them, and `mtp_loss`, the
    module's own cross-entropy; of "relu2" experts `expert_update_norm`
    [expert layer, held expert], the experts' part of the update expert
    by expert; the same under `expert_update_by_expert`). With a mesh,
    batch is
    sharded over "data" and embedding/ffn rows over "model"; XLA inserts
    the psums. Under `packing` the step takes two more arrays [B, L]
    behind the targets: each position's session id and its position
    inside its session (`pack_sessions`)."""

    def step(params, opt_state, seqs, targets, *packed):
        if mesh is not None and "data" in mesh.axis_names:
            # with ring attention the sequence dim lives on "seq"; laying
            # the tokens out that way up front saves XLA a full reshard
            seq_dim = "seq" if _rings(mesh) else None
            sh = NamedSharding(mesh, P("data", seq_dim))
            seqs = jax.lax.with_sharding_constraint(seqs, sh)
            targets = jax.lax.with_sharding_constraint(targets, sh)
            packed = tuple(jax.lax.with_sharding_constraint(t, sh)
                           for t in packed)
        routes, rule_routes, product_routes = set(), set(), set()
        layouts, conv_routes = set(), set()
        with routes_into(routes, layouts), \
                linear_attention.routes_into(rule_routes, conv_routes), \
                moe.routes_into(product_routes):
            (loss, (expert_layers, mixers, exits)), grads = \
                jax.value_and_grad(_loss_fn, has_aux=True)(
                    params, seqs, targets, p, mesh, packed or None)
        with jax.named_scope("seqrec_optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        with jax.named_scope("seqrec_record"):
            grad_norm = _group_norms(grads)
        stats = {"loss": loss, "grad_norm": grad_norm,
                 "mixer_layers": mixers,
                 "attention_pallas": jnp.asarray(routes == {"pallas"}),
                 "linear_attention_pallas": jnp.asarray(
                     rule_routes == {"pallas"}),
                 "expert_product_pallas": jnp.asarray(
                     product_routes == {"pallas"}),
                 **exits}
        if layouts == {"rows"}:
            # (a step whose kernels read head-first reports what it did
            # before there were two layouts: its program is the one it was)
            stats["attention_rows"] = jnp.asarray(True)
        if conv_routes:
            # (likewise: only a step with a "conv" layer says how it ran)
            stats["short_conv_pallas"] = jnp.asarray(
                conv_routes == {"pallas"})
        if p.n_loops > 1 or p.sublayers or p.mtp_layers:
            # the layers run by the pass they ran in (a layer need not
            # have a mixer, so `mixer_layers` does not add up to them)
            first = len(p.layer_kinds())
            stats["layer_passes"] = {
                name: jnp.asarray(n, jnp.int32) for name, n in (
                    ("first", first), ("repeat", (p.n_loops - 1) * first))}
        if expert_layers:
            # a selection bias is moved by its layer's load, not by adamw:
            # the tokens of all its passes
            moe_layers = [layer for layer, (_, ffn) in zip(
                updates["layers"] + updates.get("mtp", {}).get("layers", []),
                p.layer_kinds()) if ffn and KINDS[ffn].routed]
            with jax.named_scope("seqrec_optimizer"):
                for n, layer in enumerate(moe_layers):
                    # (no 0 + load where there is one pass: the step's
                    # program is then the one it was)
                    layer["router_bias"] = moe.bias_update(
                        jnp.zeros_like(layer["router_bias"]),
                        functools.reduce(operator.add, (
                            s["load"] for s in
                            expert_layers[n::len(moe_layers)])),
                        p.bias_update_rate)
            with jax.named_scope("seqrec_record"):
                stats.update({
                    key: jnp.stack([s[key] for s in expert_layers])
                    for key in ("load", "held_tokens", "dropped")})
                if p.expert_act == "relu2" or p.expert_update_by_expert:
                    # a hidden unit that none of an expert's tokens switched
                    # on has a gradient of exactly 0 and adamw leaves it
                    # where it is: the norm of a layer's update counts the
                    # units that a few tokens reached in its emptiest
                    # experts (and of any kind's an expert with no token
                    # does not move). By held expert, a reader weighs it
                    # by the expert's tokens
                    stats["expert_update_norm"] = jnp.stack([
                        _expert_norms(layer["experts"])
                        for layer in moe_layers])
        with jax.named_scope("seqrec_record"):
            stats["update_norm"] = _group_norms(updates)
        with jax.named_scope("seqrec_optimizer"):
            params = jax.tree.map(lambda w, u: w + u, params, updates)
        return params, opt_state, stats

    return jax.jit(step, donate_argnums=(0, 1))


def shard_params(params: Dict, mesh: Mesh) -> Dict:
    """Lay out the big matrices over the "model" axis (tp): embedding rows,
    ffn inner dim, qkv columns. Small norms replicate."""
    if "model" not in mesh.axis_names:
        return params

    def spec_of(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name == "emb" or any(name in kind.rows for kind in KINDS.values()):
            return P("model", None)
        if name == "head" or any(name in kind.columns
                                 for kind in KINDS.values()):
            return P(None, "model")
        return P()

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jax.device_put(
            leaf, NamedSharding(mesh, spec_of(path, leaf))), params)


def pad_sessions(sessions: Sequence[Sequence[int]], max_len: int
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Sessions of 1-based item ids -> (inputs [N, L], targets [N, L]):
    inputs are the sequence shifted right; targets the sequence itself.
    Keeps the LAST max_len items of each session (recency window)."""
    n = len(sessions)
    inputs = np.zeros((n, max_len), np.int32)
    targets = np.zeros((n, max_len), np.int32)
    for i, s in enumerate(sessions):
        s = list(s)[-(max_len + 1):]
        tgt = s[1:] if len(s) > 1 else []
        inp = s[:-1] if len(s) > 1 else []
        if not inp:
            continue
        inputs[i, -len(inp):] = inp
        targets[i, -len(tgt):] = tgt
    return inputs, targets


@dataclasses.dataclass(frozen=True)
class PackedRows:
    """`pack_sessions`' rows, [rows, max_len] int32 each: `inputs` and
    `targets` as `pad_sessions`' (each session's own shift, 0 = padding,
    a row's unfilled tail), `ids` each position's session in its row (0 =
    padding, from 1, rising by one along the row), `positions` its place
    inside its session (from 0); `sessions` [row]: the sessions laid
    into it, in order, as indices into the sessions given."""

    inputs: np.ndarray
    targets: np.ndarray
    ids: np.ndarray
    positions: np.ndarray
    sessions: Tuple[Tuple[int, ...], ...]


def pack_sessions(sessions: Sequence[Sequence[int]], max_len: int
                  ) -> PackedRows:
    """Sessions of 1-based item ids -> rows of `max_len` positions that
    hold whole sessions one after another: first-fit over the sessions
    in decreasing length (ties in the order given), each laid into the
    first row with room for it, a new row where none has. A session is
    its inputs and targets as `pad_sessions` makes them (n items give n -
    1 positions; the LAST max_len + 1 items of a longer one), so no
    position's target is another session's item; a session of fewer than
    two items takes no room. Deterministic: the same sessions, the same
    rows."""
    spans = [min(len(s), max_len + 1) - 1 for s in sessions]
    order = sorted((i for i, n in enumerate(spans) if n > 0),
                   key=lambda i: -spans[i])
    room: List[int] = []                  # what each row still takes
    members: List[List[int]] = []
    # rows that are full never take another session: the search starts
    # behind the leading run of them
    first_open = 0
    for i in order:
        row = next((r for r in range(first_open, len(room))
                    if room[r] >= spans[i]), len(room))
        if row == len(room):
            room.append(max_len)
            members.append([])
        room[row] -= spans[i]
        members[row].append(i)
        while first_open < len(room) and room[first_open] == 0:
            first_open += 1
    shape = (len(room), max_len)
    inputs, targets, ids, positions = (np.zeros(shape, np.int32)
                                       for _ in range(4))
    for row, inside in enumerate(members):
        at = 0
        for n, i in enumerate(inside):
            s = np.asarray(sessions[i][-(max_len + 1):], np.int32)
            span = slice(at, at + len(s) - 1)
            inputs[row, span], targets[row, span] = s[:-1], s[1:]
            ids[row, span] = n + 1
            positions[row, span] = np.arange(len(s) - 1)
            at = span.stop
    return PackedRows(inputs, targets, ids, positions,
                      tuple(map(tuple, members)))


def _on_one_device(leaf) -> jax.Array:
    """A weight where the serving forward pass, a program for one device,
    wants it: a trained leaf stays where it is unless a mesh holds it."""
    if isinstance(leaf, jax.Array) and len(leaf.sharding.device_set) == 1:
        return leaf
    return jnp.asarray(np.asarray(leaf))


@dataclasses.dataclass
class SeqRecModel:
    """Trained weights + id maps. Out of `train_seqrec` the weights are
    `jax.Array`s where the train left them, their copies to the host
    under way; a release holds them as numpy arrays (the serialiser
    writes a `jax.Array` as one), so out of the model store they are
    numpy and load without a device."""

    item_vocab: np.ndarray     # index i -> item id string for code i+1
    params: Dict               # pytree of jax.Array (trained) or numpy (loaded)
    hyper: SeqRecParams
    #: what training saw, a few numbers a step (train_seqrec's docstring)
    record: Optional[Dict] = None

    def __getstate__(self):
        d = dict(self.__dict__)
        d.pop("_resident", None)
        return d

    def _device_params(self):
        """(weights on the device, the jitted forward pass over them)."""
        cached = getattr(self, "_resident", None)
        if cached is None or cached[0] is not self.params:
            dev = jax.tree.map(_on_one_device, self.params)
            # (no mesh: serving always uses the local attention kernel)
            cached = (self.params, dev,
                      jax.jit(lambda w, seqs: forward(w, seqs, self.hyper)))
            self._resident = cached
        return cached[1:]

    def item_code(self, item_id: str) -> Optional[int]:
        i = np.searchsorted(self.item_vocab, item_id)
        if i < len(self.item_vocab) and self.item_vocab[i] == item_id:
            return int(i) + 1          # 0 is padding
        return None

    def recommend_next(self, recent_items: Sequence[str], num: int,
                       exclude_seen: bool = True) -> List[Tuple[str, float]]:
        codes = [c for it in recent_items
                 if (c := self.item_code(it)) is not None]
        if not codes:
            return []
        l = self.hyper.max_len
        seq = np.zeros((1, l), np.int32)
        tail = codes[-l:]
        seq[0, -len(tail):] = tail
        dev, hidden_of = self._device_params()
        hidden = hidden_of(dev, jnp.asarray(seq))
        logits = np.array(hidden[0, -1] @ head_matrix(dev))  # writable copy
        logits[0] = -np.inf                     # padding id
        logits[len(self.item_vocab) + 1:] = -np.inf   # vocab-padding rows
        if exclude_seen:
            logits[np.asarray(codes)] = -np.inf   # ALL seen, not just tail
        k = min(num, len(self.item_vocab))
        top = np.argpartition(-logits, kth=k - 1)[:k]
        top = top[np.argsort(-logits[top])]
        return [(str(self.item_vocab[i - 1]), float(logits[i]))
                for i in top if np.isfinite(logits[i])]


def seqrec_fingerprint(item_vocab: np.ndarray, p: SeqRecParams,
                       sessions: Sequence[Sequence[str]] = ()) -> str:
    """Identity of a seqrec run for checkpoint-resume safety: every
    hyperparam that shapes the trajectory (epochs excluded — training
    further IS the resume use case) + the full item vocabulary + the
    training sessions themselves. Guards against resuming onto a changed
    item set/order of the same size (embeddings silently mapped to wrong
    item codes), changed learning_rate/seed, or an event store whose
    interactions changed while the vocab did not."""
    import hashlib

    h = hashlib.blake2b(digest_size=16)
    h.update(repr(p.spec_key(memory=False)).encode())
    h.update("\x00".join(str(it) for it in item_vocab).encode())
    for s in sessions:
        h.update("\x00".join(str(it) for it in s).encode())
        h.update(b"\x01")
    return h.hexdigest()


def train_seqrec(mesh: Optional[Mesh], sessions: Sequence[Sequence[str]],
                 p: SeqRecParams, checkpointer=None) -> SeqRecModel:
    """End-to-end: id-assign, pad (or, under `packing`, pack: a row is
    then several whole sessions, `pack_sessions`, and a batch is
    `batch_size` rows), adamw train, return pickled-friendly
    model. `sessions` are per-user time-ordered item-id lists. With a
    `workflow.checkpoint.Checkpointer`, (params, opt_state) snapshot every
    `interval` epochs and a preempted run resumes from the latest one.

    The model carries a short record of the steps this call ran
    (`SeqRecModel.record`), one entry a step in each list: `loss`,
    `grad_norm` and `update_norm` {group: norm} (groups: `grad_group`;
    the update is what the step added to the group's parameters, the
    selection bias's own update with its router's), `rows` (the
    sessions of the batch, as indices into the sessions of two events or
    more, in the order given; under `packing` the batch's rows, as
    indices into `pack_sessions`' rows, and beside them `sessions`: for
    each of those rows the sessions laid into it, in order, as such
    indices) and, with expert layers, `load` [expert
    layer, routed expert], `held_tokens` [expert layer, held expert] and
    `dropped` [expert layer] (under `n_loops` an expert layer once a
    pass, the first pass's layers first; the multi-token-prediction
    module's expert layers after the stack's), of "relu2" experts and
    under `expert_update_by_expert` `expert_update_norm` [expert layer,
    held expert], `update_norm`'s
    "experts" groups expert by expert, and, under `exit_gate`,
    `loop_loss` and `exit_share` [pass]; under `mtp_layers`, `mtp_loss`,
    the module's own cross-entropy (`loss` holds it `mtp_loss_weight`
    times)."""
    p.check()
    unringed = sorted(kind for kind in p.mixers() if not KINDS[kind].ring)
    if _rings(mesh) and unringed:
        raise ValueError(f'a mesh with a "seq" axis (ring attention) does '
                         f'not go with the mixers {unringed}')
    if _rings(mesh) and p.packing:
        raise ValueError('a mesh with a "seq" axis (ring attention) does '
                         'not go with packing')
    with span("seqrec_prepare"):
        all_items = np.asarray(sorted({it for s in sessions for it in s}),
                               dtype=object)
        code = {it: i + 1 for i, it in enumerate(all_items)}
        coded = [[code[it] for it in s] for s in sessions if len(s) >= 2]
        if not coded:
            raise ValueError("need at least one session with >= 2 events")
        packed = None
        if p.packing:
            with span("seqrec_pack"):
                packed = pack_sessions(coded, p.max_len)
            inputs, targets = packed.inputs, packed.targets
        else:
            inputs, targets = pad_sessions(coded, p.max_len)
        fp = seqrec_fingerprint(all_items, p, sessions)

    with span("seqrec_init"):
        rng = np.random.default_rng(p.seed)
        tp = mesh.shape.get("model", 1) if mesh is not None else 1
        params = init_params(rng, len(all_items), p, vocab_multiple=tp)
        jax.block_until_ready(params)
    train_stats.seqrec_param_bytes().set(
        sum(leaf.nbytes for leaf in jax.tree.leaves(params)))
    epoch0 = 0
    restored_opt_leaves = None
    snap = checkpointer.latest(fingerprint=fp) \
        if checkpointer is not None else None
    if snap is not None and "params" in snap[1]:
        e, state = snap
        restored = jax.tree.map(jnp.asarray, state["params"])
        same = jax.tree.structure(restored) == jax.tree.structure(params) \
            and all(a.shape == b.shape for a, b in
                    zip(jax.tree.leaves(restored), jax.tree.leaves(params)))
        if same:
            epoch0, params = e, restored
            restored_opt_leaves = state.get("opt_leaves")
    with span("seqrec_put"):
        # shard BEFORE optimizer.init so adamw's mu/nu inherit the tp
        # layout (a replicated opt state would double-replicate the
        # embedding table)
        if mesh is not None and "model" in mesh.axis_names:
            params = shard_params(params, mesh)
        optimizer = make_optimizer(p)
        opt_state = optimizer.init(params)
        jax.block_until_ready(opt_state)
    if restored_opt_leaves is not None:
        # snapshots hold the opt state as a flat leaf list (numpy-only
        # pytrees survive the restricted snapshot unpickler); rebuild it
        # against the freshly-initialized state's structure + sharding
        treedef = jax.tree.structure(opt_state)
        init_leaves = jax.tree.leaves(opt_state)
        # leaf count alone can't prove layout compatibility (round-3
        # advisor finding): every restored leaf must also match the
        # freshly-initialized leaf's shape AND dtype, else a snapshot
        # from different hyperparams (or an optax layout change) would
        # smuggle mis-shaped moments into the first apply_updates
        compatible = treedef.num_leaves == len(restored_opt_leaves) and all(
            np.asarray(s).shape == np.asarray(i).shape
            and np.asarray(s).dtype == np.asarray(i).dtype
            for s, i in zip(restored_opt_leaves, init_leaves))
        if compatible:
            saved = jax.tree.unflatten(treedef, restored_opt_leaves)
            opt_state = jax.tree.map(
                lambda init_leaf, s: jax.device_put(
                    jnp.asarray(s), init_leaf.sharding)
                if hasattr(init_leaf, "sharding") else s,
                opt_state, saved)
        else:
            import logging

            logging.getLogger(__name__).warning(
                "seqrec snapshot optimizer state incompatible with the "
                "current optimizer layout (%d leaves saved, %d expected, "
                "or shape/dtype mismatch) — resuming params at epoch %d "
                "with RESET adam moments",
                len(restored_opt_leaves), treedef.num_leaves, epoch0)
    # one jitted step per (mesh, spec), kept across trains: a retrain in
    # the same process neither traces nor compiles it again
    from predictionio_tpu.ops.fn_cache import mesh_cached_fn

    step = mesh_cached_fn(
        "seqrec_train_step", mesh, p.spec_key(),
        lambda: make_train_step(mesh, p, make_optimizer(p)),
        scopes=STEP_SCOPES)

    n = len(inputs)
    bs = min(p.batch_size, n)
    batch_starts = range(0, n - bs + 1, bs)
    jax_stats.listen_to_compiler()
    steps: List[Dict] = []     # each step's numbers, still on the device
    rows: List[np.ndarray] = []
    for epoch in range(epoch0, p.epochs):
        # shuffle a FRESH arange keyed by epoch: a resumed run replays the
        # identical batch order the uninterrupted run would have used
        order = np.arange(n)
        np.random.default_rng(p.seed + epoch).shuffle(order)
        with span("seqrec_steps"):
            for lo in batch_starts:
                idx = order[lo:lo + bs]
                compiles_before = jax_stats.backend_compile_count()
                t0 = time.perf_counter()
                params, opt_state, stats = step(
                    params, opt_state, jnp.asarray(inputs[idx]),
                    jnp.asarray(targets[idx]),
                    *(() if packed is None else (
                        jnp.asarray(packed.ids[idx]),
                        jnp.asarray(packed.positions[idx]))))
                # a step's numbers are ready when its program has ended
                jax.block_until_ready(stats["loss"])
                # a warm step only: compile seconds would drown the step's
                if jax_stats.backend_compile_count() == compiles_before:
                    train_stats.seqrec_step_seconds().observe(
                        time.perf_counter() - t0)
                steps.append(stats)
                rows.append(idx)
            jax.block_until_ready(params)
        done = epoch + 1
        if checkpointer is not None and checkpointer.due(done) \
                and done < p.epochs:
            checkpointer.save(done, {"params": params,
                                     "opt_leaves": jax.tree.leaves(opt_state)},
                              fingerprint=fp)
    del opt_state
    if mesh is not None and jax.process_count() > 1:
        # tp-sharded leaves span processes (not host-addressable); one
        # jitted identity with replicated out_shardings gathers them over
        # the interconnect so every host can extract the full model —
        # ledger-cached per mesh so retrains don't re-trace the gather
        replicate = mesh_cached_fn(
            "seqrec_replicate", mesh, (),
            lambda: jax.jit(lambda t: t,
                            out_shardings=NamedSharding(mesh, P())))
        params = replicate(params)
    with span("seqrec_fetch"):
        # the steps' few numbers first: behind the weights they would
        # wait for every copy in front of them
        steps = jax.device_get(steps)
        # the weights' copies to the host start here and nobody waits for
        # them: whoever reads a leaf (the release's pickler, leaf by leaf
        # under its write) waits for that leaf alone
        leaves, treedef = jax.tree.flatten(params)
        for leaf in leaves:
            leaf.copy_to_host_async()
        # rebuilt from its leaves, so that a release lists a dict's keys
        # in the one order whatever built the tree
        params = jax.tree.unflatten(treedef, leaves)
        record = _training_record(steps, rows)
        if packed is not None:
            record["sessions"] = [[list(packed.sessions[r]) for r in idx]
                                  for idx in rows]
    train_stats.seqrec_fetch_bytes().inc(sum(leaf.nbytes for leaf in leaves))
    # one compiled step made every step of the train: one route a kind
    # of layer, one pattern of mixers
    mixer_layers = {kind: int(n) for kind, n in
                    steps[0]["mixer_layers"].items()} if steps else {}
    family_layers: Dict[str, int] = {}
    for kind, n in mixer_layers.items():
        family = KINDS[kind].family
        family_layers[family] = family_layers.get(family, 0) + n
    # of the mixers under a window: a session and head's pairs inside
    # the band and in the blocks its route visits, a layer each
    window_pairs = None
    # of a packed train's rows, by the scope of the layers' kind: the
    # pairs of one session its queries see and the pairs of the block
    # pairs its route multiplied for them, a row, layer and query head
    packed_pairs: Optional[Dict[str, Tuple[int, int]]] = None
    if packed is not None:
        packed_pairs = {}
        trained = packed.ids[np.concatenate(rows)] if rows \
            else packed.ids[:0]
    route = (jax.devices()[0].device_kind, ATTENTION_BLOCK,
             1 if mesh is None else mesh.size)
    for kind, n in mixer_layers.items():
        band = p.held_kind(kind)
        if packed is not None:
            pairs = session_pairs(route[0], trained, band.head_dim,
                                  band.head_dim, band.window, *route[1:])
            packed_pairs[band.scope] = tuple(
                n * new + old for new, old in zip(
                    pairs, packed_pairs.get(band.scope, (0, 0))))
        elif getattr(band, "window", None):
            pairs = band_pairs(route[0], p.max_len, band.head_dim,
                               band.head_dim, band.window, *route[1:])
            window_pairs = tuple(n * new + old for new, old in zip(
                pairs, window_pairs or (0, 0)))
    train_stats.observe_seqrec_record(
        record, targets, rows,
        *("pallas" if steps and steps[0][key] else "xla"
          for key in ("attention_pallas", "linear_attention_pallas",
                      "expert_product_pallas")),
        mixer_layers, family_layers,
        {name: int(n) for name, n in steps[0]["layer_passes"].items()}
        if steps and "layer_passes" in steps[0] else None,
        "rows" if steps and steps[0].get("attention_rows") else "heads",
        "pallas" if steps and steps[0].get("short_conv_pallas") else "xla",
        window_pairs,
        None if packed is None else [[len(packed.sessions[r]) for r in idx]
                                     for idx in rows],
        packed_pairs)
    return SeqRecModel(item_vocab=all_items, params=params, hyper=p,
                       record=record)


def _training_record(steps: List[Dict], rows: List[np.ndarray]) -> Dict:
    """Per-step numbers (host values) -> the record's lists."""
    record = {"rows": [r.tolist() for r in rows],
              "loss": [float(s["loss"]) for s in steps],
              **{key: [{k: float(v) for k, v in s[key].items()}
                       for s in steps] for key in ("grad_norm",
                                                   "update_norm")}}
    for key in ("load", "held_tokens", "dropped", "expert_update_norm",
                "loop_loss", "exit_share", "mtp_loss"):
        if steps and key in steps[0]:
            record[key] = [np.asarray(s[key]).tolist() for s in steps]
    return record
