"""The small configurations of the sequence model that tests/test_seqrec_*.py
hold against the benchmark's plain references (benchmarks/checks/): ONE
record each (`CASES`), the helpers every file used to carry a copy of
(`batch`, `rel`, a record's `small_spec`, `weights`, `ref_spec`), a
module's `model` fixture, which compiles a record's loss with its
gradient, its forward pass and its train step ONCE a file and remembers
what the reference gave, and the tests all the files share, each written
once here and collected in a file under the name it has there.

A new configuration adds a record here, a file that sets `CASE` and
collects what of the shared suite applies, and keeps in that file only
what is its own: its mixer by hand, its `check()` refusals, its fault
controls (ROADMAP.md D17)."""

import contextlib
import dataclasses
import datetime as dt
import functools
import inspect
import json
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.checks import (
    seqrec_conv_reference, seqrec_hybrid_reference, seqrec_looped_reference,
    seqrec_packed_reference, seqrec_reference, seqrec_ssm_reference,
    seqrec_window_reference,
)
from predictionio_tpu.models import seqrec
from predictionio_tpu.ops import linear_attention

VOCAB = 97
YARN = dict(factor=4.0, original_max_len=16, beta_fast=4.0, beta_slow=1.0,
            attention_factor=1.1386294361119891)
NORMS = dict.fromkeys(("ln1", "ln2", "ln_f"), 0.1)
#: a session of 24 takes three attention blocks, a step's 48 tokens four
#: token blocks
BLOCKS = ((seqrec, "ATTENTION_BLOCK", 8), (seqrec, "TOKEN_BLOCK", 12))


def batch(seed=0, rows=2, pad=0, length=24):
    rng = np.random.default_rng(seed)
    s = rng.integers(1, VOCAB, size=(rows, length + 1))
    s[:, :pad] = 0
    return s[:, :-1].astype(np.int32), s[:, 1:].astype(np.int32)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


@dataclasses.dataclass(frozen=True)
class Case:
    """One small configuration: the reference it is held against, the
    fields of its spec, the leaves its weights move off their start (by a
    key of their path: the scale of the noise), the block sizes its file
    runs under, and how close the program comes to the reference."""
    ref: object
    spec: Dict
    moved: Dict[str, float]
    blocks: Tuple = BLOCKS
    length: int = 24
    #: of the loss; of each gradient's largest entry; of a group's update
    loss_tol: float = 2e-6
    grad_tol: float = 2e-5
    update_tol: float = 2e-4
    #: leaves no gradient reaches on either side
    no_gradient: Tuple[str, ...] = ()
    #: the reference's own int8 products: (layer, leaves, how far off)
    int8: Optional[Tuple] = None

    def small_spec(self, **over) -> seqrec.SeqRecParams:
        return seqrec.SeqRecParams(**{**self.spec, "max_len": self.length,
                                      "seed": 11, "remat": True, **over})

    def weights(self, p, seed=3, vocab_multiple=1):
        """The spec's draws, with `moved`'s leaves off their start so that
        they matter."""
        params = seqrec.init_params(np.random.default_rng(seed), VOCAB - 1,
                                    p, vocab_multiple)
        rng = np.random.default_rng(seed + 1)

        def move(path, w):
            for k in path:
                if getattr(k, "key", None) in self.moved:
                    return w + jnp.asarray(
                        rng.normal(size=w.shape) * self.moved[k.key],
                        jnp.float32)
            return w

        return jax.tree_util.tree_map_with_path(move, params)

    def ref_spec(self, p, **over):
        return self.ref.Spec.of(dataclasses.asdict(p), **over)

    @contextlib.contextmanager
    def small_blocks(self):
        """The file's block sizes, for as long as something is traced."""
        with pytest.MonkeyPatch.context() as patch:
            for module, name, value in self.blocks:
                patch.setattr(module, name, value)
            yield


_SWA = dict(heads=8, window=7, rope_theta=10000.0, rotary_dim=8)
_SSM = dict(heads=8, head_dim=8, groups=4, state=16, conv_kernel=4, chunk=8)
CASES = {
    # d 64, 4 heads of nope/rope/v 16/8/16, latent 32, 8 experts top-2 + 1
    # shared, 1 dense + 2 expert layers; a bias that matters: selection
    # must follow score + bias
    "spec": Case(
        seqrec_reference, dict(
            d_model=64, n_heads=4, n_layers=3, mixer="mla", ffn="moe",
            norm="rms", norm_eps=1e-5, positions="rope",
            rope_theta=800000.0, tied_head=False, ffn_width=160,
            first_dense_layers=1, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=32, n_routed_experts=8,
            held_experts=(0, 8), experts_per_token=2, moe_width=48,
            n_shared_experts=1, routed_scaling_factor=2.446,
            bias_update_rate=0.001, balance_loss_alpha=0.001),
        {"router_bias": 0.05}, update_tol=1e-4, int8=(2, ("wo",), 1e-3)),
    # d 64; a period of three gdn layers (4 key heads of 8 serving 8 value
    # heads of 8, a convolution of 4) and one gqa layer (4 query heads of
    # 16 over 2 key/value heads, 4 rotary dimensions); 16 experts top-3 by
    # softmax plus a gated shared one in every layer. Three chunks of the
    # delta rule a session, a linear layer's heads in two groups. The
    # orders of summation differ (the chunked rule against the recurrence,
    # blocked attention, grouped experts, token blocks), and a linear
    # layer's output is normed a head where it can be small, so a
    # gradient's last digits are amplified on their way down: the same
    # program under another chunk reads up to 5e-4 of an array's largest
    # entry from itself
    "hybrid": Case(
        seqrec_hybrid_reference, dict(
            d_model=64, n_heads=4, n_layers=4,
            mixer=("gdn", "gdn", "gdn", "gqa"), ffn="moe",
            norm="rms_zero_centered", norm_eps=1e-6, positions="rope",
            rope_theta=1e7, tied_head=False, n_kv_heads=2, head_dim=16,
            rotary_dim=4, linear_key_heads=4, linear_value_heads=8,
            linear_key_head_dim=8, linear_value_head_dim=8,
            linear_conv_kernel=4, n_routed_experts=16, held_experts=(0, 16),
            experts_per_token=3, moe_width=24, n_shared_experts=1,
            shared_expert_gate=True, router_scoring="softmax"),
        dict(NORMS, q_norm=0.1, k_norm=0.1, o_norm=0.1),
        blocks=BLOCKS + ((seqrec, "LINEAR_KEY_HEADS", 2),
                         (linear_attention, "CHUNK", 8)),
        grad_tol=1e-3, int8=(2, ("w_out", "w_qkvz"), 1e-2)),
    # d 64; a convolution layer with the dense feed-forward, then one
    # period of a gqa layer (8 query heads of 8 = d / heads over 2
    # key/value heads, rotary on the whole head, no gate) and three
    # convolution layers of 3 taps, 16 experts top-4 by sigmoid + bias in
    # each, no shared expert; the head tied
    "conv": Case(
        seqrec_conv_reference, dict(
            d_model=64, n_heads=8, n_layers=5,
            mixer=("conv", "gqa", "conv", "conv", "conv"), ffn="moe",
            first_dense_layers=1, ffn_width=96, norm="rms", norm_eps=1e-5,
            positions="rope", rope_theta=1e6, tied_head=True, n_kv_heads=2,
            head_dim=8, rotary_dim=8, attention_gate=False, conv_kernel=3,
            n_routed_experts=16, held_experts=(0, 16), experts_per_token=4,
            moe_width=24, router_scoring="sigmoid", router_norm_eps=1e-6,
            bias_update_rate=0.001),
        dict(NORMS, q_norm=0.1, k_norm=0.1, router_bias=0.05),
        int8=(3, ("conv_in", "conv_out"), 1e-3)),
    # d 64; two layers of 4 heads of 16 with rotary positions and a SwiGLU
    # of 96, a norm before and after each sub-layer, run four times; an
    # untied head; an exit gate and an entropy term of 0.05
    "looped": Case(
        seqrec_looped_reference, dict(
            d_model=64, n_heads=4, n_layers=2, n_loops=4, mixer="mha",
            ffn="swiglu", ffn_width=96, norm="rms", norm_eps=1e-6,
            post_norm=True, positions="rope", rope_theta=1e6,
            tied_head=False, exit_gate=True, exit_entropy_beta=0.05),
        dict(NORMS, post1=0.1, post2=0.1, exit_gate=0.3),
        int8=(1, ("wqkv", "w_down"), 1e-3)),
    # d 64; a period of one attention layer (8 query heads of 8 over 2
    # key/value heads, no positions, norms or gate), two state-space layers
    # (8 heads of 8 in 4 groups, a state of 16, chunks of 8) and two expert
    # layers (16 experts top-3 of two matrices and a squared ReLU in a
    # latent of 32, a shared expert of 48); a module of one attention and
    # one expert layer; everything held here
    "ssm": Case(
        seqrec_ssm_reference, dict(
            d_model=64, n_heads=8, n_kv_heads=2, head_dim=8, n_layers=5,
            sublayers=("gqa", "moe", "ssm", "moe", "ssm"), ssm=_SSM,
            norm="rms", norm_eps=1e-5, positions="none", qk_norm=False,
            attention_gate=False, tied_head=False, n_routed_experts=16,
            held_experts=(0, 16), experts_per_token=3, moe_width=24,
            expert_act="relu2", moe_latent_size=32, n_shared_experts=2,
            routed_scaling_factor=5.0, mtp_layers=("gqa", "moe"),
            mtp_loss_weight=0.1),
        dict(NORMS, norm=0.1, norm_e=0.1, norm_h=0.1, D=0.1),
        grad_tol=1e-3),
    # d 64; five layers, full, sliding, sliding, sliding, full: the full
    # ones 4 query heads of 8 over 2 key/value heads, rotary on 4 of 8
    # columns at theta 500,000 under YaRN; the sliding ones 8 heads, a
    # window of 7 (the block pair (2, 0) is out), rotary on all 8 at theta
    # 10,000; a gate of one column a head, no q/k norm; a leading dense
    # SwiGLU of 96, then 16 sigmoid-routed experts of 24 top-3 scaled 2.5
    # beside a shared one; everything held here
    "window": Case(
        seqrec_window_reference, dict(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=8, n_layers=5,
            mixer=("gqa", "swa", "swa", "swa"), swa=_SWA, ffn="moe",
            first_dense_layers=1, ffn_width=96, norm="rms", norm_eps=1e-6,
            positions="rope", rope_theta=500000.0, rotary_dim=4,
            rope_scaling=YARN, qk_norm=False, attention_gate="head",
            tied_head=False, n_routed_experts=16, held_experts=(0, 16),
            experts_per_token=3, moe_width=24, n_shared_experts=1,
            routed_scaling_factor=2.5, expert_update_by_expert=True),
        NORMS, grad_tol=1e-3, no_gradient=("router_bias",)),
    # d 64; four layers, sliding, sliding, sliding, full: 4 query heads of
    # 8 over 2 key/value heads, rotary on all 8 columns at theta 500,000,
    # the full one under YaRN, the sliding ones a window of 7; no gate, no
    # q/k norm; 16 softmax-routed experts of 24 top-4 in every layer, no
    # shared one, no dense layer; experts 0-3 held here; rows of 48 packed
    # (six attention blocks a row, a step's 96 tokens eight token blocks)
    "packed": Case(
        seqrec_packed_reference, dict(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=8, n_layers=4,
            mixer=("swa", "swa", "swa", "gqa"),
            swa=dict(heads=4, window=7, rope_theta=500000.0, rotary_dim=8),
            ffn="moe", norm="rms", norm_eps=1e-6, positions="rope",
            rope_theta=500000.0, rotary_dim=8, rope_scaling=YARN,
            qk_norm=False, attention_gate=False, tied_head=False,
            n_routed_experts=16, held_experts=(0, 4), experts_per_token=4,
            moe_width=24, n_shared_experts=0, router_scoring="softmax",
            expert_update_by_expert=True, packing=True),
        NORMS, length=48, loss_tol=1e-5, grad_tol=2e-4,
        no_gradient=("router_bias",)),
}


class Model:
    """A record's spec and weights with its programs, each traced under
    the record's blocks and the highest matmul precision and compiled
    once however many tests call it (padding, a batch's seed are inputs,
    not programs), and what the reference gave for an input, kept."""

    def __init__(self, case: Case, **over):
        self.case, self.over = case, over
        self.p = case.small_spec(**over)
        self.params = case.weights(self.p)
        self._kept: Dict = {}

    def _keep(self, key, make):
        if key not in self._kept:
            self._kept[key] = make()
        return self._kept[key]

    def of(self, **over) -> "Model":
        """The model of this record under other fields, kept too."""
        return self._keep(("of", tuple(sorted(over.items()))), lambda: Model(
            self.case, **{**self.over, **over}))

    def _traced(self, program, *args):
        with self.case.small_blocks(), \
                jax.default_matmul_precision("highest"):
            return program(*args)

    @functools.cached_property
    def _loss(self):
        return jax.jit(lambda params, seqs, targets, *packed:
                       jax.value_and_grad(seqrec._loss_fn, has_aux=True)(
                           params, seqs, targets, self.p, None,
                           packed or None))

    def loss_and_grads(self, seqs, targets, *packed, params=None):
        """((loss, `_loss_fn`'s rest), gradients)."""
        return self._traced(
            self._loss, self.params if params is None else params,
            *(jnp.asarray(t) for t in (seqs, targets, *packed)))

    @functools.cached_property
    def _forward(self):
        def both(params, seqs, max_len):
            p = dataclasses.replace(self.p, max_len=max_len)
            return (seqrec._forward(params, seqs, p)[0],
                    seqrec.forward(params, seqs, p))

        return jax.jit(both, static_argnums=2)

    def _both(self, seqs, params):
        return self._traced(
            self._forward, self.params if params is None else params,
            jnp.asarray(seqs), seqs.shape[1])

    def passes(self, seqs, params=None):
        """Every pass's hidden states (`seqrec._forward`'s first value)
        on sessions of `seqs`' length."""
        return self._both(seqs, params)[0]

    def forward(self, seqs, params=None):
        """`seqrec.forward`, what serving reads, from the same program."""
        return self._both(seqs, params)[1]

    @functools.cached_property
    def optimizer(self):
        return seqrec.make_optimizer(self.p)

    def train_step(self, mesh=None):
        return self._keep(("train_step", mesh), lambda: seqrec.make_train_step(
            mesh, self.p, self.optimizer))

    def step(self, seqs, targets, *packed, params=None, mesh=None):
        """A step from copies of the weights -> (weights after, stats)."""
        params = jax.tree.map(
            jnp.copy, self.params if params is None else params)
        after, _, stats = self._traced(
            self.train_step(mesh), params, self.optimizer.init(params),
            *(jnp.asarray(t) for t in (seqs, targets, *packed)))
        return after, jax.device_get(stats)

    def first_step(self, seed=2):
        """The step on `batch(seed)` and the reference's numbers for it:
        (weights after, stats, reference loss, gradients, rest)."""
        return (*self._keep(("step", seed), lambda: self.step(
            *batch(seed, length=self.case.length))), *self.reference(seed))

    def reference(self, seed=0, pad=0, rows=2, **over):
        """`ref.loss_and_grads` on `batch(seed, rows, pad)` under the
        reference's spec with `over`: compiled once an input."""
        return self._keep(
            (seed, pad, rows, tuple(sorted(over.items()))),
            lambda: self.case.ref.loss_and_grads(
                jax.tree.map(np.asarray, self.params),
                *batch(seed, rows, pad, self.case.length),
                self.case.ref_spec(self.p, **over)))

    def update_norms(self, grads, rest, **over):
        """The reference's first adamw update by group from its own
        gradients -> (by group, by expert or None)."""
        fn, spec = self.case.ref.first_update_norms, self.case.ref_spec(
            self.p, **over)
        theta0 = jax.tree.map(np.asarray, self.params)
        takes_load = "load" in inspect.signature(fn).parameters
        got = fn(theta0, grads, rest, spec) if takes_load \
            else fn(theta0, grads, spec)
        return got if isinstance(got, tuple) else (got, None)


@pytest.fixture(scope="module")
def model(request) -> Model:
    """The module's `CASE`, its programs compiled once for the file."""
    return Model(request.module.CASE)


@pytest.fixture(autouse=True)
def small_blocks(request, monkeypatch):
    """The module's `CASE`'s block sizes around every test of the file (a
    test that wants the program's own calls `monkeypatch.undo()`)."""
    for module, name, value in request.module.CASE.blocks:
        monkeypatch.setattr(module, name, value)


# -- the tests every file shares ---------------------------------------------

def loss_and_every_gradient_match_the_reference(model: Model, pad):
    """float32 on both sides, on the CPU; the orders of summation differ
    (blocked attention, grouped experts, token blocks), which costs a few
    float32 roundings a value: `grad_tol` of each array's largest entry.
    A lower precision anywhere reads `int8`'s floor and more (the
    reference's own int8 products). -> (loss, `_loss_fn`'s rest,
    gradients, the reference's gradients, the reference's rest)."""
    case = model.case
    (loss, aux), grads = model.loss_and_grads(
        *batch(pad=pad, length=case.length))
    want_loss, want_grads, rest = model.reference(pad=pad)
    assert abs(float(loss) - want_loss) < case.loss_tol * want_loss
    got = dict(jax.tree_util.tree_leaves_with_path(grads))
    wanted = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    assert set(got) == set(wanted)
    for path, want in wanted.items():
        if getattr(path[-1], "key", None) in case.no_gradient:
            continue
        assert rel(got[path], want) < case.grad_tol, \
            jax.tree_util.keystr(path)
    # every group of the record
    assert set(seqrec._group_norms(grads)) == set(
        case.ref.group_norms(want_grads))
    if case.int8:
        layer, names, floor = case.int8
        _, low, _ = model.reference(pad=pad, precision="int8")
        for name in names:
            assert rel(low["layers"][layer][name],
                       want_grads["layers"][layer][name]) > floor, name
    return loss, aux, grads, want_grads, rest


def logits_match_the_reference(model: Model):
    seqs, _ = batch(seed=5, rows=1, pad=3)
    params = model.params
    hidden = model.forward(seqs)
    with jax.default_matmul_precision("highest"):
        logits = hidden[0] @ seqrec.head_matrix(params)
        want = model.case.ref.hidden_states(
            params, seqs[0], model.case.ref_spec(model.p))[0] \
            @ (params["emb"].T if model.p.tied_head else params["head"])
    assert logits.shape == (model.case.length, VOCAB)
    assert rel(logits, want) < 1e-5      # float32 roundings


def a_left_padded_session_is_the_unpadded_one(model: Model):
    """Every mixer of the configuration: nothing is written at a padding
    position, an attention layer masks its keys and rotates by distance,
    a convolution's padding positions are zeros before the session."""
    seqs, _ = batch(seed=4, rows=1)
    short = seqs[:, 7:]
    padded = np.concatenate([np.zeros((1, 7), np.int32), short], axis=1)
    whole, alone = model.forward(padded), model.forward(short)
    assert not np.asarray(whole[0, :7]).any()
    np.testing.assert_allclose(whole[0, 7:], alone[0], atol=2e-5)


def a_step_adds_what_the_references_adamw_adds(model: Model, off_group):
    """By parameter group, the norm of step 1's update against the
    reference's adamw step from its own gradients (float32 both sides;
    adamw's first step is -lr g / (|g| + eps), and the few entries whose
    gradient is near eps = 1e-8 feel the gradients' last digits), and
    what a learning rate ten times off reads in `off_group`. -> (weights
    after, stats, the reference's gradients, its rest, its norms)."""
    after, stats, _, grads, rest = model.first_step()
    want, _ = model.update_norms(grads, rest)
    off, _ = model.update_norms(grads, rest, learning_rate=1e-2)
    got = {k: float(v) for k, v in stats["update_norm"].items()}
    assert set(got) == set(want)
    for group, norm in want.items():
        assert abs(got[group] - norm) < model.case.update_tol * norm, group
    assert off[off_group] > 9 * got[off_group]
    return after, stats, grads, rest, want


def a_train_steps_record_against_the_reference_and_the_int8_control(
        model: Model, low_parts):
    """The step's gradient and update norms by group against the
    reference's, the experts' update expert by expert where the step
    reports it, and the control: every matrix product's operands at 8
    bits, the loss and the gradient of every group of `low_parts` leave
    by far more than the program does. -> (weights after, stats, the
    reference's rest, its update norms, by expert)."""
    after, stats, loss, grads, rest = model.first_step()
    update_norms, by_expert = model.update_norms(grads, rest)
    sound = model.case.ref.group_norms(grads)
    for key, want in (("grad_norm", sound), ("update_norm", update_norms)):
        assert set(stats[key]) == set(want)
        for group, norm in want.items():
            assert abs(float(stats[key][group]) - norm) < 2e-4 * norm, group
    if by_expert is not None:
        assert np.allclose(stats["expert_update_norm"], by_expert, rtol=2e-4)
    assert int(np.sum(stats["dropped"])) == 0
    low, low_grads, _ = model.reference(2, precision="int8")
    assert abs(low - loss) > 1e-4 * loss
    low_norms = model.case.ref.group_norms(low_grads)
    assert all(abs(low_norms[g] - n) > 1e-3 * n for g, n in sound.items()
               if g.endswith(low_parts))
    return after, stats, rest, update_norms, by_expert


def the_step_under_a_mesh_is_the_step(model: Model, mesh):
    """Batch over "data", the projections' columns over "model": the
    sharded step's loss and gradient norms are the one-device step's. ->
    (the sharded weights, the sharded step's stats, the one-device
    step's)."""
    params = model.case.weights(model.p, vocab_multiple=2)
    seqs, targets = batch(seed=6, rows=4)
    _, want = model.step(seqs, targets, params=params)
    sharded = seqrec.shard_params(jax.tree.map(jnp.copy, params), mesh)
    _, got = model.step(seqs, targets, params=sharded, mesh=mesh)
    assert abs(float(got["loss"]) - float(want["loss"])) < 1e-5
    for group, norm in want["grad_norm"].items():
        assert abs(float(got["grad_norm"][group]) - float(norm)) \
            < 2e-3 * float(norm), group
    return sharded, got, want


def a_seq_mesh_is_refused_where_a_mixer_does_not_ring(case: Case):
    """The ring takes one key/value head a query head: a train over a
    mesh with a "seq" axis is refused before anything is traced."""
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4),
                axis_names=("data", "seq"))
    with pytest.raises(ValueError, match="ring"):
        seqrec.train_seqrec(mesh, [["a", "b", "c"]] * 4, case.small_spec())


def counted(name, **labels):
    """A counter's (or gauge's) value in the process's registry, 0 before
    anything moved it."""
    from predictionio_tpu.obs.registry import default_registry

    c = default_registry().get(name)
    return c.value(**labels) if c is not None else 0


def sessions(n, length=25):
    """`n` sessions of `length` items out of fifty, two strides."""
    return [[f"i{(3 * s + j * (1 + s % 2)) % 50:02d}" for j in range(length)]
            for s in range(n)]


def the_model_trains_and_serves_from_an_engine_json(case: Case, tmp_path,
                                                    app):
    """`pio train` and `pio deploy`'s predict from a variant file alone:
    the keys of the layer spec reach the model through JSON like the old
    ones, the train's loss falls and the served next item is the one the
    events' order teaches. -> (the algorithm's parameters as the variant
    file holds them, the model as `pio deploy` loads it)."""
    from predictionio_tpu.core.params import engine_params_from_json
    from predictionio_tpu.data import Event
    from predictionio_tpu.data.eventstore import clear_cache
    from predictionio_tpu.engines.sessionrec import (
        AlgorithmParams, DataSourceParams, Query, engine,
    )
    from predictionio_tpu.storage import App, Storage
    from predictionio_tpu.workflow import run_train
    from predictionio_tpu.workflow.train import load_for_deploy

    Storage.configure({
        "sources": {"DB": {"TYPE": "sqlite", "PATH": str(tmp_path / "t.db")}},
        "repositories": {name: {"NAME": "pio", "SOURCE": "DB"}
                         for name in ("METADATA", "EVENTDATA", "MODELDATA")}})
    clear_cache()
    try:
        app_id = Storage.get_meta_data_apps().insert(App(id=0, name=app))
        store = Storage.get_events()
        store.init_channel(app_id)
        t0 = dt.datetime(2026, 1, 1, tzinfo=dt.timezone.utc)
        store.insert_batch([
            Event(event="view", entity_type="user", entity_id=f"u{u}",
                  target_entity_type="item",
                  target_entity_id=f"i{(u + j) % 15:02d}",
                  event_time=t0 + dt.timedelta(minutes=u * 100 + j))
            for u in range(40) for j in range(4 + u % 5)], app_id)
        spec = dataclasses.asdict(case.small_spec(
            max_len=16, epochs=30, batch_size=20, learning_rate=3e-3))
        variant = json.loads(json.dumps({
            "datasource": {"params": {"appName": app}},
            "algorithms": [{"name": "seqrec", "params": spec}]}))
        params = engine_params_from_json(
            variant, DataSourceParams, None, {"seqrec": AlgorithmParams})
        eng = engine()
        instance = run_train(eng, params)
        assert instance.status == "COMPLETED"
        result, _ = load_for_deploy(eng, instance)
        algo, trained = result.algorithms[0], result.models[0]
        assert trained.record["loss"][-1] < trained.record["loss"][0]
        pred = algo.predict(trained,
                            Query(items=["i03", "i04", "i05"], num=3))
        items = [s.item for s in pred.item_scores]
        assert "i06" in items and "i05" not in items
        return variant["algorithms"][0]["params"], trained
    finally:
        Storage.reset()
        clear_cache()
