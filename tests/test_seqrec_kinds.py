"""The sequence model's table of layer kinds (`models/seqrec.KINDS`): every
record has the one shape, holds what its kind alone knows, and a refactor
behind it draws the weights it drew; and the one table of tiny-step pins:
what each older benchmark configuration's first step gave, which a new
field's default may not move. Toy sizes; nothing compiles for a chip."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.core.params import params_from_json
from predictionio_tpu.models import seqrec
from predictionio_tpu.obs import train_stats
from predictionio_tpu.obs.registry import default_registry

BASE = dict(d_model=16, n_heads=4, n_layers=2, max_len=8, seed=11)
RMS = dict(norm="rms", positions="rope")
SSM = dict(heads=4, head_dim=4, groups=2, state=4, conv_kernel=3, chunk=4)
MOE = dict(n_routed_experts=4, held_experts=(1, 3), experts_per_token=2,
           moe_width=8, n_shared_experts=1)
#: one toy spec a mixer kind, and two with expert layers and a module
SPECS = {
    "mha": dict(BASE),
    "mla": dict(BASE, mixer="mla", ffn="swiglu", norm="rms", positions="rope",
                qk_nope_head_dim=4, qk_rope_head_dim=2, v_head_dim=4,
                kv_lora_rank=8, tied_head=False),
    "gqa": dict(BASE, mixer="gqa", ffn="swiglu", norm="rms_zero_centered",
                positions="rope", n_kv_heads=2, head_dim=4, rotary_dim=2),
    "gdn": dict(BASE, **RMS, mixer=("gdn", "gqa"), n_kv_heads=2, head_dim=4,
                rotary_dim=4, attention_gate=False, qk_norm=False,
                linear_key_heads=2, linear_value_heads=4,
                linear_key_head_dim=4, linear_value_head_dim=4,
                linear_conv_kernel=3),
    "conv": dict(BASE, **RMS, mixer="conv", ffn="swiglu", conv_kernel=3,
                 post_norm=True, n_loops=2, exit_gate=True),
    "ssm": dict(BASE, norm="rms", positions="none", mixer="ssm", ssm=SSM,
                ffn_width=24),
    "swa": dict(BASE, **RMS, mixer=("gqa", "swa"), ffn="swiglu",
                n_kv_heads=2, head_dim=4, rotary_dim=2, qk_norm=False,
                attention_gate="head",
                rope_scaling=dict(factor=4.0, original_max_len=4),
                swa=dict(heads=6, window=3, rope_theta=100.0, rotary_dim=4)),
    "moe_mtp": dict(BASE, **RMS, **MOE, mixer="gqa", ffn="moe",
                    first_dense_layers=1, n_kv_heads=2, head_dim=4,
                    rotary_dim=4, shared_expert_gate=True,
                    mtp_layers=("gqa", "moe"), mtp_loss_weight=0.1),
    "latent_relu2": dict(BASE, norm="rms", positions="none", **MOE,
                         sublayers=("ssm", "moe", "gqa", "moe"), n_layers=4,
                         n_kv_heads=2, head_dim=4, qk_norm=False,
                         attention_gate=False, expert_act="relu2",
                         moe_latent_size=8, tensor_ways=2, ssm=SSM,
                         mtp_layers=("ssm", "moe")),
}
#: the spec each of the table's ten kinds is read from here
SPEC_OF = {"mha": "mha", "mla": "mla", "gqa": "gqa", "swa": "swa",
           "gdn": "gdn",
           "conv": "conv", "ssm": "ssm", "gelu": "mha", "swiglu": "mla",
           "moe": "moe_mtp"}


def spec(name, **over) -> seqrec.SeqRecParams:
    return seqrec.SeqRecParams(**{**SPECS[name], **over})


class Reads(dict):
    """A layer's weights that note which of them a layer function read."""

    def __init__(self, leaves):
        super().__init__(leaves)
        self.read = set()

    def __getitem__(self, name):
        self.read.add(name)
        return super().__getitem__(name)

    def __contains__(self, name):
        if super().__contains__(name):
            self.read.add(name)
        return super().__contains__(name)


def draws(rng):
    def dense(n_in, n_out, experts=()):
        return jnp.asarray(rng.normal(size=(*experts, n_in, n_out))
                           * n_in ** -0.5, jnp.float32)

    def uniform(shape, hi):
        return jnp.asarray(rng.uniform(0.0, hi, size=shape), jnp.float32)

    return dense, uniform, lambda width: {
        "scale": jnp.ones((width,), jnp.float32)}


def test_the_table_names_the_ten_kinds_in_the_order_the_messages_give():
    assert tuple(seqrec.KINDS) == seqrec.MIXERS + seqrec.FFNS == (
        "mha", "mla", "gqa", "swa", "gdn", "conv", "ssm", "gelu", "swiglu",
        "moe")
    assert [seqrec._sub_layer(kind) for kind in ("gqa", "moe")] == [
        ("gqa", None), (None, "moe")]
    scopes = {record.scope for record in seqrec.KINDS.values()}
    assert scopes <= set(seqrec.STEP_SCOPES) and len(seqrec.STEP_SCOPES) == 16


@pytest.mark.parametrize("kind", list(seqrec.KINDS))
def test_a_record_draws_the_leaves_it_files_and_reads(kind):
    """`init` gives leaves that `grad_group` files under the record's own
    groups, `apply` reads exactly those, and what `shard_params` splits of
    them are leaves it has."""
    p = spec(SPEC_OF[kind])
    record = p.held_kind(kind)
    assert dataclasses.is_dataclass(record) and record == seqrec.KINDS[
        kind].of(p) and record.role in ("mixer", "ffn")
    leaves = Reads(record.init(p.d_model, *draws(np.random.default_rng(0))))
    assert leaves and set(leaves) <= set(record.grad_groups)
    path = jax.tree_util.tree_flatten_with_path({"layers": [dict(leaves)]})[0]
    groups = {str(keys[2].key): seqrec.grad_group(keys) for keys, _ in path}
    assert groups == {leaf: f"layer0.{record.grad_groups[leaf]}"
                      for leaf in leaves}
    names = {str(getattr(k, "key", k)) for keys, _ in path for k in keys}
    assert set(record.columns) | set(record.rows) <= names
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 8, p.d_model)),
                    jnp.float32)
    key_mask = jnp.ones((2, 8), bool).at[0, :3].set(False)
    out = record.apply(leaves, x, key_mask, p, None)
    if record.role == "ffn":
        out, stats = out
        assert (stats is not None) == record.routed
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())
    assert leaves.read == set(leaves)


@pytest.mark.parametrize("kind,over,message", [
    ("gqa", {"head_dim": 0}, "gqa needs head_dim > 0 and n_kv_heads a "
                             "divisor of n_heads: 0, 2, 4"),
    ("gqa", {"n_kv_heads": 3}, "divisor of n_heads: 4, 3, 4"),
    ("gqa", {"rotary_dim": 3}, "rotary_dim 3 is no even part of head_dim 4"),
    ("gqa", {"rotary_dim": 6}, "rotary_dim 6 is no even part of head_dim 4"),
    ("gdn", {"linear_key_heads": 3}, "gdn needs its five linear_* sizes > 0 "
     "and linear_key_heads a divisor of linear_value_heads: (3, 4, 4, 4, 3)"),
    ("gdn", {"linear_conv_kernel": 0}, "(2, 4, 4, 4, 0)"),
    ("conv", {"conv_kernel": 0}, "conv needs conv_kernel >= 1: 0"),
    ("ssm", {"ssm": dict(SSM, groups=3)}, "ssm needs its six sizes > 0 and "
                                          "groups a divisor of heads"),
    ("ssm", {"ssm": None}, "ssm needs its six sizes > 0"),
])
def test_a_record_refuses_its_own_bad_sizes_in_the_words_it_had(
        kind, over, message):
    p = spec(SPEC_OF[kind], **over)
    with pytest.raises(ValueError) as by_record:
        seqrec.KINDS[kind].of(p).check()
    assert message in str(by_record.value)
    with pytest.raises(ValueError) as by_spec:
        p.check()
    assert str(by_spec.value) == str(by_record.value)


@pytest.mark.parametrize("kind", list(seqrec.KINDS))
def test_a_kind_told_its_share_halves_what_it_holds(kind):
    if not seqrec.KINDS[kind].share:
        with pytest.raises(ValueError, match=f"'{kind}'.* hold no share"):
            spec(SPEC_OF[kind], tensor_ways=2).check()
        return
    whole = seqrec.KINDS[kind].of(spec("latent_relu2"))
    half = whole.held(2)
    halved = {f.name for f in dataclasses.fields(whole)
              if getattr(half, f.name) != getattr(whole, f.name)}
    assert halved == {"gqa": {"heads", "kv_heads"}, "ssm": {"heads", "groups"},
                      "moe": {"shared_width"}}[kind]
    assert all(2 * getattr(half, name) == getattr(whole, name)
               for name in halved)
    assert whole.divides(2) and not whole.divides(3)
    assert spec("latent_relu2").held_kind(kind) == half
    with pytest.raises(ValueError, match="do not divide") as refusal:
        spec("latent_relu2", tensor_ways=3).check()
    assert whole.share in str(refusal.value)


@pytest.mark.parametrize("over,message", [
    ({"norm": "layer"}, "norm 'layer' does not go with the mixers "
                        "['gdn', 'gqa']"),
    ({"positions": "learned"}, "positions 'learned' does not go with the "
                               "mixers ['gdn', 'gqa']"),
    ({"positions": "none"}, "positions 'none' goes with the mixers gqa and "
                            "ssm, not ['gdn']"),
    ({"mixer": ("gdn", "flash")}, "unknown mixer ['flash']: expected among "
     "('mha', 'mla', 'gqa', 'swa', 'gdn', 'conv', 'ssm')"),
    ({"ffn": "relu"}, "unknown ffn 'relu': expected one of ('gelu', "
                      "'swiglu', 'moe')"),
    ({"sublayers": ("gqa", "mlp")}, "unknown sublayers ['mlp']: expected "
     "among ('mha', 'mla', 'gqa', 'swa', 'gdn', 'conv', 'ssm', 'gelu', "
     "'swiglu', 'moe')"),
])
def test_what_crosses_kinds_is_refused_in_the_words_it_had(over, message):
    with pytest.raises(ValueError) as refusal:
        spec("gdn", **over).check()
    assert str(refusal.value) == message


# -- the weights a spec draws ----------------------------------------------

#: sha256 over the leaves of `init_params` in `jax.tree` order (path,
#: shape, values at half precision: a draw out of its order moves every
#: value, another CPU's last bit of a log or an exp moves none), read at
#: the commit before the kinds became records (6978d77, PR 42: this file's
#: `init_digest` run there with that tree on the path)
INIT_DIGESTS = {
    "mha-host": "26918fc2dcb51ffe", "mha-device": "325b70fea81fa145",
    "mla-host": "c5945e4754a920bb", "mla-device": "5611e90d24159c0b",
    "gqa-host": "e67e9dcaaa3e6442", "gqa-device": "e976c1cd2e42e70e",
    "gdn-host": "798b54ebeffb2232", "gdn-device": "7578361539774cf8",
    "conv-host": "297588d979c250c6", "conv-device": "6bf668567125b318",
    "ssm-host": "7c73423501e2c2c1", "ssm-device": "6deb84bcb81cea95",
    "moe_mtp-host": "69472fd81141bbbc", "moe_mtp-device": "8d34fcef27d9cd43",
    "latent_relu2-host": "1e1f8590c30ea7d2",
    "latent_relu2-device": "1eecd2f7fab1b884",
}
#: `seqrec_fingerprint` of the same specs over one vocabulary and two
#: sessions at that commit: a checkpoint it took resumes here
FINGERPRINTS = {
    "mha": "9eb9495924b5b9606ea3a556e84d4c26",
    "mla": "4fff709eee929e756467bfffd618134b",
    "gqa": "bd13153ee06c48e369024353bb1c4910",
    "gdn": "7c0e49a0a291127c4414b18a745747ca",
    "conv": "209a1f9416aa41d3820e4b812612ea4f",
    "ssm": "fe7e99dd8eb343e798a10f7cffcd3988",
    "moe_mtp": "ef4bace74b932b076771e9c266070088",
    "latent_relu2": "94e4dd2212203b563b2f0f6ac735668e",
}


def init_digest(name: str, device_init: bool) -> str:
    p = spec(name, device_init=device_init)
    params = seqrec.init_params(np.random.default_rng(p.seed), 9, p)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(repr(leaf.shape).encode())
        h.update(np.asarray(leaf).astype(np.float16).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(INIT_DIGESTS))
def test_a_spec_draws_the_weights_it_drew(case):
    name, path = case.rsplit("-", 1)
    assert init_digest(name, path == "device") == INIT_DIGESTS[case]


@pytest.mark.parametrize("name", sorted(FINGERPRINTS))
def test_a_run_keeps_the_identity_it_had(name):
    vocab = np.asarray(["a", "b", "c"], dtype=object)
    p = spec(name)
    assert seqrec.seqrec_fingerprint(
        vocab, p, [["a", "b"], ["c", "a", "b"]]) == FINGERPRINTS[name]
    assert "attention_impl" not in dict(p.spec_key())
    assert dict(p.spec_key(memory=False)) == {
        k: v for k, v in p.spec_key() if k != "remat"}


def test_the_spec_has_one_field_fewer_and_refuses_the_old_key():
    assert len(dataclasses.fields(seqrec.SeqRecParams)) == 57 + len(
        seqrec.LATER_FIELDS)
    assert seqrec.MEMORY_FIELDS == ("remat",)
    with pytest.raises(ValueError, match="attentionImpl"):
        params_from_json({"attentionImpl": "ring"}, seqrec.SeqRecParams)


# -- what the program already ran ------------------------------------------

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                       "configs")
#: the first step's loss and whole gradient norm of each sequence
#: configuration's tiny section on one seeded batch, read at the commit
#: before the configuration after it came (PRs 39, 44, 47): a new field's
#: default changes nothing. The ONE table: the next `model_config` adds
#: the row of the configuration that was the newest, and no copy.
TINY_STEPS = {
    "seqrec-kimi-vl-a3b-ep8": (5.165351390838623, 7.646289342187191),
    "seqrec-qwen3-next-80b-a3b-ep16": (5.0000810623168945, 76.57708056258434),
    "seqrec-lfm2-24b-a2b-ep8": (5.029688835144043, 4.87177540506),
    "seqrec-ouro-2.6b-pp8": (5.02148962020874, 3.437611412089871),
    "seqrec-nemotron3-super-120b-a12b-tp8ep64": (5.584339618682861,
                                                 4.24192990355414),
    "seqrec-laguna-xs2-ep8": (5.065154552459717, 6.6178168454284085),
}


@pytest.mark.parametrize("name", sorted(TINY_STEPS))
def test_the_older_configurations_tiny_steps_give_the_losses_they_gave(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        tiny = json.load(f)["tiny"]
    p = seqrec.SeqRecParams(**tiny["algorithm_params"])
    assert not p.packing
    params = seqrec.init_params(None, tiny["n_items"], p)
    optimizer = seqrec.make_optimizer(p)
    seqs = np.random.default_rng(40).integers(
        1, tiny["n_items"] + 1, size=(p.batch_size, p.max_len + 1))
    seqs[0, :7] = 0
    _, _, stats = seqrec.make_train_step(None, p, optimizer)(
        params, optimizer.init(params), jnp.asarray(seqs[:, :-1], jnp.int32),
        jnp.asarray(seqs[:, 1:], jnp.int32))
    norms = jax.device_get(stats["grad_norm"])
    loss, norm = TINY_STEPS[name]
    assert float(stats["loss"]) == pytest.approx(loss, rel=1e-6)
    assert float(np.sqrt(sum(float(v) ** 2 for v in norms.values()))) \
        == pytest.approx(norm, rel=1e-5)
    # and a step says of itself what its spec asks for, no more
    assert ("mtp_loss" in stats) == bool(p.mtp_layers)
    assert ("layer_passes" in stats) == (
        p.n_loops > 1 or bool(p.sublayers or p.mtp_layers))
    assert ("expert_update_norm" in stats) == (
        p.expert_act == "relu2" or p.expert_update_by_expert)


def test_every_configuration_but_the_newest_has_its_row_of_pins():
    """A `model_config` PR adds a row for the configuration that was the
    newest before it (whose own tests held its tiny step until then); the
    newest's is held by the reference in its own file."""
    configs = {name[:-len(".json")] for name in os.listdir(CONFIGS)
               if name.startswith("seqrec-")}
    assert set(TINY_STEPS) <= configs
    assert len(configs - set(TINY_STEPS)) == 1, sorted(
        configs - set(TINY_STEPS))


# -- the counters --------------------------------------------------------------

def test_tokens_are_counted_by_the_family_a_record_names():
    """`observe_seqrec_record` is handed layer passes by family and names
    no mixer; a kind without a family (the state-space mixer) moves the
    mixers' counter alone."""
    families = {name: kind.family for name, kind in seqrec.KINDS.items()
                if kind.role == "mixer"}
    assert families == {"mha": "attention", "mla": "attention",
                        "gqa": "attention", "swa": "attention",
                        "gdn": "linear_attention",
                        "conv": "short_conv", "ssm": None}

    def counter(name, **labels):
        return default_registry().counter(
            name, "", labelnames=tuple(labels)).value(**labels)

    names = [("pio_train_seqrec_attention_tokens_total", "xla"),
             ("pio_train_seqrec_linear_attention_tokens_total", "pallas"),
             ("pio_train_seqrec_linear_attention_chain_tokens_total",
              "pallas"),
             ("pio_train_seqrec_short_conv_chain_tokens_total", "xla")]
    before = [counter(name, impl=impl) for name, impl in names]
    mixers = counter("pio_train_seqrec_mixer_tokens_total", mixer="ssm")
    targets = np.ones((4, 8), np.int32)
    train_stats.observe_seqrec_record(
        {"loss": [1.0]}, targets, [np.arange(4)], "xla", "pallas", "xla",
        {"gqa": 1, "mla": 1, "gdn": 3, "conv": 2, "ssm": 5},
        {"attention": 2, "linear_attention": 3, "short_conv": 2, None: 5})
    after = [counter(name, impl=impl) for name, impl in names]
    assert [b - a for a, b in zip(before, after)] == [32, 96, 96, 64]
    assert counter("pio_train_seqrec_mixer_tokens_total",
                   mixer="ssm") - mixers == 5 * 32
