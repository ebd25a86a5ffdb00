"""Scope tables: a compiled train program publishes which HLO instruction
belongs to which `jax.named_scope` (ops/fn_cache.py, obs/profiler.py), and
device time by instruction joins with them."""

import json
import os

import numpy as np
import pytest

from predictionio_tpu.obs import jax_stats, profiler
from predictionio_tpu.obs.registry import default_registry
from predictionio_tpu.ops import fn_cache


@pytest.fixture(autouse=True)
def tables_in_tmp(tmp_path, monkeypatch):
    """The tables' files under the test's own directory (the program
    reads the variable itself; JAX read it at import and keeps its
    persistent cache off, as conftest set it)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def series(name, **want):
    metric = default_registry().get(name)
    return [(labels, value) for labels, value in
            (metric.samples() if metric is not None else [])
            if all(labels.get(k) == v for k, v in want.items())]


def jax_seconds():
    reg = default_registry()
    return {name: sum(v for _, v in reg.get(name).samples())
            if reg.get(name) is not None else 0.0
            for name in ("pio_jax_trace_seconds_total",
                         "pio_jax_lower_seconds_total")}


def tables_of(family):
    return [t for t in profiler.scope_tables() if t["family"] == family]


# -- the executable's text -> the table --------------------------------------

HLO = '''HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "/x.py"

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(step)/outer/reduce_sum"}
}

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %tanh.1 = f32[8]{0} tanh(%p0), metadata={op_name="jit(step)/outer/inner/tanh"}
  ROOT %mul.1 = f32[8]{0} multiply(%tanh.1, %tanh.1), metadata={op_name="jit(step)/outer/mul"}
}

%fused_computation.1 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p0.1), metadata={op_name="jit(step)/transpose(jvp(outer))/checkpoint/rematted_computation/inner/neg"}
}

%body.2 (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%t), index=1
  %fusion.7 = f32[8]{0:T(128)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(outer))/checkpoint/rematted_computation/inner/neg"}
  %gte.0 = s32[] get-tuple-element(%t), index=0
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %fusion.7)
}

%cond.3 (t.1: (s32[], f32[8])) -> pred[] {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  %c.5 = s32[] constant(4)
  %gte.2 = s32[] get-tuple-element(%t.1), index=0
  ROOT %lt.1 = pred[] compare(%gte.2, %c.5), direction=LT, metadata={op_name="jit(step)/outer/while/cond/lt"}
}

ENTRY %main.4 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/outer/mul"}
  %ragged-dot-none.6 = f32[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}, backend_config={"custom_call_config":{"body":"bW9kdWxl"},"note":"metadata={op_name=\\"jit(step)/outer/x\\"}"}
  %kernel.11 = f32[8]{0} custom-call(%ragged-dot-none.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(outer)/inner/pallas_call"}
  %copy.2 = f32[8]{0} copy(%kernel.11)
  %copy.8 = f32[8]{0} copy(%x)
  %z = s32[] constant(0)
  %tuple.0 = (s32[], f32[8]{0}) tuple(%z, %copy.2)
  %while.5 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond.3, body=%body.2, metadata={op_name="jit(step)/outer/while"}
  %sum.1 = f32[] reduce(%copy.2, %z), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(step)/transpose(jvp(outer))/reduce_sum"}
  ROOT %gte.9 = f32[8]{0} get-tuple-element(%while.5), index=1
}
'''


def test_the_text_of_an_executable_becomes_its_table():
    module, table = profiler.parse_scope_table(HLO, ("outer", "inner"))
    assert module == "jit_step"
    assert table == {
        # a fusion whole by its own metadata; its insides disagree
        "fusion.3": ["outer", "m"],
        # the innermost scope wins, whatever wraps the outer one; a
        # custom call carries its target, as a capture's event does
        "kernel.11_tpu_custom_call": ["inner", ""],
        # what the compiler made and the trace did not takes the scope
        # (and phase) its users agree on: XLA's kernel call for a
        # `ragged_dot`, a layout's copy; one nothing scoped touches has
        # none. A kernel's body, behind `backend_config=`, is not read
        "ragged-dot-none.6_tpu_custom_call": ["inner", "i"],
        "copy.2": ["outer", "ti"],
        "copy.8": ["", ""],
        # a container is marked, its body and condition are covered
        "while.5": ["outer", "c"],
        "fusion.7": ["inner", "tr"],
        "lt.1": ["outer", ""],
        "sum.1": ["outer", "t"],
    }
    # neither a fusion's inside nor a reduction's rule is an operation
    assert "tanh.1" not in table and "add.9" not in table


def test_op_key_names_an_event_as_the_table_names_its_instruction():
    event = ('%flash_attention_pallas_bwd.11 = (bf16[2,16]{1,0:T(8,128)(2,1)}'
             ') custom-call(bf16[2,16]{1,0} %x), custom_call_target='
             '"tpu_custom_call", operand_layout_constraints={}')
    assert profiler.op_key(event) \
        == "flash_attention_pallas_bwd.11_tpu_custom_call"
    assert profiler.op_key("  ROOT %fusion.75 = f32[8]{0} fusion(%p)") \
        == "fusion.75"
    assert profiler.op_key(
        '%custom-call.3 = f32[] custom-call(), custom_call_target="TopK"') \
        == "custom-call.3_TopK"


# -- the join -----------------------------------------------------------------

def test_scope_seconds_on_hand_made_op_times():
    step = {"family": "seqrec_train_step", "module": "jit_step",
            "instructions": {"fusion.3": ["seqrec_experts", "m"],
                             "fusion.4": ["seqrec_experts", "t"],
                             "copy.1": ["", ""],
                             "while.2": ["seqrec_head_loss", "c"],
                             "dot.9": ["seqrec_head_loss", "r"]}}
    train = {"family": "als_train", "module": "jit_train",
             "instructions": {"fusion.3": ["als_gram", ""],
                              "solve.1": ["als_solve", ""]}}
    got = profiler.scope_seconds(
        {"fusion.3": 2.0, "fusion.4": 1.0, "copy.1": 0.25, "while.2": 9.0,
         "dot.9": 0.5, "solve.1": 4.0, "copy-start.7": 0.125}, [step, train])
    assert got == {
        # a name two tables know goes to the first; a mixed fusion
        # counts whole under its own scope; the container is skipped
        "seqrec_train_step": {"seqrec_experts": 3.0, "": 0.25,
                              "seqrec_head_loss": 0.5},
        "als_train": {"als_solve": 4.0},
        None: {None: 0.125}}
    assert profiler.scope_seconds({}, [train]) == {"als_train": {}}
    assert profiler.scope_seconds({"x": 1.0}, []) == {None: {None: 1.0}}


# -- a sequence model's train -------------------------------------------------

@pytest.fixture()
def small_blocks(monkeypatch):
    from predictionio_tpu.models import seqrec
    from predictionio_tpu.ops import linear_attention

    monkeypatch.setattr(seqrec, "ATTENTION_BLOCK", 8)
    monkeypatch.setattr(seqrec, "TOKEN_BLOCK", 12)
    monkeypatch.setattr(seqrec, "LINEAR_KEY_HEADS", 2)
    monkeypatch.setattr(linear_attention, "CHUNK", 8)


def every_mixer_spec(**over):
    """d 64, `remat` on; a convolution layer with the dense feed-forward,
    then a linear-attention, a grouped-query attention and a state-space
    layer with 16 experts top-3 in a latent of 32 and a gated shared
    expert each, and a multi-token-prediction module of one sliding-window
    attention layer."""
    from predictionio_tpu.models import seqrec

    return seqrec.SeqRecParams(**{**dict(
        d_model=64, n_heads=4, n_layers=4, max_len=24, seed=11,
        mixer=("conv", "gdn", "gqa", "ssm"), ffn="moe", first_dense_layers=1,
        ssm=dict(heads=4, head_dim=8, groups=2, state=8, conv_kernel=4,
                 chunk=8), moe_latent_size=32, mtp_layers=("swa",),
        swa=dict(heads=4, window=9, rope_theta=1e4, rotary_dim=16),
        mtp_loss_weight=0.1,
        ffn_width=96, norm="rms", norm_eps=1e-5, positions="rope",
        rope_theta=1e6, tied_head=False, n_kv_heads=2, head_dim=16,
        rotary_dim=4, conv_kernel=3, linear_key_heads=4,
        linear_value_heads=8, linear_key_head_dim=8,
        linear_value_head_dim=8, linear_conv_kernel=4, n_routed_experts=16,
        held_experts=(0, 16), experts_per_token=3, moe_width=24,
        n_shared_experts=1, shared_expert_gate=True,
        router_scoring="sigmoid", bias_update_rate=0.001, remat=True,
        epochs=1, batch_size=2), **over})


SESSIONS = [[f"i{(7 * s + 3 * j) % 53}" for j in range(25)] for s in range(4)]


def test_a_train_publishes_its_steps_table_once_and_compiles_nothing_for_it(
        small_blocks):
    from predictionio_tpu.models import seqrec

    jax_stats.listen_to_compiler()
    # (kept as a label whatever this worker compiled before: past
    # MAX_COMPILE_FUNS names a new one folds into "other")
    jax_stats._compiler_events._funs.add("jit(step)")
    p = every_mixer_spec()

    def step_compiles():
        return sum(n for _, n in series("pio_jax_backend_compile_total",
                                        fun="jit(step)"))

    compiled_before = step_compiles()
    # (by path, not by count: a worker that has published
    # MAX_TABLES_PER_FAMILY tables of the family before drops its oldest)
    before_tables = {t["path"] for t in tables_of("seqrec_train_step")}
    seqrec.train_seqrec(None, SESSIONS, p)
    table, = [t for t in tables_of("seqrec_train_step")
              if t["path"] not in before_tables]
    # no second compile, load or lowering of the program: the compiler's
    # count stands where the step's own dispatch left it, the lowering
    # clock did not move and the looked-up trace is an event of
    # microseconds (JAX reports a cache hit too)
    assert table["compiled"] == 0
    assert step_compiles() == compiled_before + 1
    assert table["seconds"]["compile"] < 0.05
    assert sum(table["seconds"].values()) < 5.0

    # the table: every scope of the step occurs, forward, backward and
    # recomputed apart; loop bodies are in it and containers marked
    rows = table["instructions"]
    assert table["module"] == "jit_step"
    assert {scope for scope, _ in rows.values()} - {""} \
        == set(seqrec.STEP_SCOPES)
    flags_of = {}
    for scope, flags in rows.values():
        flags_of.setdefault(scope, set()).update(flags or "-")
    for scope in ("seqrec_attention", "seqrec_linear_attention",
                  "seqrec_short_conv", "seqrec_experts", "seqrec_ffn",
                  "seqrec_norm", "seqrec_head_loss"):
        # a recomputed instruction keeps its scope
        assert {"t", "r"} <= flags_of[scope], (scope, flags_of[scope])
    assert not {"t", "r"} & flags_of["seqrec_optimizer"]
    assert "c" in flags_of["seqrec_head_loss"]      # the token blocks' loop
    containers = [k for k, (_, flags) in rows.items() if "c" in flags]
    assert containers and all(k.startswith(("while", "call", "conditional"))
                              for k in containers)

    # the file and the two series
    (labels, n), = series(profiler.SCOPE_TABLE_INFO,
                          family="seqrec_train_step", path=table["path"])
    assert labels["module"] == "jit_step" and n == len(rows)
    with open(table["path"]) as f:
        on_disk = json.load(f)
    assert on_disk["instructions"] == rows
    assert on_disk["family"] == "seqrec_train_step"
    assert on_disk["scopes"] == list(seqrec.STEP_SCOPES)
    assert os.path.dirname(table["path"]).endswith(
        os.path.join("cache", "scope_tables"))
    spent = series(profiler.SCOPE_TABLE_SECONDS, family="seqrec_train_step")
    assert spent and spent[0][1] > 0

    # a second train in the process: the same step, no second table
    spent_before = spent[0][1]
    compiles = jax_stats.backend_compile_count()
    seqrec.train_seqrec(None, SESSIONS, p)
    assert tables_of("seqrec_train_step")[-1] is table
    assert {t["path"] for t in tables_of("seqrec_train_step")} \
        <= before_tables | {table["path"]}
    assert jax_stats.backend_compile_count() == compiles
    assert series(profiler.SCOPE_TABLE_SECONDS,
                  family="seqrec_train_step")[0][1] == spent_before


def test_making_the_table_moves_neither_compile_count_nor_lowering_clock():
    """A jitted function with donated arguments behind `mesh_cached_fn`:
    after its first call the table is made from the consumed arguments'
    shapes, and the compiler's counters read what they read before."""
    import jax
    import jax.numpy as jnp

    jax_stats.listen_to_compiler()

    def build():
        def probe_step(w, x):
            with jax.named_scope("probe_outer"):
                y = jnp.tanh(x @ w)
                with jax.named_scope("probe_inner"):
                    y = y * 2.0
            return w - 0.1 * y.T @ x, y.sum()
        return jax.jit(probe_step, donate_argnums=(0,))

    fn = fn_cache.mesh_cached_fn("scope_probe_family", None, ("donated",),
                                 build, scopes=("probe_outer", "probe_inner"))
    w = jnp.ones((16, 16))
    x = jnp.asarray(np.ones((16, 16), np.float32))
    counters = []
    publish = profiler.publish_scope_table

    def noting(*args, **kwargs):
        counters.append((jax_stats.backend_compile_count(), jax_seconds()))
        return publish(*args, **kwargs)

    import unittest.mock

    with unittest.mock.patch.object(profiler, "publish_scope_table", noting):
        before = jax_stats.backend_compile_count()
        w, _ = fn(w, x)
        after_call = jax_stats.backend_compile_count()
        after = jax_seconds()
        w, _ = fn(w, x)
    assert after_call > before and len(counters) == 1
    table, = tables_of("scope_probe_family")
    assert table["compiled"] == 0
    # between the call's return and the text in hand: no compile, no
    # lowering; the trace clock moved by a looked-up trace at most
    assert counters[0][0] == after_call
    assert jax_stats.backend_compile_count() == after_call
    assert after["pio_jax_lower_seconds_total"] \
        == jax_seconds()["pio_jax_lower_seconds_total"]
    assert jax_seconds()["pio_jax_trace_seconds_total"] \
        - after["pio_jax_trace_seconds_total"] < 5e-3
    scopes = {scope for scope, _ in table["instructions"].values()}
    assert "probe_inner" in scopes or "probe_outer" in scopes


def test_a_family_that_names_no_scopes_gets_no_table_file_or_series(
        tmp_path):
    import jax
    import jax.numpy as jnp

    fn = fn_cache.mesh_cached_fn(
        "scope_free_family", None, (), lambda: jax.jit(lambda x: x * 2.0))
    assert float(fn(jnp.ones(()))) == 2.0
    assert not tables_of("scope_free_family")
    assert not series(profiler.SCOPE_TABLE_INFO, family="scope_free_family")
    assert not series(profiler.SCOPE_TABLE_SECONDS,
                      family="scope_free_family")
    assert not os.path.exists(tmp_path / "cache" / "scope_tables") or not [
        name for name in os.listdir(tmp_path / "cache" / "scope_tables")
        if name.startswith("scope_free_family")]


def test_a_table_is_made_with_dispatch_attribution_off(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("PIO_DISPATCH_ATTRIBUTION", "0")
    monkeypatch.setenv("PIO_ANATOMY", "0")

    def build():
        def scoped(x):
            with jax.named_scope("probe_outer"):
                return jnp.tanh(x) * 3.0
        return jax.jit(scoped)

    fn = fn_cache.mesh_cached_fn("scope_unattributed_family", None, (),
                                 build, scopes=("probe_outer",))
    seconds = profiler.dispatch_table().get("scope_unattributed_family")
    fn(jnp.ones((4,)))
    table, = tables_of("scope_unattributed_family")
    assert "probe_outer" in {s for s, _ in table["instructions"].values()}
    assert profiler.dispatch_table().get("scope_unattributed_family") \
        == seconds


def test_an_als_train_publishes_its_table_under_a_mesh(mesh8):
    from predictionio_tpu.models import als

    rng = np.random.default_rng(0)
    n = 3000
    data = als.ALSData.build(
        rng.integers(0, 160, n), rng.integers(0, 96, n),
        rng.integers(1, 6, n).astype(np.float32), 160, 96, n_shards=8)
    before = tables_of(als.TRAIN_FAMILY)
    als.train_als(mesh8, data, als.ALSParams(rank=8, num_iterations=2,
                                             seed=35))
    tables = tables_of(als.TRAIN_FAMILY)
    # one more; a process that has trained eight such programs already
    # (other test files, the same worker) drops its oldest for it
    assert len(tables) == min(len(before) + 1, profiler.MAX_TABLES_PER_FAMILY)
    table = tables[-1]
    assert all(table is not old for old in before)
    assert table["compiled"] == 0 and table["module"] == "jit_train"
    scopes = {scope for scope, _ in table["instructions"].values()}
    assert {"als_init", "als_gram", "als_solve"} <= scopes
    # the sweeps are a loop: its body's instructions are in the table
    assert any("c" in flags and key.startswith("while")
               for key, (_, flags) in table["instructions"].items())


def test_a_process_keeps_a_familys_newest_tables_only():
    hlo = "HloModule jit_f\n\nENTRY %main (x: f32[]) -> f32[] {\n" \
        "  ROOT %neg.1 = f32[] negate(%x), metadata=" \
        '{op_name="jit(f)/probe_outer/neg"}\n}\n'
    for i in range(profiler.MAX_TABLES_PER_FAMILY + 3):
        profiler.publish_scope_table("scope_bounded_family", ("key", i),
                                     ("probe_outer",), hlo, {"text": 0.0})
    kept = tables_of("scope_bounded_family")
    assert len(kept) == profiler.MAX_TABLES_PER_FAMILY
    assert kept[-1]["instructions"] == {"neg.1": ["probe_outer", ""]}
    assert kept[-1]["path"] == profiler.scope_table_path(
        "scope_bounded_family", ("key", profiler.MAX_TABLES_PER_FAMILY + 2))


# -- a capture ----------------------------------------------------------------

def test_a_capture_returns_scopes_for_the_tabled_families(tmp_path):
    import jax
    import jax.numpy as jnp

    def build():
        def scoped(x):
            with jax.named_scope("probe_outer"):
                return jnp.tanh(x) * 3.0
        return jax.jit(scoped)

    fn = fn_cache.mesh_cached_fn("scope_capture_family", None, (), build,
                                 scopes=("probe_outer",))
    fn(jnp.ones((4,)))
    got = profiler.capture(0.05, outdir=str(tmp_path / "trace"))
    families = {t["family"] for t in profiler.scope_tables()}
    assert "scope_capture_family" in families
    # the CPU has no device plane: the families are there, empty
    assert set(got["scopes"]) == families
    assert all(isinstance(v, dict) for v in got["scopes"].values())
    assert got["untabledSeconds"] >= 0.0
    assert {"traceDir", "seconds", "dispatch"} <= set(got)


def test_a_recorded_capture_joins_by_the_program_an_operation_ran_in():
    """The TPU capture the benchmark's tests keep: `jit_topk`'s
    operations under a table made for that module, the same names under
    another module's table untabled."""
    here = os.path.dirname(os.path.abspath(__file__))
    trace = os.path.join(here, "..", "benchmarks", "tests", "data",
                         "small_trace.xplane.pb")
    if not os.path.exists(trace):
        pytest.skip("the recorded capture is not in this checkout")
    import shutil
    import tempfile

    work = tempfile.mkdtemp(prefix="pio-scope-capture-")
    try:
        run = os.path.join(work, "plugins", "profile", "run")
        os.makedirs(run)
        shutil.copy(trace, os.path.join(run, "host.xplane.pb"))
        topk = {"family": "topk", "module": "jit_topk", "instructions": {
            "fusion": ["scores", ""], "custom-call_TopK": ["topk", ""]}}
        got = profiler.capture_scopes(work, [topk])
        assert set(got["scopes"]["topk"]) == {"scores", "topk"}
        assert got["scopes"]["topk"]["topk"] > got["scopes"]["topk"][
            "scores"] > 0
        assert got["untabled_s"] > 0          # copy-start, copy-done
        other = dict(topk, module="jit_other")
        missed = profiler.capture_scopes(work, [other])
        assert missed["scopes"] == {"topk": {}}
        assert missed["untabled_s"] == pytest.approx(
            got["untabled_s"] + sum(got["scopes"]["topk"].values()))
    finally:
        shutil.rmtree(work, ignore_errors=True)
