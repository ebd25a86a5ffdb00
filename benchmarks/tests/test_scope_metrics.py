"""The thirteen per-layer metrics that read device time by the program's
own `jax.named_scope` names (readers/scope_device_time.py): their files
load through manifest.py and are listed with their cells, and the reader
reduces a hand-made evidence document, gives up where programs without a
table could sit under a wrong scope, and returns None (never raises) on
a program that publishes no table."""
import json

import pytest

from benchmarks.lib import layer_readers, manifest
from benchmarks.readers import scope_device_time

SEQ = ["kimivl-a3b-ep8.train", "qwen3next-a3b-ep16.train",
       "lfm2-a2b-ep8.train"]
KIMI, QWEN, LFM2 = SEQ
ALS = ["ml20m-r64.train"]
#: metric -> (the scope it reads, its cells)
STEP_METRICS = {
    "step_scope_ms.attention": ("seqrec_attention", SEQ),
    "step_scope_ms.linear_attention": ("seqrec_linear_attention", [QWEN]),
    "step_scope_ms.short_conv": ("seqrec_short_conv", [LFM2]),
    "step_scope_ms.router": ("seqrec_router", SEQ),
    "step_scope_ms.experts": ("seqrec_experts", SEQ),
    "step_scope_ms.shared_expert": ("seqrec_shared_expert", [KIMI, QWEN]),
    "step_scope_ms.ffn": ("seqrec_ffn", [KIMI, LFM2]),
    "step_scope_ms.head_loss": ("seqrec_head_loss", SEQ),
    "step_scope_ms.optimizer": ("seqrec_optimizer", SEQ),
}
SWEEP_METRICS = {
    "sweep_scope_ms.gram": ("als_gram", ALS),
    "sweep_scope_ms.reg": ("als_reg", ALS),
    "sweep_scope_ms.solve": ("als_solve", ALS),
}
NAMED = "scope_named_pct.train"


def read(name, ev):
    return layer_readers.read(ev, manifest.load_layer_reader(name))


def test_the_thirteen_are_listed_last_with_their_cells_and_load():
    bench = manifest.load_benchmark()
    assert manifest.check(bench) == []
    want = {**STEP_METRICS, **SWEEP_METRICS, NAMED: (None, ALS + SEQ)}
    assert [m["name"] for m in bench["per_layer"][-13:]] == list(want)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name, (scope, cells) in want.items():
        doc = manifest.load_layer_reader(name)
        entry = listed[name]
        for key in ("layer", "moves", "unit", "better", "source"):
            assert doc[key] == entry[key], (name, key)
        assert entry["workloads"] == cells, name
        assert entry["source"] == "device_trace"
        assert entry["moves"] == "train_wall_s"
        assert doc["reader"]["kind"] == "scope_device_time"
        assert doc["reader"].get("scope") == scope
    # a scope's metric is read in a cell only where the model has the
    # layer, and every cell of a metric reports what it moves
    walls = next(m for m in bench["end_to_end"]
                 if m["name"] == "train_wall_s")["workloads"]
    assert all(set(cells) <= set(walls) for _, cells in want.values())


@pytest.fixture()
def step_table(tmp_path):
    """A step's table as the program writes it, and an ALS train's."""
    step = {"family": "seqrec_train_step", "module": "jit_step",
            "scopes": ["seqrec_attention", "seqrec_experts"],
            "instructions": {
                "flash_attention_pallas_fwd.3_tpu_custom_call":
                    ["seqrec_attention", "r"],
                "fusion.10": ["seqrec_attention", "t"],
                "ragged-dot-none.4_tpu_custom_call":
                    ["seqrec_experts", "i"],
                "fusion.3": ["seqrec_experts", "m"],
                "copy.9": ["", ""],
                "while.2": ["seqrec_head_loss", "c"],
                "fusion.77": ["seqrec_head_loss", "tr"]}}
    train = {"family": "als_train", "module": "jit_train",
             "scopes": ["als_gram", "als_reg", "als_solve"],
             "instructions": {
                 "fusion.75": ["als_gram", ""],
                 "cholesky_solve_pallas.1_tpu_custom_call":
                     ["als_solve", ""],
                 "copy.1": ["", ""], "while.8": ["", "c"]}}
    paths = {}
    for table in (step, train):
        path = tmp_path / f"{table['family']}.json"
        path.write_text(json.dumps(table))
        paths[table["family"]] = str(path)
    return paths


def evidence(paths, family="seqrec_train_step", other_s=0.05):
    """What child.py hands run.py, cut to what this reader reads: a
    traced train of 8 steps of 0.7 s (or one `jit_train` of 3 s), and
    `other_s` of programs without a table."""
    info = [[{"family": fam, "module": "jit_step" if "seqrec" in fam
              else "jit_train", "path": path}, 7.0]
            for fam, path in paths.items()]
    if family == "seqrec_train_step":
        modules = [["jit_step(99)", 10.0 + i, 0.7] for i in range(8)]
        ops = [["flash_attention_pallas_fwd.3_tpu_custom_call", 96, 0.6],
               ["fusion.10", 8, 0.2], ["fusion.3", 8, 0.4],
               ["ragged-dot-none.4_tpu_custom_call", 8, 1.2],
               ["copy.9", 8, 0.1], ["while.2", 8, 3.0],
               ["fusion.77", 64, 0.7], ["copy-start.5", 3, 0.01]]
    else:
        modules = [["jit_train(7)", 12.0, 3.0]]
        ops = [["fusion.75", 40, 2.4], ["copy.1", 40, 0.12],
               ["cholesky_solve_pallas.1_tpu_custom_call", 40, 0.48],
               ["while.8", 1, 3.0]]
    modules.append(["jit__normal(5)", 9.0, other_s])
    return {"registry_after": {scope_device_time.TABLE_INFO: info},
            "shapes": {"num_iterations": 20},
            "trace": {"ops": ops, "modules": modules, "jobs": [[9.0, 12.0]]}}


def test_a_steps_scopes_per_step(step_table):
    ev = evidence(step_table)
    # (0.6 + 0.2) s over 8 steps; a mixed fusion and an inherited kernel
    # call count whole under their scope; the container is skipped
    assert read("step_scope_ms.attention", ev) == pytest.approx(100.0)
    assert read("step_scope_ms.experts", ev) == pytest.approx(200.0)
    assert read("step_scope_ms.head_loss", ev) == pytest.approx(87.5)
    # the program ran and nothing of it lies under the scope: 0
    assert read("step_scope_ms.router", ev) == 0.0
    # 3.1 s of 3.2 under a scope; the transfer no table knows is outside
    assert read(NAMED, ev) == pytest.approx(100.0 * 3.1 / 3.2)
    # the ALS metrics find no program of theirs in this window
    assert read("sweep_scope_ms.gram", ev) is None


def test_a_trains_scopes_per_half_sweep(step_table):
    ev = evidence(step_table, family="als_train")
    assert read("sweep_scope_ms.gram", ev) == pytest.approx(60.0)
    assert read("sweep_scope_ms.solve", ev) == pytest.approx(12.0)
    assert read("sweep_scope_ms.reg", ev) == 0.0
    assert read(NAMED, ev) == pytest.approx(100.0 * 2.88 / 3.0)
    assert read("step_scope_ms.attention", ev) is None
    ev["shapes"] = {}
    assert read("sweep_scope_ms.gram", ev) is None


def test_programs_without_a_table_over_two_percent_silence_the_reader(
        step_table):
    """Names repeat between programs and the reduced trace sums by name,
    so the other programs' device time bounds what may sit under a wrong
    scope: 2% of the tabled program's 5.6 s is 0.112 s."""
    assert read("step_scope_ms.attention",
                evidence(step_table, other_s=0.11)) is not None
    for name in ("step_scope_ms.attention", NAMED):
        assert read(name, evidence(step_table, other_s=0.12)) is None


@pytest.mark.parametrize("name", sorted(
    {**STEP_METRICS, **SWEEP_METRICS, NAMED: None}))
def test_nothing_to_read_is_none_and_never_raises(step_table, name):
    full = evidence(step_table)
    # no trace; a program from before the tables; a table file gone
    assert read(name, {**full, "trace": None}) is None
    assert read(name, {**full, "registry_after": {}}) is None
    assert read(name, {"trace": full["trace"]}) is None
    gone = evidence({fam: path + ".gone"
                     for fam, path in step_table.items()})
    assert read(name, gone) is None
    # no event of a tabled program in the window
    idle = evidence(step_table)
    idle["trace"]["modules"] = [["jit__normal(5)", 9.0, 0.05]]
    assert read(name, idle) is None


def test_a_program_without_scope_seconds_reads_nothing(step_table,
                                                       monkeypatch):
    """The parent's `obs/profiler` has no `scope_seconds`: the driver
    lays these files over it and the line leaves the metrics out."""
    from predictionio_tpu.obs import profiler

    monkeypatch.delattr(profiler, "scope_seconds")
    assert read("step_scope_ms.attention", evidence(step_table)) is None
