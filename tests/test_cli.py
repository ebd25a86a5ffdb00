"""`pio` CLI lifecycle test — the quickstart CI analog at the CLI layer.

The reference's integration harness drives the full lifecycle through the
console (tests/pio_tests/scenarios/quickstart_test.py:33-95: app new ->
import -> train -> query with asserted itemScores; basic_app_usecases.py:
app/channel/accesskey CRUD). This runs the same surface in-process via
click's CliRunner against a temp sqlite store.
"""

import json

import numpy as np
import pytest
from click.testing import CliRunner

from predictionio_tpu.cli.main import cli


@pytest.fixture()
def clienv(tmp_path, monkeypatch):
    """Point the env-var registry at a temp sqlite db, like pio-env.sh."""
    from predictionio_tpu.data.eventstore import clear_cache
    from predictionio_tpu.storage import Storage

    for k, v in {
        "PIO_STORAGE_SOURCES_DB_TYPE": "sqlite",
        "PIO_STORAGE_SOURCES_DB_PATH": str(tmp_path / "pio.db"),
        "PIO_STORAGE_REPOSITORIES_METADATA_NAME": "pio",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_NAME": "pio",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "DB",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_NAME": "pio",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "DB",
    }.items():
        monkeypatch.setenv(k, v)
    Storage.reset()
    clear_cache()
    yield tmp_path
    Storage.reset()
    clear_cache()


def _ok(result):
    assert result.exit_code == 0, result.output
    return result.output


def test_cli_full_lifecycle(clienv, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    r = CliRunner()

    out = _ok(r.invoke(cli, ["version"]))
    assert out.strip()
    _ok(r.invoke(cli, ["status"]))

    # app + accesskey + channel CRUD (basic_app_usecases.py surface)
    out = _ok(r.invoke(cli, ["app", "new", "cliapp", "--access-key", "CK"]))
    assert "cliapp" in out and "CK" in out
    assert "cliapp" in _ok(r.invoke(cli, ["app", "list"]))
    assert "CK" in _ok(r.invoke(cli, ["accesskey", "list"]))
    _ok(r.invoke(cli, ["app", "channel-new", "cliapp", "side"]))
    assert "side" in _ok(r.invoke(cli, ["app", "show", "cliapp"]))

    # import: JSON-lines events (FileToEvents.scala:40 analog)
    rng = np.random.default_rng(0)
    events_file = tmp_path / "events.json"
    with open(events_file, "w") as f:
        for _ in range(600):
            u, i = rng.integers(0, 25), rng.integers(0, 30)
            f.write(json.dumps({
                "event": "rate", "entityType": "user", "entityId": f"u{u}",
                "targetEntityType": "item", "targetEntityId": f"i{i}",
                "properties": {"rating": float(rng.integers(1, 6))},
                "eventTime": "2026-01-02T03:04:05.000Z"}) + "\n")
    out = _ok(r.invoke(cli, ["import", "--appname", "cliapp",
                             "--input", str(events_file)]))
    assert "Imported 600 events" in out

    # scaffold + train (quickstart_test.py:33-95 analog)
    _ok(r.invoke(cli, ["template", "get", "recommendation", "."]))
    variant = json.loads((tmp_path / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "cliapp"
    variant["algorithms"][0]["params"].update(
        {"rank": 6, "num_iterations": 5})
    (tmp_path / "engine.json").write_text(json.dumps(variant))
    out = _ok(r.invoke(cli, ["train"]))
    assert "Training completed" in out
    # the resolved training solver is echoed (README "Training kernel")
    assert "ALS solver full (block size 16)" in out

    # the train registered release v1 (deploy/ registry surface)
    out = _ok(r.invoke(cli, ["releases"]))
    assert "v1" in out and "REGISTERED" in out
    assert "Finished listing 1 release(s)" in out
    out = _ok(r.invoke(cli, ["releases", "--status", "rolled_back"]))
    assert "Finished listing 0 release(s)" in out

    # batch scoring (BatchPredict.scala:71 analog)
    queries = tmp_path / "queries.json"
    queries.write_text("\n".join(
        json.dumps({"user": f"u{u}", "num": 3}) for u in range(5)))
    preds = tmp_path / "preds.json"
    out = _ok(r.invoke(cli, ["batchpredict", "--input", str(queries),
                             "--output", str(preds)]))
    assert "Wrote 5 predictions" in out
    lines = [json.loads(ln) for ln in preds.read_text().splitlines()]
    assert len(lines) == 5
    for ln in lines:
        assert len(ln["prediction"]["itemScores"]) == 3   # quickstart assert

    # release selection + knobs: scoring with the registered release v1
    # at a forced chunk size must answer the same
    preds2 = tmp_path / "preds2.json"
    out = _ok(r.invoke(cli, ["batchpredict", "--input", str(queries),
                             "--output", str(preds2), "--release", "v1",
                             "--chunk-size", "2",
                             "--output-format", "jsonl"]))
    assert "Scoring with release v1" in out
    assert "Wrote 5 predictions" in out
    lines2 = [json.loads(ln) for ln in preds2.read_text().splitlines()]
    # same instance, so the same items in the same order (scores may
    # differ in the last float32 bits across BLAS batch shapes)
    assert ([[s["item"] for s in ln["prediction"]["itemScores"]]
             for ln in lines2]
            == [[s["item"] for s in ln["prediction"]["itemScores"]]
                for ln in lines])
    out = r.invoke(cli, ["batchpredict", "--input", str(queries),
                         "--output", str(preds2), "--release", "v99"])
    assert out.exit_code != 0 and "not found" in out.output

    # export round-trips the imported events
    exported = tmp_path / "export.json"
    out = _ok(r.invoke(cli, ["export", "--appname", "cliapp",
                             "--output", str(exported), "--format", "json"]))
    n = len([ln for ln in exported.read_text().splitlines() if ln.strip()])
    assert n == 600


def test_cli_compact_ttl(clienv, tmp_path):
    """`pio compact --appname --ttl-days` runs the retention sweep and
    echoes the stats (README 'Ingest hardening')."""
    import datetime as dt

    from predictionio_tpu.data.event import Event, UTC
    from predictionio_tpu.data.eventstore import resolve_app
    from predictionio_tpu.storage import Storage

    r = CliRunner()
    _ok(r.invoke(cli, ["app", "new", "compactapp"]))
    app_id, _ = resolve_app("compactapp", None)
    store = Storage.get_events()
    now = dt.datetime.now(tz=UTC)
    store.insert_batch([Event(
        event="view", entity_type="user", entity_id=f"u{i}",
        event_time=now - dt.timedelta(days=30)) for i in range(4)], app_id)
    keep = store.insert_batch([Event(
        event="view", entity_type="user", entity_id="fresh",
        event_time=now)], app_id)
    out = _ok(r.invoke(cli, ["compact", "--appname", "compactapp",
                             "--ttl-days", "7"]))
    assert "Compacted app" in out
    assert '"removed_rows": 4' in out
    assert [e.event_id for e in store.find(app_id)] == keep
    res = r.invoke(cli, ["compact", "--appname", "ghost"])
    assert res.exit_code == 1


def test_cli_import_requires_app(clienv, tmp_path):
    r = CliRunner()
    bad = tmp_path / "nope.json"
    bad.write_text("")
    res = r.invoke(cli, ["import", "--appname", "ghost",
                         "--input", str(bad)])
    assert res.exit_code == 1
    assert "ghost" in res.output or "ERROR" in res.output


def test_cli_eval_sweep(clienv, tmp_path, monkeypatch):
    """`pio eval <Evaluation> <ParamsGenerator>` (Console.scala:232):
    the user-module reflection path + best.json output."""
    monkeypatch.chdir(tmp_path)
    monkeypatch.syspath_prepend(str(tmp_path))
    r = CliRunner()
    _ok(r.invoke(cli, ["app", "new", "evalapp", "--access-key", "EK"]))

    rng = np.random.default_rng(1)
    events_file = tmp_path / "ev.json"
    with open(events_file, "w") as f:
        for _ in range(400):
            u, i = rng.integers(0, 20), rng.integers(0, 25)
            f.write(json.dumps({
                "event": "rate", "entityType": "user", "entityId": f"u{u}",
                "targetEntityType": "item", "targetEntityId": f"i{i}",
                "properties": {"rating": float(rng.integers(1, 6))}}) + "\n")
    _ok(r.invoke(cli, ["import", "--appname", "evalapp",
                       "--input", str(events_file)]))

    (tmp_path / "my_eval.py").write_text(
        "from predictionio_tpu.core.evaluation import ("
        "Evaluation, EngineParamsGenerator)\n"
        "from predictionio_tpu.engines.recommendation import ("
        "engine, default_engine_params, PrecisionAtK, DataSourceParams)\n"
        "\n\n"
        "class MyEval(Evaluation):\n"
        "    def __init__(self):\n"
        "        super().__init__(engine=engine(), metric=PrecisionAtK(k=3))\n"
        "\n\n"
        "class MyParams(EngineParamsGenerator):\n"
        "    def _params(rank):\n"
        "        p = default_engine_params('evalapp', rank=rank,\n"
        "                                  num_iterations=3)\n"
        "        p.data_source_params.eval_params = {'kFold': 2,\n"
        "                                            'queryNum': 3}\n"
        "        return p\n"
        "    engine_params_list = [_params(4), _params(6)]\n")

    out = _ok(r.invoke(cli, ["eval", "my_eval.MyEval", "my_eval.MyParams"]))
    assert "Evaluation completed" in out
    best = json.loads((tmp_path / "best.json").read_text())
    assert best["algorithms"][0]["params"]["rank"] in (4, 6)


def test_cli_deploy_serves_and_stops(clienv, tmp_path, monkeypatch):
    """`pio deploy` as a REAL process (CreateServer.scala:109 analog):
    bind, answer /queries.json with itemScores, undeploy via /stop."""
    import os
    import socket
    import subprocess
    import sys as _sys
    import time
    import urllib.request

    monkeypatch.chdir(tmp_path)
    r = CliRunner()
    _ok(r.invoke(cli, ["app", "new", "depapp", "--access-key", "DK"]))
    rng = np.random.default_rng(2)
    events_file = tmp_path / "ev.json"
    with open(events_file, "w") as f:
        for _ in range(400):
            u, i = rng.integers(0, 20), rng.integers(0, 25)
            f.write(json.dumps({
                "event": "rate", "entityType": "user", "entityId": f"u{u}",
                "targetEntityType": "item", "targetEntityId": f"i{i}",
                "properties": {"rating": float(rng.integers(1, 6))}}) + "\n")
    _ok(r.invoke(cli, ["import", "--appname", "depapp",
                       "--input", str(events_file)]))
    _ok(r.invoke(cli, ["template", "get", "recommendation", "."]))
    variant = json.loads((tmp_path / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "depapp"
    variant["algorithms"][0]["params"].update({"rank": 4,
                                               "num_iterations": 3})
    (tmp_path / "engine.json").write_text(json.dumps(variant))
    _ok(r.invoke(cli, ["train"]))

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the child runs from tmp_path: put the checkout on its path so the
    # test does not depend on the package being pip-installed
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    # the server's durable telemetry lands under PIO_HOME: keep it here
    env["PIO_HOME"] = str(tmp_path / "pio_home")
    # serve through the quantized kernel: the deploy must echo the
    # resolved scorer mode and /deploy/status.json must mirror it
    env["PIO_SCORER_MODE"] = "fused_int8"
    proc = subprocess.Popen(
        [_sys.executable, "-m", "predictionio_tpu.cli.main", "deploy",
         "--port", str(port), "--accesskey", "DK"],
        cwd=tmp_path, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    try:
        body = None
        for _ in range(120):                   # server + jax cold start
            time.sleep(1)
            if proc.poll() is not None:
                raise AssertionError(
                    f"deploy died: {proc.stdout.read()[-2000:]}")
            try:
                req = urllib.request.Request(
                    f"http://localhost:{port}/queries.json",
                    data=json.dumps({"user": "u1", "num": 3}).encode(),
                    headers={"Content-Type": "application/json"})
                body = json.loads(urllib.request.urlopen(req, timeout=5)
                                  .read())
                break
            except OSError:
                continue
        assert body and len(body["itemScores"]) == 3, body
        status = json.loads(urllib.request.urlopen(
            f"http://localhost:{port}/deploy/status.json",
            timeout=5).read())
        assert status["scorer"]["mode"] == "fused_int8", status
        # undeploy via /stop with the access key (CreateServer.scala:635)
        req = urllib.request.Request(
            f"http://localhost:{port}/stop?accessKey=DK", data=b"")
        urllib.request.urlopen(req, timeout=5)
        proc.wait(timeout=30)
        out = proc.stdout.read()
        assert "Scoring kernel fused_int8" in out, out[-2000:]
    finally:
        if proc.poll() is None:
            proc.kill()
