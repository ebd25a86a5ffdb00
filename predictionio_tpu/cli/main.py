"""`pio` CLI entry point.

Command surface mirrors the reference console (Console.scala:134-623):
app/accesskey/channel management, train, deploy, eval, batchpredict,
eventserver, import/export, status. Training runs in-process (no
spark-submit analog; SURVEY.md section 7 design mapping).
"""

from __future__ import annotations

import json
import sys

import click

from predictionio_tpu import __version__


@click.group()
def cli():
    """predictionio_tpu — TPU-native ML server framework."""
    from predictionio_tpu.utils.device import enable_compile_cache

    enable_compile_cache()


@cli.command()
def version():
    """Print framework version (Console.scala:134)."""
    click.echo(__version__)


@cli.command()
@click.option("--fleet", "fleet_path", default=None,
              help="Show the merged fleet observability of a sharded run: "
                   "the <output>.fleet.json a batchpredict merge commits "
                   "(or the output path itself).")
def status(fleet_path):
    """Verify storage configuration (Console.scala:435, Management.scala:99);
    with --fleet, print a sharded run's merged per-process metric view."""
    if fleet_path:
        _print_fleet(fleet_path)
        return
    from predictionio_tpu.storage import Storage
    click.echo("[INFO] Inspecting predictionio_tpu installation...")
    click.echo(f"[INFO] Version {__version__}")
    try:
        Storage.verify_all_data_objects()
    except Exception as e:
        click.echo(f"[ERROR] Unable to connect to all storage backends: {e}")
        sys.exit(1)
    click.echo("[INFO] All storage backends are properly configured.")
    click.echo("[INFO] Your system is all ready to go.")


def _print_fleet(path):
    """The merged fleet view: per-process counters, exact fleet totals,
    and the trace ids spanning the run. A DIRECTORY path is read as a
    durable-telemetry root instead (obs/fleet.history_reader): the
    merged per-process tsdb stores, one summary line per series."""
    import os

    if os.path.isdir(path):
        _print_fleet_history(path)
        return
    if not path.endswith(".fleet.json") and not os.path.exists(path):
        path = f"{path}.fleet.json"
    elif os.path.isfile(f"{path}.fleet.json"):
        path = f"{path}.fleet.json"
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        click.echo(f"[ERROR] cannot read fleet view {path}: {e}")
        sys.exit(1)
    click.echo(f"[INFO] Fleet view {path}: "
               f"{len(doc.get('processes', []))} process(es) "
               f"{doc.get('processes')}")
    totals = doc.get("counterTotals", {})
    metrics = doc.get("metrics", {})
    for name in sorted(totals):
        click.echo(f"[INFO] {name} fleet total: {totals[name]:g}")
        for sample in metrics.get(name, {}).get("samples", []):
            labels = sample.get("labels", {})
            proc = labels.get("process", "?")
            rest = {k: v for k, v in labels.items() if k != "process"}
            suffix = f" {rest}" if rest else ""
            click.echo(f"[INFO]   process {proc}{suffix}: "
                       f"{sample.get('value'):g}")
    trace_ids = []
    for t in doc.get("traces", []):
        tid = t.get("traceId")
        if tid and tid not in trace_ids:
            trace_ids.append(tid)
    for tid in trace_ids:
        spans = [t for t in doc.get("traces", [])
                 if t.get("traceId") == tid]
        procs = sorted({t.get("process", "?") for t in spans})
        click.echo(f"[INFO] trace {tid}: {len(spans)} span(s) across "
                   f"processes {procs}")


def _print_fleet_history(root):
    """Fleet-wide history summary over a telemetry root: the merged
    per-process stores (one `process` label per service dir)."""
    from predictionio_tpu.obs import fleet

    reader = fleet.history_reader(root)
    by_process = {}
    for info in reader.series():
        if not info.points:
            continue
        proc = info.labels.get("process", "?")
        count, newest = by_process.get(proc, (0, 0))
        by_process[proc] = (count + 1,
                            max(newest, info.points[-1][0]))
    if not by_process:
        click.echo(f"[INFO] No telemetry stores under {root}.")
        return
    click.echo(f"[INFO] Telemetry root {root}: "
               f"{len(by_process)} process store(s)")
    import datetime as _dt

    for proc, (count, newest) in sorted(by_process.items()):
        when = _dt.datetime.fromtimestamp(newest / 1000.0)
        click.echo(f"[INFO]   {proc}: {count} series, newest sample "
                   f"{when.strftime('%Y-%m-%d %H:%M:%S')}")
    events = reader.events()[-10:]
    for _ts, e in events:
        click.echo(f"[INFO]   event {e.get('kind')} "
                   f"proc={e.get('process', '?')} "
                   f"trace={(e.get('traceId') or '-')[:12]}")


# ---------------------------------------------------------------------------
# app management (commands/App.scala:31-363)
# ---------------------------------------------------------------------------

@cli.group()
def app():
    """Manage apps (Console.scala:452-517)."""


@app.command("new")
@click.argument("name")
@click.option("--id", "app_id", type=int, default=0, help="Preferred app id.")
@click.option("--description", default=None)
@click.option("--access-key", default="", help="Use this access key instead of generating one.")
def app_new(name, app_id, description, access_key):
    from predictionio_tpu.storage import AccessKey, App, Storage
    apps = Storage.get_meta_data_apps()
    if apps.get_by_name(name):
        click.echo(f"[ERROR] App {name} already exists. Aborting.")
        sys.exit(1)
    new_id = apps.insert(App(id=app_id, name=name, description=description))
    if new_id is None:
        click.echo("[ERROR] Unable to create new app.")
        sys.exit(1)
    Storage.get_events().init_channel(new_id)
    key = Storage.get_meta_data_access_keys().insert(
        AccessKey(key=access_key, appid=new_id, events=()))
    if key is None:
        click.echo(f"[ERROR] Access key {access_key} already exists. Aborting.")
        Storage.get_events().remove_channel(new_id)
        Storage.get_meta_data_apps().delete(new_id)
        sys.exit(1)
    click.echo("[INFO] Created a new app:")
    click.echo(f"[INFO]         Name: {name}")
    click.echo(f"[INFO]           ID: {new_id}")
    click.echo(f"[INFO] Access Key: {key}")


@app.command("list")
def app_list():
    from predictionio_tpu.storage import Storage
    apps = Storage.get_meta_data_apps().get_all()
    keys = Storage.get_meta_data_access_keys()
    click.echo(f"[INFO] {'Name':<20} | {'ID':<4} | Access Key")
    for a in sorted(apps, key=lambda x: x.name):
        for k in keys.get_by_appid(a.id) or [None]:
            key = k.key if k else ""
            click.echo(f"[INFO] {a.name:<20} | {a.id:<4} | {key}")
    click.echo(f"[INFO] Finished listing {len(apps)} app(s).")


@app.command("show")
@click.argument("name")
def app_show(name):
    from predictionio_tpu.storage import Storage
    a = Storage.get_meta_data_apps().get_by_name(name)
    if a is None:
        click.echo(f"[ERROR] App {name} does not exist. Aborting.")
        sys.exit(1)
    click.echo(f"[INFO]     App Name: {a.name}")
    click.echo(f"[INFO]       App ID: {a.id}")
    click.echo(f"[INFO]  Description: {a.description or ''}")
    for k in Storage.get_meta_data_access_keys().get_by_appid(a.id):
        events = ",".join(k.events) if k.events else "(all)"
        click.echo(f"[INFO]   Access Key: {k.key} | {events}")
    for c in Storage.get_meta_data_channels().get_by_appid(a.id):
        click.echo(f"[INFO]      Channel: {c.name} ({c.id})")


@app.command("delete")
@click.argument("name")
@click.option("--force", "-f", is_flag=True)
def app_delete(name, force):
    from predictionio_tpu.storage import Storage
    a = Storage.get_meta_data_apps().get_by_name(name)
    if a is None:
        click.echo(f"[ERROR] App {name} does not exist. Aborting.")
        sys.exit(1)
    if not force and not click.confirm(
            f"Delete app {name} and ALL its data?"):
        click.echo("[INFO] Aborted.")
        return
    events = Storage.get_events()
    for c in Storage.get_meta_data_channels().get_by_appid(a.id):
        events.remove_channel(a.id, c.id)
        Storage.get_meta_data_channels().delete(c.id)
    events.remove_channel(a.id)
    for k in Storage.get_meta_data_access_keys().get_by_appid(a.id):
        Storage.get_meta_data_access_keys().delete(k.key)
    Storage.get_meta_data_apps().delete(a.id)
    click.echo(f"[INFO] App {name} deleted.")


@app.command("data-delete")
@click.argument("name")
@click.option("--channel", default=None)
@click.option("--all", "delete_all", is_flag=True)
@click.option("--force", "-f", is_flag=True)
def app_data_delete(name, channel, delete_all, force):
    from predictionio_tpu.storage import Storage
    a = Storage.get_meta_data_apps().get_by_name(name)
    if a is None:
        click.echo(f"[ERROR] App {name} does not exist. Aborting.")
        sys.exit(1)
    if not force and not click.confirm(f"Delete data of app {name}?"):
        click.echo("[INFO] Aborted.")
        return
    events = Storage.get_events()
    if delete_all or channel is None:
        events.remove_channel(a.id)
        events.init_channel(a.id)
        click.echo(f"[INFO] Deleted data of app {name} (default channel).")
    if channel is not None or delete_all:
        channels = Storage.get_meta_data_channels().get_by_appid(a.id)
        if channel is not None and channel not in [c.name for c in channels]:
            click.echo(f"[ERROR] Channel {channel} does not exist. Aborting.")
            sys.exit(1)
        for c in channels:
            if delete_all or c.name == channel:
                events.remove_channel(a.id, c.id)
                events.init_channel(a.id, c.id)
                click.echo(f"[INFO] Deleted data of channel {c.name}.")


@app.command("channel-new")
@click.argument("app_name")
@click.argument("channel_name")
def app_channel_new(app_name, channel_name):
    from predictionio_tpu.storage import Channel, Storage
    a = Storage.get_meta_data_apps().get_by_name(app_name)
    if a is None:
        click.echo(f"[ERROR] App {app_name} does not exist. Aborting.")
        sys.exit(1)
    try:
        cid = Storage.get_meta_data_channels().insert(
            Channel(id=0, name=channel_name, appid=a.id))
    except ValueError as e:
        click.echo(f"[ERROR] {e}")
        sys.exit(1)
    if cid is None:
        click.echo(f"[ERROR] Channel {channel_name} already exists.")
        sys.exit(1)
    Storage.get_events().init_channel(a.id, cid)
    click.echo(f"[INFO] Created channel {channel_name} ({cid}).")


@app.command("channel-delete")
@click.argument("app_name")
@click.argument("channel_name")
@click.option("--force", "-f", is_flag=True)
def app_channel_delete(app_name, channel_name, force):
    from predictionio_tpu.storage import Storage
    a = Storage.get_meta_data_apps().get_by_name(app_name)
    if a is None:
        click.echo(f"[ERROR] App {app_name} does not exist. Aborting.")
        sys.exit(1)
    matched = [c for c in Storage.get_meta_data_channels().get_by_appid(a.id)
               if c.name == channel_name]
    if not matched:
        click.echo(f"[ERROR] Channel {channel_name} does not exist.")
        sys.exit(1)
    if not force and not click.confirm(
            f"Delete channel {channel_name} and its data?"):
        click.echo("[INFO] Aborted.")
        return
    Storage.get_events().remove_channel(a.id, matched[0].id)
    Storage.get_meta_data_channels().delete(matched[0].id)
    click.echo(f"[INFO] Deleted channel {channel_name}.")


# ---------------------------------------------------------------------------
# accesskey management (commands/AccessKey.scala)
# ---------------------------------------------------------------------------

@cli.group()
def accesskey():
    """Manage access keys (Console.scala:554-592)."""


@accesskey.command("new")
@click.argument("app_name")
@click.option("--key", default="")
@click.option("--event", "events", multiple=True,
              help="Allowed event names (default: all).")
def accesskey_new(app_name, key, events):
    from predictionio_tpu.storage import AccessKey, Storage
    a = Storage.get_meta_data_apps().get_by_name(app_name)
    if a is None:
        click.echo(f"[ERROR] App {app_name} does not exist. Aborting.")
        sys.exit(1)
    k = Storage.get_meta_data_access_keys().insert(
        AccessKey(key=key, appid=a.id, events=tuple(events)))
    if k is None:
        click.echo("[ERROR] Unable to create access key.")
        sys.exit(1)
    click.echo(f"[INFO] Created new access key: {k}")


@accesskey.command("list")
@click.argument("app_name", required=False)
def accesskey_list(app_name):
    from predictionio_tpu.storage import Storage
    keys = Storage.get_meta_data_access_keys()
    if app_name:
        a = Storage.get_meta_data_apps().get_by_name(app_name)
        if a is None:
            click.echo(f"[ERROR] App {app_name} does not exist. Aborting.")
            sys.exit(1)
        listing = keys.get_by_appid(a.id)
    else:
        listing = keys.get_all()
    for k in listing:
        events = ",".join(k.events) if k.events else "(all)"
        click.echo(f"[INFO] {k.key} | app {k.appid} | {events}")
    click.echo(f"[INFO] Finished listing {len(listing)} access key(s).")


@accesskey.command("delete")
@click.argument("key")
def accesskey_delete(key):
    from predictionio_tpu.storage import Storage
    Storage.get_meta_data_access_keys().delete(key)
    click.echo(f"[INFO] Deleted access key {key}.")


# ---------------------------------------------------------------------------
# train / deploy / eval / batchpredict (commands/Engine.scala)
# ---------------------------------------------------------------------------

def _load_engine_variant(variant_path):
    """Read engine.json and resolve the factory + params
    (CreateWorkflow.scala:65 + WorkflowUtils.getEngine:53 parity)."""
    import os

    from predictionio_tpu.core.base import load_class

    if not os.path.exists(variant_path):
        click.echo(f"[ERROR] {variant_path} does not exist. Aborting.")
        sys.exit(1)
    with open(variant_path) as f:
        variant = json.load(f)
    factory_path = variant.get("engineFactory")
    if not factory_path:
        click.echo(f"[ERROR] {variant_path} has no engineFactory. Aborting.")
        sys.exit(1)
    factory = load_class(factory_path)
    engine = factory() if callable(factory) else factory.apply()
    engine_params = engine.engine_params_from_json(variant)
    return (engine, engine_params, factory_path,
            variant.get("id", "default"), variant)


@cli.command()
@click.option("--variant", "-v", default="engine.json",
              help="Engine variant JSON (engine.json).")
@click.option("--batch", default="", help="Batch label.")
@click.option("--skip-sanity-check", is_flag=True)
@click.option("--stop-after-read", is_flag=True)
@click.option("--stop-after-prepare", is_flag=True)
@click.option("--mesh-shape", default=None,
              help="Device mesh shape, e.g. 8 or 4,2.")
@click.option("--mesh-axes", default=None, help="Mesh axis names, e.g. data,model.")
@click.option("--checkpoint-dir", default=None,
              help="Mid-training checkpoint/resume directory.")
@click.option("--checkpoint-interval", default=10, type=int,
              help="Iterations/epochs between snapshots.")
def train(variant, batch, skip_sanity_check, stop_after_read,
          stop_after_prepare, mesh_shape, mesh_axes, checkpoint_dir,
          checkpoint_interval):
    """Train an engine instance (Console.scala:179, CoreWorkflow.runTrain)."""
    from predictionio_tpu.utils import configure_logging
    from predictionio_tpu.workflow import WorkflowParams, run_train

    configure_logging()

    engine, engine_params, factory_path, variant_id, _ = \
        _load_engine_variant(variant)
    # echo the resolved ALS training solver for every ALS-backed
    # algorithm (engine.json "solver" section + PIO_ALS_SOLVER /
    # PIO_ALS_BLOCK_SIZE overrides, README "Training kernel")
    from predictionio_tpu.utils.server_config import als_solver_config
    for algo_name, algo_params in engine_params.algorithm_params_list:
        if hasattr(algo_params, "solver"):
            try:
                mode, block = als_solver_config(
                    getattr(algo_params, "solver", None))
            except ValueError as e:
                click.echo(f"[ERROR] Algorithm '{algo_name}': {e}. "
                           "Aborting.")
                sys.exit(1)
            click.echo(f"[INFO] Algorithm '{algo_name}': ALS solver "
                       f"{mode} (block size {block}).")
    runtime_conf = {}
    if mesh_shape:
        runtime_conf["mesh_shape"] = mesh_shape
    if mesh_axes:
        runtime_conf["mesh_axes"] = mesh_axes
    if checkpoint_dir:
        runtime_conf["checkpoint_dir"] = checkpoint_dir
        runtime_conf["checkpoint_interval"] = str(checkpoint_interval)
    wp = WorkflowParams(
        batch=batch, skip_sanity_check=skip_sanity_check,
        stop_after_read=stop_after_read,
        stop_after_prepare=stop_after_prepare,
        runtime_conf=runtime_conf)
    from predictionio_tpu.core.engine import (
        StopAfterPrepareInterruption, StopAfterReadInterruption,
    )
    try:
        instance = run_train(engine, engine_params,
                             engine_factory=factory_path,
                             engine_variant=variant_id, workflow_params=wp)
    except StopAfterReadInterruption:
        click.echo("[INFO] Training interrupted by --stop-after-read.")
        return
    except StopAfterPrepareInterruption:
        click.echo("[INFO] Training interrupted by --stop-after-prepare.")
        return
    click.echo(f"[INFO] Training completed. Engine instance: {instance.id}")


@cli.command()
@click.option("--variant", "-v", default="engine.json")
@click.option("--ip", default="localhost")
@click.option("--port", default=8000, type=int)
@click.option("--engine-instance-id", default=None,
              help="Deploy a specific instance instead of the latest.")
@click.option("--release", "release_selector", default=None,
              help="Deploy a specific release (id, version number or vN) "
                   "from `pio releases`.")
@click.option("--feedback", is_flag=True, help="Record query/prediction events.")
@click.option("--event-server-app", default=None,
              help="App name for feedback events.")
@click.option("--accesskey", default=None,
              help="Key required for /stop, /reload and the deploy API.")
@click.option("--log-url", default=None,
              help="POST serving errors to this URL "
                   "(CreateServer remoteLog).")
@click.option("--log-prefix", default="",
              help="Prefix prepended to remote log payloads.")
def deploy(variant, ip, port, engine_instance_id, release_selector, feedback,
           event_server_app, accesskey, log_url, log_prefix):
    """Deploy the latest COMPLETED instance (Console.scala:260,
    CreateServer.scala:109), or a pinned release via --release."""
    from predictionio_tpu.deploy.releases import resolve_release
    from predictionio_tpu.server.query_server import run_query_server
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.utils import configure_logging
    from predictionio_tpu.workflow.train import load_for_deploy

    configure_logging()

    engine, _, factory_path, variant_id, _vj = _load_engine_variant(variant)
    instances = Storage.get_meta_data_engine_instances()
    release = None
    if release_selector:
        release = resolve_release(Storage.get_meta_data_releases(),
                                  factory_path, "1", variant_id,
                                  release_selector)
        if release is None:
            click.echo(f"[ERROR] Release {release_selector} not found "
                       "(see `pio releases`). Aborting.")
            sys.exit(1)
        instance = instances.get(release.instance_id)
        if instance is None or instance.status != "COMPLETED":
            click.echo(f"[ERROR] Release v{release.version} points at "
                       f"instance {release.instance_id}, which is not "
                       "deployable. Aborting.")
            sys.exit(1)
    elif engine_instance_id:
        instance = instances.get(engine_instance_id)
        if instance is None or instance.status != "COMPLETED":
            click.echo(f"[ERROR] Engine instance {engine_instance_id} is not "
                       "deployable. Aborting.")
            sys.exit(1)
    else:
        instance = instances.get_latest_completed(
            factory_path, "1", variant_id)
        if instance is None:
            click.echo("[ERROR] No COMPLETED engine instance found. "
                       "Run `pio train` first. Aborting.")
            sys.exit(1)
    if release is None:
        release = _release_of_instance(factory_path, variant_id, instance.id)
    click.echo(f"[INFO] Deploying engine instance {instance.id}"
               + (f" (release v{release.version})" if release else "")
               + f" at {ip}:{port}")
    # online fold-in knobs: env > engine.json "foldin" > server.json
    from predictionio_tpu.utils.server_config import (
        foldin_config, scorer_config, telemetry_config,
    )
    fic = foldin_config((_vj or {}).get("foldin"))
    # durable telemetry rides the same chain (README "Fleet console")
    tcfg = telemetry_config((_vj or {}).get("telemetry"))
    # scoring-kernel knobs ride the same chain (README "Scoring kernel");
    # echoed like the ALS-solver line so the operator sees what the box
    # will actually serve with
    scfg = scorer_config((_vj or {}).get("scorer"))
    if scfg.mode == "exact":
        click.echo("[INFO] Scoring kernel exact (fused modes via "
                   'engine.json {"scorer": {"mode": ...}} or '
                   "PIO_SCORER_MODE)")
    else:
        click.echo(f"[INFO] Scoring kernel {scfg.mode} (tile "
                   f"{scfg.tile_items} items"
                   + (f", shortlist {scfg.shortlist}"
                      if scfg.mode == "twostage" else "")
                   + f", parity floor recall@10 >= {scfg.min_recall:g})")
    if fic.enabled:
        click.echo(f"[INFO] Online fold-in enabled: apply interval "
                   f"{fic.apply_interval_s:g}s, max pending "
                   f"{fic.max_pending} rows")
    else:
        click.echo("[INFO] Online fold-in disabled (enable via engine.json "
                   '{"foldin": {"enabled": true}} or PIO_FOLDIN=1)')
    result, ctx = load_for_deploy(engine, instance)
    # claim the device NOW: a server that cannot get its chip (one chip
    # serves one process) must die here with JAX's reason, not at its
    # first device-lane query
    import jax

    from predictionio_tpu.utils.device import describe_devices

    click.echo(f"[INFO] Serving on {describe_devices(jax.devices())}")
    run_query_server(engine, result, instance, ctx, ip=ip, port=port,
                     feedback=feedback, feedback_app_name=event_server_app,
                     access_key=accesskey, log_url=log_url,
                     log_prefix=log_prefix, release=release,
                     foldin_config=fic, scorer_config=scfg,
                     telemetry_config=tcfg)


@cli.command()
@click.option("--tenant", "-t", "tenant_specs", multiple=True, required=True,
              help="NAME=VARIANT_PATH, repeatable: co-host the latest "
                   "COMPLETED instance of each variant as tenant NAME.")
@click.option("--ip", default="localhost")
@click.option("--port", default=8800, type=int)
@click.option("--accesskey", default=None,
              help="Key guarding every tenant's /stop, /reload and "
                   "deploy API.")
def multiserve(tenant_specs, ip, port, accesskey):
    """Serve N engine variants from ONE process under one device-memory
    budget (server/multitenant.py): per-tenant routes at
    /t/NAME/queries.json, LRU warm eviction/reload under
    PIO_MT_DEVICE_BUDGET_BYTES, per-tenant int8/bf16 scorer residency,
    and SLO-burn admission control."""
    from predictionio_tpu.server.multitenant import (
        TenantSpec, run_multitenant_server,
    )
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.utils.server_config import (
        foldin_config, scorer_config,
    )
    from predictionio_tpu.workflow.train import load_for_deploy

    specs = []
    instances = Storage.get_meta_data_engine_instances()
    for entry in tenant_specs:
        name, sep, variant_path = entry.partition("=")
        if not sep or not name or not variant_path:
            click.echo(f"[ERROR] --tenant wants NAME=VARIANT_PATH, got "
                       f"{entry!r}. Aborting.")
            sys.exit(1)
        engine, _, factory_path, variant_id, _vj = \
            _load_engine_variant(variant_path)
        instance = instances.get_latest_completed(
            factory_path, "1", variant_id)
        if instance is None:
            click.echo(f"[ERROR] Tenant {name!r}: no COMPLETED engine "
                       f"instance for {variant_path}. Run `pio train` "
                       "first. Aborting.")
            sys.exit(1)
        release = _release_of_instance(factory_path, variant_id, instance.id)
        scfg = scorer_config((_vj or {}).get("scorer"))
        result, ctx = load_for_deploy(engine, instance)
        click.echo(f"[INFO] Tenant {name!r}: instance {instance.id}"
                   + (f" (release v{release.version})" if release else "")
                   + f", scorer {scfg.mode}")
        specs.append(TenantSpec(
            name=name, engine=engine, train_result=result,
            instance=instance, ctx=ctx, release=release,
            scorer_config=scfg,
            foldin_config=foldin_config((_vj or {}).get("foldin")),
            slo=(_vj or {}).get("slo")))
    click.echo(f"[INFO] Hosting {len(specs)} tenant(s) at {ip}:{port}")
    run_multitenant_server(specs, ip=ip, port=port, access_key=accesskey)


@cli.command()
@click.option("--variant", "-v", default="engine.json")
@click.option("--ip", default="localhost")
@click.option("--port", default=None, type=int,
              help="Router port (default PIO_ROUTER_PORT / server.json).")
@click.option("--replicas", default=None, type=int,
              help="Query-server replicas to spawn (default "
                   "PIO_ROUTER_REPLICAS / server.json).")
@click.option("--replica-url", "replica_urls", multiple=True,
              help="Front an EXISTING replica instead of spawning "
                   "(repeatable); disables the spawner.")
@click.option("--accesskey", default=None,
              help="Key forwarded to spawned replicas' deploy APIs.")
def router(variant, ip, port, replicas, replica_urls, accesskey):
    """Serve a replicated fleet behind one router (server/router.py):
    spawn N `pio deploy` replicas via the worker-env contract (one
    trace id spans router -> replica -> device), spread queries with
    the error-diffusion splitter, sequence fleet cutovers, and
    autoscale on the SLO burn signal when server.json enables it."""
    import os
    import subprocess

    from predictionio_tpu.server.router import run_router
    from predictionio_tpu.utils.server_config import router_config

    cfg = router_config()
    if port is not None:
        cfg.port = port
    if replicas is not None:
        cfg.replicas = max(1, replicas)

    spawn = None
    if not replica_urls:
        from predictionio_tpu.parallel.distributed import worker_env

        def spawn(rank):
            """One replica = one `pio deploy` subprocess on
            base_port + rank, carrying the router's trace context and
            the PIO_PROCESS_ID/PIO_NUM_PROCESSES contract."""
            from predictionio_tpu.server.router import ReplicaHandle

            port_r = cfg.base_port + rank
            argv = [sys.executable, "-m", "predictionio_tpu.cli.main",
                    "deploy", "--variant", variant, "--ip", ip,
                    "--port", str(port_r)]
            if accesskey:
                argv += ["--accesskey", accesskey]
            env = worker_env(rank, max(cfg.replicas, rank + 1),
                             base=dict(os.environ))
            proc = subprocess.Popen(argv, env=env)
            click.echo(f"[INFO] Spawned replica {rank} (pid {proc.pid}) "
                       f"on {ip}:{port_r}")
            return ReplicaHandle(rank=rank,
                                 url=f"http://{ip}:{port_r}",
                                 proc=proc)

    click.echo(f"[INFO] Router starting at {ip}:{cfg.port} over "
               + (f"{len(replica_urls)} existing replica(s)"
                  if replica_urls else f"{cfg.replicas} replica(s)"))
    run_router(config=cfg, ip=ip, spawn=spawn,
               replica_urls=replica_urls)


@cli.command()
@click.option("--scenario", "-s", "scenario_path", default=None,
              help="Scenario JSON (loadtest/scenario.py schema); "
                   "omit to run the built-in example scenario.")
@click.option("--example", "show_example", is_flag=True,
              help="Print an example scenario file and exit.")
@click.option("--dir", "workdir", default=None,
              help="Fleet working directory (default: a temp dir, "
                   "removed afterwards).")
@click.option("--report", "report_path", default=None,
              help="Write the verdict JSON here (default "
                   "PIO_LOADTEST_REPORT_DIR/<scenario>.json when the "
                   "knob is set, else stdout only).")
@click.option("--json", "as_json", is_flag=True,
              help="Print the full report JSON instead of the summary.")
def loadtest(scenario_path, show_example, workdir, report_path, as_json):
    """Storm a full in-process fleet (loadtest/) with synthetic mixed
    traffic — events, queries, feedback — under a declarative scenario
    (Zipfian population, diurnal arrivals, injected incidents) and
    assert the runtime invariants live: no dropped acks, exactly-once
    ingest by post-run audit, one LIVE release, freshness SLO held.
    Exit status is the verdict."""
    import os
    import tempfile

    from predictionio_tpu.loadtest.scenario import (
        Scenario, ScenarioError, example_scenario,
    )

    if show_example:
        click.echo(json.dumps(example_scenario(), indent=2, sort_keys=True))
        return

    try:
        if scenario_path:
            sc = Scenario.load(scenario_path)
        else:
            sc = Scenario.from_dict(example_scenario())
    except ScenarioError as e:
        click.echo(f"[ERROR] bad scenario: {e}")
        sys.exit(1)

    from predictionio_tpu.loadtest.fleet import LocalFleet, MultiTenantFleet
    from predictionio_tpu.loadtest.simulator import (
        run_storm, run_tenant_storm, storm_report_json,
    )
    from predictionio_tpu.utils.server_config import loadtest_config

    knobs = loadtest_config()
    knobs.apply(sc)

    tmp = None
    if workdir is None:
        tmp = tempfile.TemporaryDirectory(prefix="pio-loadtest-")
        workdir = tmp.name
    try:
        if sc.tenants:
            click.echo(
                f"[INFO] Multi-tenant storm '{sc.name}': "
                f"{len(sc.tenants)} tenant(s) "
                f"[{', '.join(t.name for t in sc.tenants)}] "
                f"duration={sc.duration_s:g}s rate={sc.base_rate:g}/s "
                f"incidents={len(sc.incidents)}")
            fleet = MultiTenantFleet(workdir, sc.tenants)
            try:
                fleet.start()
                report = run_tenant_storm(sc, fleet)
            finally:
                fleet.stop()
        else:
            click.echo(
                f"[INFO] Storm '{sc.name}': population={sc.population} "
                f"duration={sc.duration_s:g}s rate={sc.base_rate:g}/s "
                f"replicas={sc.replicas} partitions={sc.partitions} "
                f"backend={sc.backend} incidents={len(sc.incidents)}")
            fleet = LocalFleet(workdir, replicas=sc.replicas,
                               partitions=sc.partitions,
                               backend=sc.backend)
            try:
                fleet.start()
                report = run_storm(sc, fleet)
            finally:
                fleet.stop()
    finally:
        if tmp is not None:
            tmp.cleanup()

    if report_path is None and knobs.report_dir:
        os.makedirs(knobs.report_dir, exist_ok=True)
        report_path = os.path.join(knobs.report_dir, f"{sc.name}.json")
    if report_path:
        tmp_report = f"{report_path}.tmp"
        with open(tmp_report, "w") as f:
            f.write(storm_report_json(report) + "\n")
        os.replace(tmp_report, report_path)
        click.echo(f"[INFO] Report written to {report_path}")

    if as_json:
        click.echo(storm_report_json(report))
    else:
        for lane, res in sorted(report.get("lanes", {}).items()):
            click.echo(
                f"[INFO] lane {lane}: offered={res['offered']} "
                f"acked={res['acked']} failed={res['failed']} "
                f"p99={res['ack_p99_ms']:.1f}ms")
        for name, res in sorted(report.get("tenants", {}).items()):
            click.echo(
                f"[INFO] tenant {name}: offered={res['offered']} "
                f"acked={res['acked']} failed={res['failed']} "
                f"rejected={res['rejections']} "
                f"p99={res['ack_p99_ms']:.1f}ms")
        for inv in report["invariants"]:
            mark = "ok " if inv["ok"] else "FAIL"
            click.echo(f"[{mark.upper().strip()}] {inv['name']}: "
                       f"{inv['detail']}")
    if not report["ok"]:
        click.echo("[ERROR] storm verdict: INVARIANT VIOLATED")
        sys.exit(1)
    arrivals = report.get(
        "arrivals",
        sum(r["offered"] for r in report.get("tenants", {}).values()))
    click.echo(f"[INFO] storm verdict: OK "
               f"({arrivals} arrivals, "
               f"{report['wall_s']:.1f}s wall)")


def _release_of_instance(engine_id, variant_id, instance_id):
    """The release manifest registered for an instance, if any (pre-
    release-registry instances deploy fine without one)."""
    from predictionio_tpu.storage import Storage

    try:
        for r in Storage.get_meta_data_releases().get_for_variant(
                engine_id, "1", variant_id):
            if r.instance_id == instance_id:
                return r
    except Exception:
        pass
    return None


@cli.command()
@click.option("--variant", "-v", default="engine.json")
@click.option("--status", "status_filter", default=None,
              help="Only releases in this status (REGISTERED, CANARY, "
                   "LIVE, RETIRED, ROLLED_BACK).")
def releases(variant, status_filter):
    """List release manifests for an engine variant (deploy/ registry)."""
    from predictionio_tpu.storage import Storage

    engine, _, factory_path, variant_id, _vj = _load_engine_variant(variant)
    listing = Storage.get_meta_data_releases().get_for_variant(
        factory_path, "1", variant_id)
    if status_filter:
        listing = [r for r in listing if r.status == status_filter.upper()]
    click.echo(f"[INFO] {'Ver':<5} | {'Status':<11} | "
               f"{'Instance':<32} | {'Created':<20} | Model")
    for r in listing:
        size = (f"{r.model_size_bytes / 1024:.0f}KiB"
                if r.model_size_bytes else "-")
        digest = r.model_digest[:12] if r.model_digest else "-"
        click.echo(f"[INFO] v{r.version:<4} | {r.status:<11} | "
                   f"{r.instance_id:<32} | "
                   f"{r.created_time.strftime('%Y-%m-%d %H:%M:%S'):<20} | "
                   f"{digest} {size}")
    click.echo(f"[INFO] Finished listing {len(listing)} release(s).")


@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=8000, type=int)
@click.option("--accesskey", default=None)
def rollback(ip, port, accesskey):
    """Roll a live query server back to its previous release
    (POST /rollback.json against the deploy API)."""
    import urllib.error
    import urllib.request

    url = f"http://{ip}:{port}/rollback.json"
    if accesskey:
        url += f"?accessKey={accesskey}"
    try:
        with urllib.request.urlopen(
                urllib.request.Request(url, method="POST"),
                timeout=60) as r:
            out = json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        try:
            message = json.loads(e.read().decode()).get("message", str(e))
        except Exception:
            message = str(e)
        click.echo(f"[ERROR] Rollback failed: {message}")
        sys.exit(1)
    except Exception as e:
        click.echo(f"[ERROR] Unable to reach query server: {e}")
        sys.exit(1)
    version = out.get("releaseVersion")
    click.echo(f"[INFO] {out.get('message', 'Rolled back')}: now serving "
               f"instance {out.get('engineInstanceId')}"
               + (f" (release v{version})" if version else ""))


def _parse_duration_s(text):
    """'30m' / '2h' / '45s' / '1d' / plain seconds -> float seconds."""
    text = str(text).strip().lower()
    units = {"s": 1.0, "m": 60.0, "h": 3600.0, "d": 86400.0}
    try:
        if text and text[-1] in units:
            return float(text[:-1]) * units[text[-1]]
        return float(text)
    except ValueError:
        raise click.BadParameter(
            f"{text!r} is not a duration (try 30m, 2h, 45s)")


@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=8000, type=int)
@click.option("--trace-id", "trace_id", default=None,
              help="Only spans of this trace id.")
@click.option("--limit", type=int, default=20,
              help="Most recent N trace records (default 20).")
@click.option("--since", "since", default=None, metavar="30m",
              help="Only records newer than this (e.g. 45s, 30m, 2h) — "
                   "reaches back through the rings a restart reloaded "
                   "from the durable telemetry store.")
@click.option("--events", "show_events", is_flag=True,
              help="Also print lifecycle events (deploys, swaps, "
                   "fold-in applies, canary verdicts, SLO breaches).")
@click.option("--json", "as_json", is_flag=True,
              help="Raw /debug/traces.json body.")
def traces(ip, port, trace_id, limit, since, show_events, as_json):
    """Read a live server's flight recorder (GET /debug/traces.json):
    the bounded ring of recent traces + lifecycle events. Works against
    any server in the fleet (event server, query server, admin,
    dashboard)."""
    import urllib.parse
    import urllib.request

    params = {"limit": str(limit)}
    if trace_id:
        params["traceId"] = trace_id
    if since:
        params["sinceS"] = str(_parse_duration_s(since))
    url = (f"http://{ip}:{port}/debug/traces.json?"
           + urllib.parse.urlencode(params))
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            doc = json.loads(r.read().decode())
    except Exception as e:
        click.echo(f"[ERROR] Unable to read {url}: {e}")
        sys.exit(1)
    if as_json:
        click.echo(json.dumps(doc, indent=1, sort_keys=True))
        return
    for t in doc.get("traces", []):
        spans = " ".join(f"{k}={v * 1e3:.1f}ms"
                         for k, v in (t.get("spans") or {}).items())
        click.echo(f"[INFO] {t.get('traceId', '?')[:12]} "
                   f"{t.get('name')} {t.get('durationSec', 0) * 1e3:.1f}ms "
                   f"[{t.get('status')}] proc={t.get('process')}"
                   + (f" | {spans}" if spans else ""))
    if show_events:
        for e in doc.get("events", []):
            tid = (e.get("traceId") or "-")[:12]
            rest = {k: v for k, v in e.items()
                    if k not in ("kind", "ts", "traceId", "process")}
            click.echo(f"[INFO] event {e.get('kind')} trace={tid} {rest}")
    click.echo(f"[INFO] {len(doc.get('traces', []))} trace record(s), "
               f"{len(doc.get('events', []))} lifecycle event(s).")


@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=8000, type=int)
@click.option("--accesskey", default=None)
@click.option("--seconds", type=float, default=2.0,
              help="Capture window (capped server-side at 60s).")
@click.option("--dir", "outdir", default=None,
              help="Trace output directory (server-side path; default a "
                   "fresh temp dir).")
def profile(ip, port, accesskey, seconds, outdir):
    """Capture a bounded on-demand device profile from a live query
    server (POST /debug/profile): a jax.profiler trace, the capture's
    device seconds by compile family and `jax.named_scope` for the
    programs that publish a scope table, and the host seconds spent
    dispatching each compile family."""
    import urllib.error
    import urllib.request

    url = f"http://{ip}:{port}/debug/profile"
    if accesskey:
        url += f"?accessKey={accesskey}"
    body = json.dumps({"seconds": seconds,
                       **({"dir": outdir} if outdir else {})}).encode()
    req = urllib.request.Request(
        url, data=body, method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=seconds + 30) as r:
            out = json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        try:
            message = json.loads(e.read().decode()).get("message", str(e))
        except Exception:
            message = str(e)
        click.echo(f"[ERROR] Profile failed: {message}")
        sys.exit(1)
    except Exception as e:
        click.echo(f"[ERROR] Unable to reach query server: {e}")
        sys.exit(1)
    click.echo(f"[INFO] Captured {out.get('seconds')}s device profile "
               f"-> {out.get('traceDir')}")
    for family, by_scope in (out.get("scopes") or {}).items():
        click.echo(f"[INFO] Device seconds of this capture by scope, "
                   f"{family}:")
        for scope, secs in sorted(by_scope.items(), key=lambda kv: -kv[1]):
            click.echo(f"[INFO]   {scope or '(under no scope)':<24} "
                       f"{secs:.6f}s")
    if out.get("scopes"):
        click.echo(f"[INFO]   {'(other programs)':<24} "
                   f"{out.get('untabledSeconds', 0.0):.6f}s")
    dispatch = out.get("dispatch") or {}
    if dispatch:
        click.echo("[INFO] Host seconds dispatching, by compile family "
                   "(cumulative since process start; nothing waits for "
                   "the device, so not device time):")
        for family, secs in dispatch.items():
            click.echo(f"[INFO]   {family:<24} {secs:.3f}s")
    else:
        click.echo("[INFO] No dispatch attribution recorded yet "
                   "(PIO_DISPATCH_ATTRIBUTION=0, or nothing dispatched).")


@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=8000, type=int)
def slo(ip, port):
    """Read a live query server's SLO burn-rate evaluation (/slo.json)."""
    import urllib.request

    url = f"http://{ip}:{port}/slo.json"
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            doc = json.loads(r.read().decode())
    except Exception as e:
        click.echo(f"[ERROR] Unable to read {url}: {e}")
        sys.exit(1)
    if not doc.get("enabled"):
        click.echo("[INFO] SLO engine disabled "
                   '(configure server.json {"slo": {...}}).')
        return
    state = "BREACHED" if doc.get("breached") else "ok"
    click.echo(f"[INFO] SLO status: {state}")
    for obj in doc.get("objectives", []):
        mark = "BREACHED" if obj.get("breached") else "ok"
        if obj.get("window") == "cold":
            mark += " (cold: history does not span the window yet)"
        windows = ", ".join(
            f"{int(w['seconds'])}s burn {w['burn']:.2f}/{w['burnThreshold']}"
            for w in obj.get("windows", []))
        click.echo(f"[INFO]   {obj['name']} ({obj['kind']}): {mark} "
                   f"[{windows}]")


@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=8000, type=int)
@click.option("--json", "as_json", is_flag=True,
              help="Raw /capacity.json body.")
def capacity(ip, port, as_json):
    """Read a live server's device-memory ledger (GET /capacity.json):
    process-level device bytes / watermark / host RSS, plus per serving
    unit the resident factor, quantized-scorer and shortlist bytes.
    Works against any server in the fleet."""
    import urllib.request

    url = f"http://{ip}:{port}/capacity.json"
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            doc = json.loads(r.read().decode())
    except Exception as e:
        click.echo(f"[ERROR] Unable to read {url}: {e}")
        sys.exit(1)
    if as_json:
        click.echo(json.dumps(doc, indent=1, sort_keys=True))
        return

    def _mb(n):
        return f"{float(n or 0) / (1 << 20):.1f}MiB"

    proc = doc.get("process") or {}
    click.echo(f"[INFO] process: device {_mb(proc.get('deviceBytes'))} "
               f"across {int(proc.get('deviceArrays') or 0)} array(s), "
               f"watermark {_mb(proc.get('deviceWatermarkBytes'))}, "
               f"host RSS {_mb(proc.get('hostRssBytes'))}")
    units = doc.get("units") or []
    for u in units:
        click.echo(f"[INFO] unit {u.get('role')}: resident "
                   f"{_mb(u.get('residentBytes'))} (scorer "
                   f"{_mb(u.get('scorerBytes'))}) release "
                   f"v{u.get('release')} instance "
                   f"{u.get('engineInstanceId')}")
        for m in u.get("models") or []:
            click.echo(f"[INFO]   {m.get('model')}: factors "
                       f"{_mb(m.get('modelFactorBytes'))} + scorer "
                       f"{_mb(m.get('scorerFactorBytes'))} + shortlist "
                       f"{_mb(m.get('shortlistBytes'))}")
    if not units:
        click.echo("[INFO] no serving units reported (event server, "
                   "admin and dashboard answer process-level only).")


# ---------------------------------------------------------------------------
# durable telemetry (obs/tsdb.py + obs/telemetry.py)
# ---------------------------------------------------------------------------

@cli.group()
def metrics():
    """Query the durable local telemetry stores (metrics history that
    survives restarts; OBSERVABILITY.md "Durable telemetry")."""


def _history_reader(dirpath):
    from predictionio_tpu.obs import fleet
    from predictionio_tpu.utils.server_config import telemetry_config

    root = dirpath or telemetry_config().root_dir()
    reader = fleet.history_reader(root)
    return root, reader


@metrics.command("series")
@click.option("--dir", "dirpath", default=None,
              help="Telemetry root (default $PIO_HOME/telemetry or "
                   "PIO_TELEMETRY_DIR).")
@click.option("--name", default=None, help="Only this metric.")
def metrics_series(dirpath, name):
    """List the persisted series: name, labels, sample count, range."""
    root, reader = _history_reader(dirpath)
    listing = reader.series(name=name)
    for info in listing:
        if not info.points:
            continue
        span = (info.points[-1][0] - info.points[0][0]) / 1000.0
        click.echo(f"[INFO] {info.name} {info.labels} [{info.kind}] "
                   f"{len(info.points)} sample(s) over {span:.0f}s")
    click.echo(f"[INFO] {len(listing)} series in {root}.")


@metrics.command("query")
@click.argument("name")
@click.option("--since", default="1h", metavar="30m",
              help="Trailing window (e.g. 45s, 30m, 2h, 1d; default 1h).")
@click.option("--rate", "as_rate", is_flag=True,
              help="Per-second rate + increase over the window "
                   "(reset-adjusted: restarts never read negative).")
@click.option("--quantile", type=float, default=None,
              help="Histogram quantile over the window, e.g. 0.99.")
@click.option("--label", "label_filters", multiple=True,
              metavar="KEY=VALUE", help="Label filter (repeatable).")
@click.option("--dir", "dirpath", default=None,
              help="Telemetry root (default $PIO_HOME/telemetry or "
                   "PIO_TELEMETRY_DIR).")
@click.option("--json", "as_json", is_flag=True)
def metrics_query(name, since, as_rate, quantile, label_filters, dirpath,
                  as_json):
    """Range-query a metric's persisted history, fleet-merged across
    every local process's store (each labeled with its `process`)."""
    import time as _time

    root, reader = _history_reader(dirpath)
    since_ms = int((_time.time() - _parse_duration_s(since)) * 1000)
    labels = {}
    for spec in label_filters:
        if "=" not in spec:
            click.echo(f"[ERROR] --label expects KEY=VALUE, got {spec!r}")
            sys.exit(1)
        k, v = spec.split("=", 1)
        labels[k] = v
    labels = labels or None
    if quantile is not None:
        value = reader.quantile_over_time(name, quantile, labels=labels,
                                          since_ms=since_ms)
        if as_json:
            click.echo(json.dumps({"name": name, "quantile": quantile,
                                   "value": value}))
        elif value is None:
            click.echo(f"[INFO] no histogram data for {name} in the "
                       f"window (root {root}).")
        else:
            click.echo(f"[INFO] {name} p{quantile * 100:g} over {since}: "
                       f"{value:.6g}")
        return
    if as_rate:
        rates = reader.rate(name, labels=labels, since_ms=since_ms)
        if as_json:
            click.echo(json.dumps({"name": name, "series": rates}))
            return
        for r in rates:
            click.echo(f"[INFO] {name} {r['labels']}: "
                       f"{r['rate']:.4g}/s (+{r['increase']:.6g} over "
                       f"{r['seconds']:.0f}s)")
        if not rates:
            click.echo(f"[INFO] no data for {name} in the window "
                       f"(root {root}).")
        return
    series = reader.series(name=name, labels=labels, since_ms=since_ms)
    if as_json:
        out = []
        for info in series:
            points = ([[ts, sum(c), s] for ts, c, s in info.points]
                      if info.kind == "histogram"
                      else [[ts, v] for ts, v in info.points])
            out.append({"labels": info.labels, "kind": info.kind,
                        "points": points})
        click.echo(json.dumps({"name": name, "series": out}))
        return
    shown = 0
    for info in series:
        if not info.points:
            continue
        shown += 1
        if info.kind == "histogram":
            first, last = info.points[0], info.points[-1]
            click.echo(f"[INFO] {name} {info.labels} [histogram]: "
                       f"count {sum(first[1]):g} -> {sum(last[1]):g} "
                       f"over {len(info.points)} sample(s)")
        else:
            values = [p[1] for p in info.points]
            click.echo(f"[INFO] {name} {info.labels} [{info.kind}]: "
                       f"{values[0]:g} -> {values[-1]:g} over "
                       f"{len(values)} sample(s)")
    if not shown:
        click.echo(f"[INFO] no data for {name} in the window "
                   f"(root {root}).")


@cli.command()
@click.option("--path", "anatomy_path", default="serving",
              type=click.Choice(["serving", "ingest"]),
              help="Which critical path to analyze (default serving).")
@click.option("--since", default="1h", metavar="30m",
              help="Trailing window (e.g. 45s, 30m, 2h; default 1h).")
@click.option("--diff", "do_diff", is_flag=True,
              help="Two-window regression diff: the trailing window vs "
                   "the equal-length window before it; names the stage "
                   "the regression came from.")
@click.option("--dir", "dirpath", default=None,
              help="Telemetry root (default $PIO_HOME/telemetry or "
                   "PIO_TELEMETRY_DIR).")
@click.option("--json", "as_json", is_flag=True)
def analyze(anatomy_path, since, do_diff, dirpath, as_json):
    """Tail anatomy off the durable telemetry store: where p50 and p99
    requests spend their wall, per critical-path stage
    (pio_anatomy_stage_seconds), with an optional two-window diff that
    names the stage a latency regression came from."""
    import time as _time

    from predictionio_tpu.obs.anatomy import (
        composition, regression_diff, stage_stats,
    )

    root, reader = _history_reader(dirpath)
    window_ms = int(_parse_duration_s(since) * 1000)
    now_ms = int(_time.time() * 1000)
    since_ms = now_ms - window_ms
    stats = stage_stats(reader, anatomy_path, since_ms=since_ms)
    diff = None
    if do_diff:
        before = stage_stats(reader, anatomy_path,
                             since_ms=since_ms - window_ms,
                             until_ms=since_ms)
        if before and stats:
            diff = regression_diff(before, stats)
    if as_json:
        click.echo(json.dumps({
            "path": anatomy_path, "sinceMs": since_ms,
            "stages": stats,
            "p50Composition": composition(stats, anatomy_path, "p50"),
            "p99Composition": composition(stats, anatomy_path, "p99"),
            "diff": diff}, sort_keys=True))
        return
    if not stats:
        click.echo(f"[INFO] no anatomy history for path={anatomy_path} "
                   f"in the window (root {root}; is PIO_ANATOMY on and "
                   "telemetry persisting?).")
        return
    p50_comp = composition(stats, anatomy_path, "p50")
    p99_comp = composition(stats, anatomy_path, "p99")
    requests = max(s["count"] for s in stats.values())
    click.echo(f"[INFO] {anatomy_path} anatomy over {since} "
               f"({requests:g} request(s)):")
    click.echo(f"[INFO]   {'stage':<16} {'mean':>9} {'p50':>9} "
               f"{'p99':>9} {'p50 share':>10} {'p99 share':>10}")
    for stage, s in sorted(stats.items(), key=lambda kv: -kv[1]["p99"]):
        def _share(comp):
            return (f"{100.0 * comp[stage]:.0f}%"
                    if stage in comp else "-")
        click.echo(
            f"[INFO]   {stage:<16} {1e3 * s['mean']:>7.2f}ms "
            f"{1e3 * s['p50']:>7.2f}ms {1e3 * s['p99']:>7.2f}ms "
            f"{_share(p50_comp):>10} {_share(p99_comp):>10}")
    if do_diff:
        if diff is None:
            click.echo("[INFO] diff: not enough history in the "
                       "baseline window.")
        else:
            click.echo(
                f"[INFO] regression diff vs previous {since}: stage "
                f"'{diff['stage']}' moved most "
                f"({1e3 * diff['beforeMeanS']:.2f}ms -> "
                f"{1e3 * diff['afterMeanS']:.2f}ms mean, "
                f"{1e3 * diff['deltaMeanS']:+.2f}ms)")


@cli.command()
@click.option("--variant", "-v", default="engine.json")
@click.option("--once", is_flag=True,
              help="One trigger evaluation (and one cycle if it fires), "
                   "then exit.")
@click.option("--force", is_flag=True,
              help="Fire one manual cycle immediately (skips the "
                   "data-driven triggers and the cooldown window).")
@click.option("--cycles", type=int, default=None,
              help="Exit after this many completed cycles (default: "
                   "run forever).")
@click.option("--server", default=None, metavar="HOST:PORT",
              help="Drive a live query server's deploy API for the "
                   "canary phase (default: registry-only plane).")
@click.option("--accesskey", default=None)
@click.option("--state-dir", default=None,
              help="Crash-safe cycle-document directory (default "
                   "$PIO_HOME/orchestrator or PIO_ORCH_STATE_DIR).")
@click.option("--eval-class", default=None,
              help="Dotted Evaluation path for the eval-gate phase "
                   "(skipped when absent, like `pio eval`'s argument).")
def orchestrate(variant, once, force, cycles, server, accesskey,
                state_dir, eval_class):
    """Continuous-training orchestrator: the closed Lambda loop.

    Recurring train -> eval-gate -> batchpredict smoke -> SLO-judged
    canary -> promote over the release registry, with crash-safe phase
    state (kill it anywhere; the next start converges), data-driven
    retrain triggers (ingest volume, fold-in pressure, SLO burn) and
    jittered backoff on failure. README "Continuous training".
    """
    import os

    from predictionio_tpu.deploy.orchestrator import build_orchestrator

    if not os.path.exists(variant):
        click.echo(f"[ERROR] {variant} does not exist. Aborting.")
        sys.exit(1)
    orch = build_orchestrator(variant, eval_path=eval_class,
                              server=server, access_key=accesskey,
                              state_dir=state_dir)
    cfg = orch.cfg
    click.echo(f"[INFO] Orchestrating {orch.engine_id}/"
               f"{orch.engine_variant} (state in {orch.store.state_dir})")
    click.echo(f"[INFO] Triggers: ingest>={cfg.min_ingest_events or 'off'}"
               f" foldin>={cfg.foldin_pending_max or 'off'}"
               f" slo={'on' if cfg.slo_trigger else 'off'}; "
               f"cooldown {cfg.cooldown_s:g}s, check every "
               f"{cfg.interval_s:g}s")
    click.echo(f"[INFO] Canary plane: "
               + (f"live server {server}" if server
                  else "release registry"))
    if once or force:
        action = orch.recover()
        if action:
            click.echo(f"[INFO] Recovery: {action}")
        doc = orch.tick(force=force)
        if doc is None:
            click.echo("[INFO] No trigger fired; nothing to do.")
            return
        _echo_cycle(doc)
        if doc.outcome != "promoted":
            sys.exit(1)
        return
    try:
        done = orch.run(cycles=cycles)
    except KeyboardInterrupt:
        click.echo("[INFO] Orchestrator stopped.")
        return
    click.echo(f"[INFO] Orchestrator exiting after {done} cycle(s).")


def _echo_cycle(doc) -> None:
    click.echo(f"[INFO] Cycle {doc.cycle_id} ({doc.trigger}): "
               f"{doc.outcome} — {doc.reason}")
    if doc.candidate_release_version:
        click.echo(f"[INFO]   candidate release "
                   f"v{doc.candidate_release_version}"
                   + (f" | eval score {doc.eval_score}"
                      if doc.eval_score is not None else ""))
    trace = (doc.trace or ":").split(":")[0]
    click.echo(f"[INFO]   trace id {trace} (follow with `pio traces "
               f"--trace-id {trace}` on a live server)")


@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=8000, type=int)
@click.option("--accesskey", default=None)
def undeploy(ip, port, accesskey):
    """Stop a deployed query server (Console.scala:318)."""
    import urllib.request

    url = f"http://{ip}:{port}/stop"
    if accesskey:
        url += f"?accessKey={accesskey}"
    try:
        with urllib.request.urlopen(
                urllib.request.Request(url, method="POST"), timeout=10) as r:
            click.echo(f"[INFO] {r.read().decode()}")
    except Exception as e:
        click.echo(f"[ERROR] Unable to undeploy: {e}")
        sys.exit(1)


@cli.command("eval")
@click.argument("evaluation_path")
@click.argument("params_generator_path", required=False)
@click.option("--batch", default="")
@click.option("--grid", "grid_specs", multiple=True, metavar="NAME=V1,V2",
              help="Cross-product override on the algorithm params, e.g. "
                   "--grid rank=8,12 --grid reg=0.01,0.1 (repeatable).")
@click.option("--k-fold", "k_fold", type=int, default=None,
              help="Override the datasource's kFold eval param.")
@click.option("--query-num", "query_num", type=int, default=None,
              help="Override the datasource's queryNum eval param.")
@click.option("--sequential", is_flag=True,
              help="Force the per-candidate sequential loop instead of "
                   "the device-batched sweep.")
def eval_cmd(evaluation_path, params_generator_path, batch, grid_specs,
             k_fold, query_num, sequential):
    """Run an evaluation sweep (Console.scala:232).

    EVALUATION_PATH: dotted path to an Evaluation object/factory;
    PARAMS_GENERATOR_PATH: dotted path to an EngineParamsGenerator (optional
    when the Evaluation carries its own params list).

    With --grid flags the supported engines execute the whole grid as a
    few device programs (folds become zero-weight masks over one shared
    data build; one XLA compile per distinct rank).
    """
    import dataclasses as _dc
    import os

    from predictionio_tpu.core.base import load_class
    from predictionio_tpu.core.evaluation import (
        VECTORIZE_ENV, Evaluation, expand_param_grid,
    )
    from predictionio_tpu.workflow import WorkflowParams, run_evaluation

    evaluation = load_class(evaluation_path)
    if isinstance(evaluation, type):
        evaluation = evaluation()          # Evaluation subclass
    elif callable(evaluation) and not isinstance(evaluation, Evaluation):
        evaluation = evaluation()          # factory function
    params_list = None
    if params_generator_path:
        gen = load_class(params_generator_path)
        if isinstance(gen, type):
            gen = gen()
        elif callable(gen) and not hasattr(gen, "engine_params_list"):
            gen = gen()
        params_list = list(gen.engine_params_list)
    if params_list is None:
        params_list = list(getattr(evaluation, "engine_params_list", []))
    if not params_list:
        click.echo("[ERROR] No engine params to evaluate. Aborting.")
        sys.exit(1)
    try:
        params_list = expand_param_grid(params_list, grid_specs)
    except ValueError as e:
        click.echo(f"[ERROR] {e}. Aborting.")
        sys.exit(1)
    if k_fold is not None or query_num is not None:
        overrides = {}
        if k_fold is not None:
            overrides["kFold"] = k_fold
        if query_num is not None:
            overrides["queryNum"] = query_num
        patched = []
        for ep in params_list:
            ds = ep.data_source_params
            if not hasattr(ds, "eval_params"):
                click.echo("[ERROR] --k-fold/--query-num need a datasource "
                           "with eval_params. Aborting.")
                sys.exit(1)
            ds = _dc.replace(ds, eval_params={**(ds.eval_params or {}),
                                              **overrides})
            patched.append(_dc.replace(ep, data_source_params=ds))
        params_list = patched
    old_vectorize = os.environ.get(VECTORIZE_ENV)
    if sequential:
        os.environ[VECTORIZE_ENV] = "0"
    try:
        result = run_evaluation(
            evaluation, params_list,
            evaluation_class=evaluation_path,
            params_generator_class=params_generator_path or "",
            workflow_params=WorkflowParams(batch=batch))
    finally:
        if sequential:
            if old_vectorize is None:
                os.environ.pop(VECTORIZE_ENV, None)
            else:
                os.environ[VECTORIZE_ENV] = old_vectorize
    sweep = result.sweep or {}
    if sweep.get("mode") == "batched":
        click.echo(f"[INFO] Sweep ran device-batched: "
                   f"{len(params_list)} candidates in "
                   f"{sweep.get('compileGroups')} compile group(s), "
                   f"batch sizes {sweep.get('batchSizes')}")
    for i, detail in enumerate(result.candidate_details):
        _ep, score, _others = result.engine_params_scores[i]
        click.echo(f"[INFO]   #{i}: score={score} "
                   f"wall={detail.get('wallTimeS')}s "
                   f"group={detail.get('group')}"
                   + (" <- best" if i == result.best_idx else ""))
    click.echo(f"[INFO] {result.to_one_liner()}")
    click.echo("[INFO] Evaluation completed.")


@cli.command()
@click.option("--variant", "-v", default="engine.json")
@click.option("--input", "input_path", required=True,
              help="Queries: one JSON object per line, or a .parquet "
                   "table (a 'query' JSON column or one column per "
                   "query field).")
@click.option("--output", "output_path", required=True,
              help="Predictions: JSON-lines, or .parquet when the path "
                   "(or --output-format) says so.")
@click.option("--engine-instance-id", default=None)
@click.option("--release", "release_selector", default=None,
              help="Score with a specific release (id, version number "
                   "or vN) from `pio releases`, like `pio deploy`.")
@click.option("--chunk-size", type=int, default=None,
              help="Maximal scoring bucket (default from server.json "
                   "batchpredict section / PIO_BATCHPREDICT_CHUNK_SIZE; "
                   "1024 out of the box).")
@click.option("--output-format", "output_format",
              type=click.Choice(["jsonl", "parquet"]), default=None,
              help="Force the output format instead of inferring from "
                   "the --output extension.")
@click.option("--input-format", "input_format",
              type=click.Choice(["jsonl", "parquet"]), default=None)
def batchpredict(variant, input_path, output_path, engine_instance_id,
                 release_selector, chunk_size, output_format, input_format):
    """Offline batch scoring (Console.scala:331, BatchPredict.scala:71):
    pipelined reader->scorer->writer over the engine's bucketed batch
    path. Multi-process sharding rides the PIO_PROCESS_ID /
    PIO_NUM_PROCESSES env contract: run one `pio batchpredict` per
    shard and the last to finish merges the fragments."""
    from predictionio_tpu.deploy.releases import resolve_release
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.workflow.batch_predict import run_batch_predict

    engine, _, factory_path, variant_id, variant_json = \
        _load_engine_variant(variant)
    variant_conf = variant_json.get("batchpredict")
    # offline scoring honors the same scorer-mode chain as serving, so
    # batchpredict parity runs compare like against like
    from predictionio_tpu.ops.scoring import set_process_scorer_config
    from predictionio_tpu.utils.server_config import scorer_config

    scfg = scorer_config(variant_json.get("scorer"))
    set_process_scorer_config(scfg)
    if scfg.mode != "exact":
        click.echo(f"[INFO] Scoring kernel {scfg.mode} (tile "
                   f"{scfg.tile_items} items)")
    instances = Storage.get_meta_data_engine_instances()
    if release_selector:
        release = resolve_release(Storage.get_meta_data_releases(),
                                  factory_path, "1", variant_id,
                                  release_selector)
        if release is None:
            click.echo(f"[ERROR] Release {release_selector} not found "
                       "(see `pio releases`). Aborting.")
            sys.exit(1)
        instance = instances.get(release.instance_id)
        if instance is not None and instance.status == "COMPLETED":
            click.echo(f"[INFO] Scoring with release v{release.version} "
                       f"(instance {release.instance_id})")
    elif engine_instance_id:
        instance = instances.get(engine_instance_id)
    else:
        instance = instances.get_latest_completed(
            factory_path, "1", variant_id)
    if instance is None or instance.status != "COMPLETED":
        click.echo("[ERROR] No COMPLETED engine instance found. Aborting.")
        sys.exit(1)
    report = run_batch_predict(
        engine, instance, input_path, output_path, chunk_size=chunk_size,
        output_format=output_format, input_format=input_format,
        variant_conf=variant_conf)
    if report.merged:
        click.echo(f"[INFO] Wrote {report.total_written} predictions to "
                   f"{report.output_path}")
        if report.fleet:
            totals = report.fleet.get("counterTotals", {})
            scored = totals.get("pio_batchpredict_queries_total")
            click.echo(
                f"[INFO] Fleet view ({len(report.fleet.get('processes', []))}"
                f" process(es)) -> {report.output_path}.fleet.json"
                + (f"; fleet queries scored {scored:g}"
                   if scored is not None else "")
                + "; inspect with `pio status --fleet "
                + f"{report.output_path}`")
    else:
        rank, size = report.worker
        click.echo(f"[INFO] Shard {rank}/{size} wrote {report.written} "
                   f"predictions to fragment {report.output_path} "
                   "(awaiting merge by the last shard)")
    if report.invalid or (report.total_invalid or 0):
        n_bad = report.total_invalid if report.merged else report.invalid
        click.echo(f"[WARN] Skipped {n_bad} invalid queries "
                   f"-> {report.errors_path}")
    if report.trace_id:
        click.echo(f"[INFO] Trace id {report.trace_id} "
                   "(follow with `pio traces --trace-id ...` on a live "
                   "server, or in the .fleet.json)")


# ---------------------------------------------------------------------------
# import / export (commands/{Import,Export}.scala)
# ---------------------------------------------------------------------------

@cli.command("import")
@click.option("--appid", type=int, default=None)
@click.option("--appname", default=None)
@click.option("--channel", default=None)
@click.option("--input", "input_path", required=True,
              help="JSON-lines file of events (FileToEvents.scala:40).")
def import_cmd(appid, appname, channel, input_path):
    """Import events from a JSON-lines file (Console.scala:623)."""
    from predictionio_tpu.data.event import Event, validate_event
    from predictionio_tpu.data.eventstore import resolve_app
    from predictionio_tpu.storage import Storage, StorageError

    if appname:
        try:
            app_id, channel_id = resolve_app(appname, channel)
        except StorageError as e:
            click.echo(f"[ERROR] {e}. Aborting.")
            sys.exit(1)
    elif appid is not None:
        app_id, channel_id = appid, None
    else:
        click.echo("[ERROR] --appid or --appname is required.")
        sys.exit(1)
    store = Storage.get_events()
    store.init_channel(app_id, channel_id)
    BATCH = 5000
    batch, total = [], 0
    with open(input_path) as f:  # streamed: memory stays one batch deep
        for line in f:
            line = line.strip()
            if not line:
                continue
            e = Event.from_json(line)
            validate_event(e)
            batch.append(e)
            if len(batch) >= BATCH:
                store.insert_batch(batch, app_id, channel_id)
                total += len(batch)
                batch = []
    if batch:
        store.insert_batch(batch, app_id, channel_id)
        total += len(batch)
    click.echo(f"[INFO] Imported {total} events.")


@cli.command("export")
@click.option("--appid", type=int, default=None)
@click.option("--appname", default=None)
@click.option("--channel", default=None)
@click.option("--output", "output_path", required=True)
@click.option("--format", "fmt", type=click.Choice(["json", "parquet"]),
              default="json")
def export_cmd(appid, appname, channel, output_path, fmt):
    """Export events to a file (Console.scala:606, EventsToFile.scala:40)."""
    import os

    from predictionio_tpu.data.eventstore import resolve_app
    from predictionio_tpu.storage import Storage, StorageError

    if appname:
        try:
            app_id, channel_id = resolve_app(appname, channel)
        except StorageError as e:
            click.echo(f"[ERROR] {e}. Aborting.")
            sys.exit(1)
    elif appid is not None:
        app_id, channel_id = appid, None
    else:
        click.echo("[ERROR] --appid or --appname is required.")
        sys.exit(1)
    store = Storage.get_events()
    # temp-write + rename: an interrupted export must never leave a
    # truncated file that looks like a complete dump (the import side
    # has no way to tell "all the events" from "the first half")
    tmp = f"{output_path}.tmp-{os.getpid()}"
    try:
        if fmt == "parquet":
            import pyarrow.parquet as pq

            table = store.find_columnar(app_id, channel_id)
            pq.write_table(table, tmp)
            n = table.num_rows
        else:
            n = 0
            with open(tmp, "w") as f:
                for e in store.find(app_id, channel_id):
                    f.write(e.to_json() + "\n")
                    n += 1
        os.replace(tmp, output_path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    click.echo(f"[INFO] Exported {n} events to {output_path}.")


@cli.command("compact")
@click.option("--appid", type=int, default=None)
@click.option("--appname", default=None)
@click.option("--channel", default=None)
@click.option("--ttl-days", type=float, default=None,
              help="Also drop events older than this many days "
                   "(per-app retention sweep).")
def compact_cmd(appid, appname, channel, ttl_days):
    """Event-store maintenance: fold deletes, merge fragments, apply
    retention. Crash-safe on parquet (write-new-then-remove-old behind an
    atomically committed manifest); a retention DELETE on SQL backends.
    Run one compactor per app namespace at a time."""
    from predictionio_tpu.data.eventstore import resolve_app
    from predictionio_tpu.storage import Storage, StorageError

    if appname:
        try:
            app_id, channel_id = resolve_app(appname, channel)
        except StorageError as e:
            click.echo(f"[ERROR] {e}. Aborting.")
            sys.exit(1)
    elif appid is not None:
        app_id, channel_id = appid, None
        if channel is not None:
            # compaction is destructive: never silently fall back to the
            # default channel when the named one cannot be resolved
            matched = [c for c in Storage.get_meta_data_channels()
                       .get_by_appid(appid) if c.name == channel]
            if not matched:
                click.echo(f"[ERROR] app {appid} has no channel "
                           f"'{channel}'. Aborting.")
                sys.exit(1)
            channel_id = matched[0].id
    else:
        click.echo("[ERROR] --appid or --appname is required.")
        sys.exit(1)
    store = Storage.get_events()
    try:
        stats = store.compact(app_id, channel_id, ttl_days=ttl_days)
    except StorageError as e:
        click.echo(f"[ERROR] compaction failed: {e}")
        sys.exit(1)
    click.echo(f"[INFO] Compacted app {app_id}"
               + (f" channel {channel_id}" if channel_id is not None else "")
               + ": " + json.dumps(stats, sort_keys=True))


@cli.command("reshard")
@click.option("--partitions", "-p", type=int, required=True,
              help="New partition count for the event store.")
def reshard_cmd(partitions):
    """Change the partitioned event store's partition count.

    Copies every app/channel namespace into a new generation of
    partition stores (idempotent inserts, original event ids), commits
    the partition map atomically, then collects the old generation —
    exactly-once at every crash point; an interrupted run can simply be
    re-run. Offline maintenance: stop event servers first (like
    `pio compact`, one operator at a time)."""
    from predictionio_tpu.storage import Storage, StorageError

    store = Storage.get_events()
    if not hasattr(store, "reshard"):
        click.echo(
            "[ERROR] the configured event store is not partitioned. "
            "Set PIO_INGEST_PARTITIONS>1 on a sqlite or parquet "
            "EVENTDATA source to create one.")
        sys.exit(1)
    apps = []
    for app in Storage.get_meta_data_apps().get_all():
        apps.append((app.id, None))
        for ch in Storage.get_meta_data_channels().get_by_appid(app.id):
            apps.append((app.id, ch.id))
    try:
        stats = store.reshard(partitions, apps)
    except StorageError as e:
        click.echo(f"[ERROR] reshard failed (safe to re-run): {e}")
        sys.exit(1)
    click.echo(f"[INFO] Resharded {len(apps)} namespace(s): "
               + json.dumps(stats, sort_keys=True))


# ---------------------------------------------------------------------------
# servers
# ---------------------------------------------------------------------------

@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=7070, type=int)
@click.option("--stats", is_flag=True, help="Enable hourly ingest statistics.")
def eventserver(ip, port, stats):
    """Launch the Event Server (Console.scala:384, EventServer.scala:552)."""
    from predictionio_tpu.server.event_server import run_event_server
    click.echo(f"[INFO] Creating Event Server at {ip}:{port}")
    run_event_server(ip=ip, port=port, stats=stats)


@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=7071, type=int)
def adminserver(ip, port):
    """Launch the admin API (Console.scala:399, AdminAPI.scala:45)."""
    from predictionio_tpu.server.admin import run_admin_server
    click.echo(f"[INFO] Creating Admin API at {ip}:{port}")
    run_admin_server(ip=ip, port=port)


@cli.command()
@click.option("--ip", default="localhost")
@click.option("--port", default=9000, type=int)
def dashboard(ip, port):
    """Launch the evaluation dashboard (Console.scala:371, Dashboard.scala:45)."""
    from predictionio_tpu.server.dashboard import run_dashboard
    click.echo(f"[INFO] Creating Dashboard at {ip}:{port}")
    run_dashboard(ip=ip, port=port)


@cli.command()
def shell():
    """Interactive REPL with the framework preloaded (bin/pio-shell analog)."""
    import code

    from predictionio_tpu.data.eventstore import EventStoreClient
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.workflow import WorkflowContext

    banner = ("predictionio_tpu shell\n"
              "preloaded: Storage, EventStoreClient (PEventStore/LEventStore"
              " analog), WorkflowContext")
    local = {"Storage": Storage, "EventStoreClient": EventStoreClient,
             "WorkflowContext": WorkflowContext}
    try:
        import IPython

        IPython.start_ipython(argv=[], user_ns=local)
    except ImportError:
        code.interact(banner=banner, local=local)


@cli.group()
def template():
    """Engine template helpers (Console.scala:595-605)."""


@template.command("list")
def template_list():
    """List built-in engine templates."""
    templates = {
        "recommendation": "predictionio_tpu.engines.recommendation:engine",
        "similarproduct": "predictionio_tpu.engines.similarproduct:engine",
        "classification": "predictionio_tpu.engines.classification:engine",
        "ecommerce": "predictionio_tpu.engines.ecommerce:engine",
        "sessionrec": "predictionio_tpu.engines.sessionrec:engine",
        "recommendeduser": "predictionio_tpu.engines.recommended_user:engine",
    }
    for name, factory in templates.items():
        click.echo(f"[INFO] {name:<16} {factory}")


@template.command("get")
@click.argument("name")
@click.argument("directory", required=False)
def template_get(name, directory):
    """Scaffold an engine.json for a built-in template."""
    import os

    factories = {
        "recommendation": ("predictionio_tpu.engines.recommendation:engine",
                           {"app_name": "MyApp"},
                           [{"name": "als",
                             "params": {"rank": 10, "num_iterations": 20,
                                        "reg": 0.01, "seed": 3}}]),
        "similarproduct": ("predictionio_tpu.engines.similarproduct:engine",
                           {"app_name": "MyApp"},
                           [{"name": "als",
                             "params": {"rank": 10, "num_iterations": 20}}]),
        "classification": ("predictionio_tpu.engines.classification:engine",
                           {"app_name": "MyApp"},
                           [{"name": "naive", "params": {"reg": 1.0}}]),
        "ecommerce": ("predictionio_tpu.engines.ecommerce:engine",
                      {"app_name": "MyApp"},
                      [{"name": "ecomm",
                        "params": {"app_name": "MyApp", "rank": 10}}]),
        "sessionrec": ("predictionio_tpu.engines.sessionrec:engine",
                       {"app_name": "MyApp"},
                       [{"name": "seqrec",
                         "params": {"d_model": 64, "n_heads": 2,
                                    "n_layers": 2, "max_len": 32,
                                    "epochs": 10}}]),
        "recommendeduser": (
            "predictionio_tpu.engines.recommended_user:engine",
            {"app_name": "MyApp"},
            [{"name": "als",
              "params": {"rank": 10, "num_iterations": 20}}]),
    }
    if name not in factories:
        click.echo(f"[ERROR] Unknown template {name}. "
                   f"Known: {', '.join(factories)}")
        sys.exit(1)
    factory, ds_params, algos = factories[name]
    target_dir = directory or name
    os.makedirs(target_dir, exist_ok=True)
    target = os.path.join(target_dir, "engine.json")
    # temp-write + rename: engine.json is the deploy surface — a crash
    # here must leave the previous template or nothing, never half a file
    tmp = f"{target}.tmp-{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump({
                "id": "default",
                "description": f"{name} engine",
                "engineFactory": factory,
                "datasource": {"params": ds_params},
                "algorithms": algos,
            }, f, indent=2)
        os.replace(tmp, target)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    click.echo(f"[INFO] Engine template {name} written to {target}")


@cli.command()
@click.argument("paths", nargs=-1)
@click.option("--rule", "-r", "rules", multiple=True,
              help="Run only these rule ids (repeatable), e.g. -r PIO002.")
@click.option("--json", "as_json", is_flag=True,
              help="Machine-readable report on stdout.")
@click.option("--baseline", "baseline_path", default=None,
              help="Baseline file of grandfathered findings "
                   "(default: conf/pio_check_baseline.json when present).")
@click.option("--write-baseline", is_flag=True,
              help="Rewrite the baseline to absorb every current finding.")
@click.option("--no-baseline", is_flag=True,
              help="Report every finding, ignoring any baseline.")
@click.option("--list-rules", is_flag=True,
              help="List the shipped rule ids and exit.")
def check(paths, rules, as_json, baseline_path, write_baseline,
          no_baseline, list_rules):
    """Static analysis: enforce the fleet's safety invariants.

    Scans predictionio_tpu/ (or just PATHS, root-relative)
    with the checker engine; exits 1 when any finding is not covered by
    the committed baseline or an inline `# pio: ignore[RULE]: reason`.
    """
    import pathlib

    import predictionio_tpu
    from predictionio_tpu.analysis import Baseline, Project, run_check
    from predictionio_tpu.analysis.engine import DEFAULT_BASELINE, all_rules

    if list_rules:
        for rid, title in sorted(all_rules().items()):
            click.echo(f"{rid}  {title}")
        return
    if write_baseline and (rules or paths):
        # a partial run would rewrite the baseline WITHOUT the entries
        # the filtered-out rules/files still need, silently un-
        # grandfathering them
        click.echo("[ERROR] --write-baseline regenerates the whole "
                   "baseline; it cannot be combined with --rule or PATHS.")
        sys.exit(2)
    root = pathlib.Path(predictionio_tpu.__file__).resolve().parent.parent
    # ALWAYS parse the full tree: whole-program rules (committer
    # reachability, builder routing, docs drift) need it; PATHS only
    # filters which files findings are reported for
    project = Project.from_root(root)
    scanned = {f.path for f in project.files}
    norm_paths = []
    for p in paths:
        # PATHS are project-root-relative; normalize `./`, `..`, and
        # absolute spellings to the scanned form so a mistyped path can
        # never silently filter every finding away and report clean
        base = pathlib.Path(p) if pathlib.Path(p).is_absolute() \
            else root / p
        try:
            norm = base.resolve().relative_to(root).as_posix()
        except ValueError:
            click.echo(f"[ERROR] {p} is outside the project root {root}.")
            sys.exit(2)
        if not any(s == norm or s.startswith(norm + "/")
                   for s in scanned):
            click.echo(f"[ERROR] {p} matches no scanned file "
                       "(paths are relative to the project root, e.g. "
                       "predictionio_tpu/deploy/foldin.py).")
            sys.exit(2)
        norm_paths.append(norm)
    baseline = Baseline()
    resolved = pathlib.Path(baseline_path) if baseline_path \
        else root / DEFAULT_BASELINE
    if not no_baseline and not write_baseline and resolved.is_file():
        baseline = Baseline.load(resolved)
    try:
        report = run_check(project, rules=rules or None, baseline=baseline,
                           paths=norm_paths or None)
    except ValueError as e:
        click.echo(f"[ERROR] {e}")
        sys.exit(2)
    if write_baseline:
        Baseline.from_findings(
            report.findings + report.baselined).save(resolved)
        click.echo(f"[INFO] baseline written to {resolved} "
                   f"({len(report.findings) + len(report.baselined)} "
                   "findings absorbed)")
        return
    if as_json:
        click.echo(json.dumps(report.to_json(), indent=2, sort_keys=True))
    else:
        click.echo(report.render())
    if not report.ok:
        sys.exit(1)


@cli.command()
@click.argument("main_module")
@click.argument("args", nargs=-1)
def run(main_module, args):
    """Run a module's main() in the framework environment (Console.scala:412)."""
    import runpy

    sys.argv = [main_module, *args]
    runpy.run_module(main_module, run_name="__main__")


def main():
    cli()


if __name__ == "__main__":
    main()
