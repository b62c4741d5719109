"""CLI: `python -m ray_tpu <command>`.

Capability parity with the reference CLI (python/ray/scripts/scripts.py,
click group :61 — `ray start/stop/status/submit/timeline/memory` plus the
state CLI `ray list ...`, experimental/state/state_cli.py), over the head
RPC protocol instead of GCS.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import click

from ray_tpu.scripts.head_daemon import address_file_path


def _load_file_token():
    """Adopt the daemon-minted cluster token from the address file so
    same-machine CLI clients authenticate (an explicit
    RAY_TPU_cluster_token env var wins)."""
    if os.environ.get("RAY_TPU_cluster_token"):
        return
    from ray_tpu.scripts.head_daemon import read_address_file
    _addr, token, _pid = read_address_file()
    if token:
        from ray_tpu._private.config import GlobalConfig
        if not GlobalConfig.cluster_token:
            GlobalConfig.apply_system_config({"cluster_token": token})


def _resolve_address(address):
    _load_file_token()
    if address:
        return address
    env = os.environ.get("RAY_TPU_ADDRESS")
    if env:
        return env
    from ray_tpu.scripts.head_daemon import read_address_file
    addr, _token, _pid = read_address_file()
    if addr:
        return addr
    raise click.ClickException(
        "No running cluster found: pass --address, set RAY_TPU_ADDRESS, "
        "or run `ray-tpu start --head` first.")


def _head_client(address):
    from ray_tpu.runtime.rpc import RpcClient
    return RpcClient(_resolve_address(address), timeout=30)


@click.group()
def cli():
    """TPU-native distributed runtime CLI."""


@cli.command()
@click.option("--head", is_flag=True, help="Start a head node here.")
@click.option("--address", default=None,
              help="Join an existing head (starts one more worker).")
@click.option("--num-workers", default=2, show_default=True)
@click.option("--resources", default='{"CPU": 2}', show_default=True,
              help="Per-worker resources as JSON.")
@click.option("--store-capacity", default=256 * 1024 * 1024,
              show_default=True)
@click.option("--block", is_flag=True,
              help="Run the head in the foreground.")
def start(head, address, num_workers, resources, store_capacity, block):
    """Start a head daemon or add a worker to a running head."""
    if head and address:
        raise click.ClickException("--head and --address are exclusive")
    if not head and not address and not os.path.exists(
            address_file_path()):
        raise click.ClickException("Pass --head to start a new cluster")
    if head:
        cmd = [sys.executable, "-m", "ray_tpu.scripts.head_daemon",
               "--num-workers", str(num_workers),
               "--resources", resources,
               "--store-capacity", str(store_capacity)]
        env = dict(os.environ)
        if block:
            os.execve(sys.executable, [sys.executable] + cmd[1:], env)
        proc = subprocess.Popen(cmd, env=env,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT,
                                start_new_session=True, text=True)
        deadline = time.time() + 60
        addr = None
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("RAY_TPU_HEAD_ADDRESS="):
                addr = line.strip().split("=", 1)[1]
                break
            if proc.poll() is not None:
                raise click.ClickException(
                    f"Head daemon exited: {line}")
        if addr is None:
            proc.terminate()
            raise click.ClickException("Head daemon did not report an "
                                       "address within 60s")
        click.echo(f"Started head at {addr} (pid {proc.pid}).")
        click.echo(f"Connect with ray_tpu.init(address={addr!r}) or "
                   f"RAY_TPU_ADDRESS={addr}")
    else:
        client = _head_client(address)
        wid = client.call("request_worker", json.loads(resources))
        click.echo(f"Started worker {wid}")


@cli.command()
@click.option("--address", default=None)
def stop(address):
    """Stop the running cluster."""
    from ray_tpu.scripts.head_daemon import read_address_file
    file_addr, _token, pid = read_address_file()
    # The pid/file belong to the LOCAL daemon: only touch them when
    # that is the cluster being stopped (no explicit --address, or an
    # --address matching the file), never when stopping a remote one.
    local_target = address is None or address == file_addr
    try:
        client = _head_client(address)
        client.call("shutdown", timeout=5)
    except Exception:
        pass
    # The daemon wrapper outlives the head's RPC shutdown: signal it
    # so the process tree actually exits (it removes the address file
    # itself on the way out).
    if local_target and pid:
        import signal as _signal
        try:
            os.kill(pid, _signal.SIGTERM)
            for _ in range(50):
                try:
                    os.kill(pid, 0)
                except OSError:
                    break
                time.sleep(0.1)
        except OSError:
            pass
    if local_target:
        path = address_file_path()
        if os.path.exists(path):
            os.remove(path)
    click.echo("Stopped.")


@cli.command()
@click.option("--address", default=None)
def status(address):
    """Cluster resources, workers, and jobs."""
    client = _head_client(address)
    total = client.call("cluster_resources")
    avail = client.call("available_resources")
    workers = client.call("list_workers")
    click.echo("Resources:")
    for k in sorted(total):
        click.echo(f"  {k}: {avail.get(k, 0.0):g}/{total[k]:g} free")
    click.echo(f"Workers ({len(workers)}):")
    for w in workers:
        state = "ALIVE" if w["alive"] else "DEAD"
        click.echo(f"  {w['worker_id']}: {state} "
                   f"{w['resources']} running={len(w['running_tasks'])}")
    try:
        jobs = client.call("list_jobs")
        if jobs:
            click.echo(f"Jobs ({len(jobs)}):")
            for j in jobs:
                click.echo(f"  {j['job_id']}: {j['status']} "
                           f"({j['entrypoint']!r})")
    except Exception:
        pass


@cli.command()
@click.option("--address", default=None)
@click.option("--working-dir", default=None)
@click.option("--submission-id", default=None)
@click.option("--no-wait", is_flag=True)
@click.argument("entrypoint", nargs=-1, required=True)
def submit(address, working_dir, submission_id, no_wait, entrypoint):
    """Submit a job: ray-tpu submit -- python my_script.py"""
    from ray_tpu.job import JobSubmissionClient
    addr = _resolve_address(address)
    client = JobSubmissionClient(addr)
    import shlex
    runtime_env = {"working_dir": working_dir} if working_dir else None
    job_id = client.submit_job(entrypoint=shlex.join(entrypoint),
                               submission_id=submission_id,
                               runtime_env=runtime_env)
    click.echo(f"Submitted {job_id}")
    if no_wait:
        return
    status_ = client.wait_until_finished(job_id, timeout=3600)
    click.echo(client.get_job_logs(job_id), nl=False)
    click.echo(f"Job {job_id}: {status_}")
    if status_ != "SUCCEEDED":
        sys.exit(1)


@cli.command()
@click.option("--address", default=None)
@click.argument("job_id")
def logs(address, job_id):
    """Print a job's captured output."""
    from ray_tpu.job import JobSubmissionClient
    client = JobSubmissionClient(_resolve_address(address))
    click.echo(client.get_job_logs(job_id), nl=False)


@cli.command()
@click.option("--address", default=None)
def memory(address):
    """Object-store usage (reference: `ray memory`)."""
    client = _head_client(address)
    stats = client.call("store_stats")
    click.echo(json.dumps(stats, indent=2))


@cli.command()
@click.option("--address", default=None)
@click.option("--prometheus", is_flag=True,
              help="Prometheus text exposition instead of JSON.")
def metrics(address, prometheus):
    """Cluster-wide metrics from the native shm segment."""
    client = _head_client(address)
    if prometheus:
        click.echo(client.call("metrics_prometheus"), nl=False)
    else:
        click.echo(json.dumps(client.call("metrics_snapshot"),
                              indent=2))


@cli.command()
@click.option("--address", default=None)
@click.option("--port", default=8265, type=int)
@click.option("--host", default="127.0.0.1")
def dashboard(address, port, host):
    """Serve the dashboard UI + JSON API (reference: `ray dashboard`).
    Attaches to the cluster, then blocks."""
    import time as _time

    import ray_tpu
    ray_tpu.init(address=_resolve_address(address),
                 ignore_reinit_error=True)
    from ray_tpu.dashboard import Dashboard
    dash = Dashboard(host=host, port=port).start()
    click.echo(f"dashboard at http://{host}:{dash.port}/")
    try:
        while True:
            _time.sleep(1)
    except KeyboardInterrupt:
        dash.stop()


@cli.command("client-proxy")
@click.option("--address", default=None,
              help="head address (host:port)")
@click.option("--port", default=10001, type=int)
def client_proxy(address, port):
    """Run a ray:// client proxy next to the head so remote drivers
    can connect with init(address='ray://host:port')."""
    from ray_tpu.runtime.client_proxy import serve_forever
    serve_forever(_resolve_address(address), port, echo=click.echo)


@cli.command("list")
@click.option("--address", default=None)
@click.argument("kind",
                type=click.Choice(["actors", "workers", "jobs"]))
def list_cmd(address, kind):
    """State listing (reference: `ray list actors` state CLI)."""
    client = _head_client(address)
    rows = client.call({"actors": "list_actors",
                        "workers": "list_workers",
                        "jobs": "list_jobs"}[kind])
    click.echo(json.dumps(rows, indent=2, default=str))


@cli.command()
@click.option("--output", "-o", default="timeline.json",
              show_default=True)
def timeline(output):
    """Export the local profile timeline as a Chrome trace
    (reference: `ray timeline`)."""
    import ray_tpu
    path = ray_tpu.timeline(output)
    click.echo(f"Wrote {path}")


@cli.group("serve")
def serve_group():
    """Serve deployments from the command line (reference: the
    `serve run/status/shutdown` CLI, serve/scripts.py)."""


def _serve_attach(address, standalone_ok=False):
    """Driver attach for serve subcommands: join the running cluster.
    Only `serve run` may fall back to starting a local runtime
    (standalone_ok); status/shutdown are queries and must not spawn a
    cluster just to report there is nothing to query."""
    import ray_tpu
    try:
        addr = _resolve_address(address)
    except click.ClickException:
        if not standalone_ok:
            raise
        addr = None
    ray_tpu.init(address=addr, ignore_reinit_error=True)
    return ray_tpu


@serve_group.command("run")
@click.argument("target")
@click.option("--address", default=None)
@click.option("--host", default="127.0.0.1", show_default=True)
@click.option("--port", default=8000, show_default=True, type=int)
@click.option("--blocking/--no-blocking", default=True,
              show_default=True)
def serve_run_cmd(target, address, host, port, blocking):
    """Import TARGET (module:attr — a deployment or bound node), run
    it, and expose the HTTP proxy."""
    import importlib
    sys.path.insert(0, os.getcwd())
    mod_name, _, attr = target.partition(":")
    if not attr:
        raise click.ClickException(
            f"target must be module:attr, got {target!r}")
    module = importlib.import_module(mod_name)
    try:
        app = getattr(module, attr)
    except AttributeError:
        raise click.ClickException(
            f"{mod_name!r} has no attribute {attr!r}")
    _serve_attach(address, standalone_ok=True)
    from ray_tpu import serve as serve_api
    from ray_tpu.serve.api import Deployment
    from ray_tpu.serve.http_proxy import start_http
    if isinstance(app, Deployment):
        app = app.bind()
    serve_api.run(app)
    names = sorted(serve_api.list_deployments())
    if not blocking:
        # The HTTP proxy lives in THIS process; advertising an
        # endpoint that dies on exit would be a lie. Deploy-only.
        click.echo(f"Deployed {names} (replicas stay up on the "
                   f"cluster; run without --no-blocking to serve "
                   f"HTTP, or reach them via serve handles)")
        return
    proxy = start_http(host, port)
    click.echo(f"Serving {names} at http://{host}:{proxy.port}/"
               f"<deployment>")
    click.echo("Ctrl-C to stop.")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        click.echo("Shutting down.")
        serve_api.shutdown()


@serve_group.command("status")
@click.option("--address", default=None)
def serve_status_cmd(address):
    """Deployment + replica status (reference: `serve status`)."""
    ray_tpu = _serve_attach(address)
    from ray_tpu.serve.controller import CONTROLLER_NAME
    try:
        ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        raise click.ClickException("Serve is not running here")
    from ray_tpu import serve as serve_api
    click.echo(json.dumps(serve_api.status(), indent=2, default=str))


@serve_group.command("shutdown")
@click.option("--address", default=None)
@click.option("--yes", "-y", is_flag=True,
              help="Skip the confirmation prompt.")
def serve_shutdown_cmd(address, yes):
    """Tear down all deployments (reference: `serve shutdown`)."""
    if not yes:
        click.confirm("Shut down all serve deployments?", abort=True)
    ray_tpu = _serve_attach(address)
    from ray_tpu.serve.controller import CONTROLLER_NAME
    try:
        ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        raise click.ClickException("Serve is not running here")
    from ray_tpu import serve as serve_api
    serve_api.shutdown()
    click.echo("Serve shut down.")


def main():
    cli()


if __name__ == "__main__":
    main()
