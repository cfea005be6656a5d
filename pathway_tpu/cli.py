"""Command-line interface — ``pathway-tpu spawn`` process launcher.

Parity with the reference CLI (``python/pathway/cli.py:53-175``): ``spawn``
launches N host processes with the ``PATHWAY_THREADS / PATHWAY_PROCESSES /
PATHWAY_FIRST_PORT / PATHWAY_PROCESS_ID / PATHWAY_RUN_ID`` env contract, and
``spawn-from-env`` re-reads the same flags from ``PATHWAY_SPAWN_ARGS``.

TPU-native difference: worker processes join through ``jax.distributed``
(coordinator at ``127.0.0.1:first_port``) instead of timely's TCP cluster
(reference ``src/engine/dataflow/config.rs:63-127``); the env names are kept
so reference deployment scripts keep working. The git-repository bootstrap
mode of the reference (``cli.py:30-66``, clones a repo into a temp venv) is
supported when GitPython is importable and gated off otherwise — this build
has zero network egress.
"""

from __future__ import annotations

import logging
import os
import shlex
import subprocess
import sys
import tempfile
import uuid
import venv
from pathlib import Path

from pathway_tpu.internals.config import environ_snapshot, pathway_config

import click

import pathway_tpu as pw


def plural(n: int, singular: str, plural_form: str) -> str:
    return f"{n} {singular if n == 1 else plural_form}"


def get_temporary_paths(temp_root: tempfile.TemporaryDirectory) -> tuple[Path, Path]:
    root = Path(temp_root.name)
    return root / "repository", root / "venv"


def checkout_repository(repository_url: str | None, branch: str | None):
    """Clone ``repository_url`` into a temp dir with a fresh venv (reference
    ``cli.py:30-50``). Requires GitPython + network; errors out cleanly
    when unavailable."""
    if repository_url is None:
        return None
    try:
        import git
    except ImportError:
        logging.error("To run the code from a Git repository please install GitPython")
        raise SystemExit(1)
    temp_root_directory = tempfile.TemporaryDirectory()
    repository_path, venv_path = get_temporary_paths(temp_root_directory)
    repository = git.Repo.clone_from(repository_url, repository_path)
    if branch is not None:
        repository.git.checkout(branch)
    venv.create(venv_path, with_pip=True)
    return temp_root_directory


def _local_tpu_chips() -> int:
    """TPU chips attached to this host, counted from their device nodes —
    the launcher itself must stay off JAX (a parent that touched the
    backend would hold the chips its children need)."""
    import glob

    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def _check_children_can_have_devices(processes: int, env: dict) -> None:
    """``spawn`` gives every child the same environment and assigns no
    chip to any of them, so on a TPU host each of M > 1 children asks for
    EVERY chip: the first to load the TPU runtime takes them all and the
    rest fail at backend start-up or hang waiting (a chip belongs to one
    process at a time). Refuse that launch up front with the way out,
    instead of letting the children hang. Children held to the CPU
    (``JAX_PLATFORMS=cpu``) never ask for a chip and are fine."""
    if processes <= 1:
        return
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return
    chips = _local_tpu_chips()
    if chips == 0:
        return
    raise click.ClickException(
        f"spawn --processes {processes} on a host with {chips} TPU "
        f"chip(s): the launcher assigns no chip to a child, so all "
        f"{processes} children would ask for the same chip(s) and all but "
        f"one would fail or hang. Run ONE process (-n 1 drives all "
        f"{chips} chip(s)), or set JAX_PLATFORMS=cpu to run the workers "
        f"on the CPU."
    )


def spawn_program(
    *,
    threads: int,
    processes: int,
    first_port: int,
    repository_url: str | None,
    branch: str | None,
    program: str,
    arguments: tuple[str, ...],
    env_base: dict[str, str],
) -> None:
    """Launch ``processes`` copies of ``program`` with the worker-topology env
    contract (reference ``cli.py:53-109``)."""
    _check_children_can_have_devices(processes, env_base)
    temp_root_directory = checkout_repository(repository_url, branch)
    if temp_root_directory is not None:
        repository_path, venv_path = get_temporary_paths(temp_root_directory)
        requirements_path = repository_path / "requirements.txt"
        if program.startswith("python"):
            program = os.fspath(venv_path / "bin" / program)
        if requirements_path.exists():
            pip_path = venv_path / "bin" / "pip"
            handle = subprocess.run(
                [os.fspath(pip_path), "install", "-r", os.fspath(requirements_path)],
                stderr=subprocess.STDOUT,
            )
            if handle.returncode != 0:
                logging.error("Failed to install requirements")
                raise RuntimeError("Failed to install dependencies")
        os.chdir(repository_path)

    processes_str = plural(processes, "process", "processes")
    workers_str = plural(processes * threads, "total worker", "total workers")
    click.echo(f"Preparing {processes_str} ({workers_str})", err=True)
    run_id = uuid.uuid4()
    process_handles: list[subprocess.Popen] = []
    try:
        for process_id in range(processes):
            env = env_base.copy()
            env["PATHWAY_THREADS"] = str(threads)
            env["PATHWAY_PROCESSES"] = str(processes)
            env["PATHWAY_FIRST_PORT"] = str(first_port)
            env["PATHWAY_PROCESS_ID"] = str(process_id)
            env["PATHWAY_RUN_ID"] = str(run_id)
            handle = subprocess.Popen([program, *arguments], env=env)
            process_handles.append(handle)
        for handle in process_handles:
            handle.wait()
    finally:
        for handle in process_handles:
            handle.terminate()
    # non-zero (incl. signal-killed, negative returncode) in any worker is a
    # failed run — don't let a clean worker's 0 mask it via max()
    sys.exit(0 if all(h.returncode == 0 for h in process_handles) else 1)


@click.group
@click.version_option(version=pw.__version__, prog_name="pathway-tpu")
def cli() -> None:
    pass


@cli.command(
    context_settings={"allow_interspersed_args": False, "show_default": True}
)
@click.option("-t", "--threads", metavar="N", type=int, default=1,
              help="number of logical workers (chips) per process")
@click.option("-n", "--processes", metavar="N", type=int, default=1,
              help="number of host processes")
@click.option("--first-port", type=int, metavar="PORT", default=10000,
              help="coordinator / first communication port")
@click.option("--record", is_flag=True,
              help="record data in the input connectors")
@click.option("--record-path", type=str, default="record",
              help="directory in which the record is saved")
@click.option("--repository-url", type=str,
              help="github repository to spawn the program from")
@click.option("--branch", type=str, help="branch if not the default")
@click.argument("program")
@click.argument("arguments", nargs=-1)
def spawn(threads, processes, first_port, record, record_path,
          repository_url, branch, program, arguments):
    """Launch PROGRAM as a multi-process pathway-tpu run."""
    env = environ_snapshot()
    if record:
        env["PATHWAY_REPLAY_STORAGE"] = record_path
        env["PATHWAY_SNAPSHOT_ACCESS"] = "record"
    spawn_program(
        threads=threads,
        processes=processes,
        first_port=first_port,
        repository_url=repository_url,
        branch=branch,
        program=program,
        arguments=arguments,
        env_base=env,
    )


@cli.command(
    context_settings={"allow_interspersed_args": False, "show_default": True}
)
@click.option("-t", "--threads", metavar="N", type=int, default=1,
              help="number of logical workers (chips) per process")
@click.option("-n", "--processes", metavar="N", type=int, default=1,
              help="number of host processes")
@click.option("--first-port", type=int, metavar="PORT", default=10000,
              help="coordinator / first communication port")
@click.option("--record-path", type=str, default="record",
              help="directory from which the record is replayed")
@click.option("--mode", type=click.Choice(["batch", "speedrun"]),
              default="batch", help="replay mode")
@click.option("--continue-after-replay", is_flag=True,
              help="keep processing live data after the replay finishes")
@click.option("--repository-url", type=str,
              help="github repository to spawn the program from")
@click.option("--branch", type=str, help="branch if not the default")
@click.argument("program")
@click.argument("arguments", nargs=-1)
def replay(threads, processes, first_port, record_path, mode,
           continue_after_replay, repository_url, branch, program, arguments):
    """Replay PROGRAM against a recorded input stream (reference
    ``cli.py:replay``)."""
    env = environ_snapshot()
    env["PATHWAY_REPLAY_STORAGE"] = record_path
    env["PATHWAY_SNAPSHOT_ACCESS"] = "replay"
    env["PATHWAY_PERSISTENCE_MODE"] = (
        "speedrun_replay" if mode == "speedrun" else mode
    )
    if continue_after_replay:
        env["PATHWAY_CONTINUE_AFTER_REPLAY"] = "true"
    spawn_program(
        threads=threads,
        processes=processes,
        first_port=first_port,
        repository_url=repository_url,
        branch=branch,
        program=program,
        arguments=arguments,
        env_base=env,
    )


@cli.command(context_settings={"allow_interspersed_args": False})
@click.argument("program")
@click.argument("arguments", nargs=-1)
def spawn_from_env(program, arguments):
    """Like ``spawn`` but flags come from $PATHWAY_SPAWN_ARGS (reference
    ``cli.py`` spawn-from-env)."""
    spawn_args = pathway_config.spawn_args
    argv = [*shlex.split(spawn_args), program, *arguments]
    spawn.main(args=argv, standalone_mode=True)


@cli.command()
@click.option("--url", type=str, default=None, metavar="URL",
              help="base URL of a running server (fetches URL/v1/statistics);"
                   " omit to read this process's in-memory registry")
@click.option("--as-json", is_flag=True, help="dump the raw snapshot as JSON")
def stats(url, as_json):
    """Pretty-print the unified observability snapshot (serving counters,
    latency histograms, scheduler summary) — local registry or a remote
    ``/v1/statistics`` endpoint."""
    import json

    if url is not None:
        import urllib.request

        endpoint = url.rstrip("/") + "/v1/statistics"
        with urllib.request.urlopen(endpoint, timeout=10.0) as resp:  # noqa: S310
            snap = json.loads(resp.read().decode())
    else:
        from pathway_tpu.engine import probes
        from pathway_tpu.internals import run as run_mod

        snap = probes.unified_snapshot(getattr(run_mod, "LAST_RUN_STATS", None))

    if as_json:
        click.echo(json.dumps(snap, indent=2, default=str))
        return

    serving = snap.get("serving") or {}

    def section(title: str, rows: dict) -> None:
        if not rows:
            return
        click.echo(title)
        width = max(len(str(k)) for k in rows)
        for k, v in rows.items():
            click.echo(f"  {str(k):<{width}}  {v}")

    latency = serving.get("latency") or {}
    for name, summary in sorted(latency.items()):
        if summary:
            section(f"latency/{name} (ms)", summary)
    section("prefix", serving.get("prefix") or {})
    section("spec", serving.get("spec") or {})
    section("cascade", serving.get("cascade") or {})
    section("dispatch", serving.get("dispatch") or {})
    section("occupancy", serving.get("occupancy") or {})
    section("lanes", serving.get("lanes") or {})
    section("tenants", serving.get("tenants") or {})
    section("kv_parked_bytes", {
        k: v for k, v in (serving.get("kv_parked_bytes") or {}).items() if v
    })
    section("retrieval", serving.get("retrieval") or {})
    hbm = snap.get("hbm") or {}
    section("hbm_bytes", hbm.get("current_bytes") or {})
    # per-device rows (PATHWAY_TPU_MESH): one section per mesh device,
    # plus the per-device total high-water capacity planning reads
    for dev, comps in sorted((hbm.get("device_bytes") or {}).items()):
        section(f"hbm_bytes/device={dev}", comps)
    section(
        "hbm_high_water_bytes/device",
        hbm.get("per_device_high_water_bytes") or {},
    )
    sched = snap.get("scheduler") or {}
    if sched:
        section("scheduler", {
            k: sched[k]
            for k in ("current_time", "epochs_total", "uptime_s", "finished")
            if k in sched
        })
    if not any((latency, serving.get("prefix"), serving.get("spec"),
                serving.get("cascade"), serving.get("dispatch"),
                serving.get("occupancy"),
                hbm.get("current_bytes"), sched)):
        click.echo("no metrics recorded yet")


@cli.command()
@click.option("--url", type=str, default=None, metavar="URL",
              help="base URL of a running server (fetches URL/v1/statistics);"
                   " omit to watch this process's in-memory registry")
@click.option("--interval", type=float, default=2.0, show_default=True,
              help="seconds between evaluations")
@click.option("--iterations", type=int, default=0,
              help="stop after N evaluations (0 = run until interrupted)")
@click.option("--fail-on-alert", is_flag=True,
              help="exit nonzero if any SLO alert is firing at the end")
def watch(url, interval, iterations, fail_on_alert):
    """Live SLO watchdog view: evaluates the configured
    ``PATHWAY_TPU_SLO_*`` objectives (or reads a remote server's
    ``/v1/statistics`` slo section) every ``--interval`` seconds and
    prints per-objective burn rates and alert state."""
    import json
    import time as time_mod

    def one_pass() -> tuple[dict, dict]:
        if url is not None:
            import urllib.request

            endpoint = url.rstrip("/") + "/v1/statistics"
            with urllib.request.urlopen(endpoint, timeout=10.0) as resp:  # noqa: S310
                snap = json.loads(resp.read().decode())
            return snap.get("slo") or {}, snap.get("serving") or {}
        from pathway_tpu.engine import probes
        from pathway_tpu.engine import slo as slo_mod

        wd = slo_mod.get_watchdog()
        state = wd.tick() if wd.objectives else wd.state()
        return state, probes.serving_snapshot()

    n = 0
    state: dict = {}
    try:
        while True:
            state, serving = one_pass()
            n += 1
            objectives = state.get("objectives") or {}
            if not objectives:
                click.echo(
                    "no SLO objectives configured "
                    "(set PATHWAY_TPU_SLO_* thresholds)"
                )
            else:
                stamp = time_mod.strftime("%H:%M:%S")
                alerting = state.get("alerting") or []
                click.echo(
                    f"[{stamp}] slo: "
                    + ("ALERT " + ",".join(alerting) if alerting else "ok")
                )
                for name, o in sorted(objectives.items()):
                    value = o.get("value")
                    vtxt = (
                        f"{value:.3f}{o.get('unit', '')}"
                        if isinstance(value, (int, float)) else "-"
                    )
                    mark = "!" if o.get("alert") else " "
                    click.echo(
                        f" {mark} {name:<16} value={vtxt:<12} "
                        f"target {o['kind']} {o['threshold']} "
                        f"burn fast={o['burn_fast']:.2f} "
                        f"slow={o['burn_slow']:.2f} "
                        f"breaches={o['breaches']}"
                    )
            lanes = serving.get("lanes") or {}
            tenants = serving.get("tenants") or {}
            if lanes:
                click.echo(
                    "   lanes: " + " ".join(
                        f"{k}={v:.0f}" for k, v in sorted(lanes.items())
                    )
                )
            if tenants:
                click.echo(
                    "   tenants queued: " + " ".join(
                        f"{k}={v:.0f}" for k, v in sorted(tenants.items())
                    )
                )
            if iterations and n >= iterations:
                break
            time_mod.sleep(max(interval, 0.05))
    except KeyboardInterrupt:
        pass
    if fail_on_alert and state.get("alerting"):
        raise SystemExit(1)


@cli.command()
@click.argument("profile")
@click.option("--out", type=str, default=None, metavar="PATH",
              help="write the winning tuned-config JSON here "
                   "[default: tuned-<profile>.json]")
@click.option("--seed", type=int, default=None,
              help="search seed [default: PATHWAY_TPU_TUNE_SEED]")
@click.option("--trials", type=int, default=None,
              help="cap the candidate pool (baseline + N-1 candidates) "
                   "[default: PATHWAY_TPU_TUNE_TRIALS; 0 = full ladder]")
@click.option("--scale", type=float, default=1.0, show_default=True,
              help="trace-scale multiplier for the first halving round")
@click.option("--rounds", type=int, default=3, show_default=True,
              help="successive-halving rounds")
@click.option("--smoke", is_flag=True,
              help="seconds-scale CI invocation: 2 trials, 1 round, "
                   "half-scale traces")
def tune(profile, out, seed, trials, scale, rounds, smoke):
    """Search the tunable flag surface for a workload PROFILE, validate
    survivors under the SLO watchdog + a chaos drill, and persist the
    winner as a tuned-config JSON for ``PATHWAY_TPU_TUNED_CONFIG``.

    Exits nonzero when validation rejects every candidate (the current
    defaults stay in force)."""
    import json

    from pathway_tpu.tuning import (
        Autotuner,
        PROFILES,
        TuneError,
        save_artifact,
        to_artifact,
    )

    if profile not in PROFILES:
        click.echo(
            f"unknown profile {profile!r}; available: {sorted(PROFILES)}",
            err=True,
        )
        raise SystemExit(2)
    if smoke:
        trials = 2 if trials is None else trials
        rounds = min(rounds, 1)
        scale = min(scale, 0.5)
    tuner = Autotuner(
        profile, seed=seed, max_trials=trials,
        base_scale=scale, rounds=rounds,
    )
    try:
        result = tuner.run()
    except TuneError as exc:
        click.echo(f"tune failed: {exc}", err=True)
        raise SystemExit(3) from exc
    path = out or f"tuned-{profile}.json"
    save_artifact(result, path)
    art = to_artifact(result)
    click.echo(json.dumps(
        {
            "profile": art["profile"],
            "headline": art["headline"],
            "direction": art["direction"],
            "flags": art["flags"],
            "score": art["score"],
            "baseline_score": art["baseline_score"],
            "trials": len(result.trials),
            "rejected": len(result.rejected),
            "artifact": path,
        },
        indent=2, sort_keys=True,
    ))
    click.echo(f"export PATHWAY_TPU_TUNED_CONFIG={path}", err=True)


@cli.group()
def fleet() -> None:
    """Replicated serving fleet: spawn replicas behind the
    prefix-affinity router, or inspect a running fleet."""


@fleet.command("serve", context_settings={
    "allow_interspersed_args": False, "show_default": True,
})
@click.option("-n", "--replicas", metavar="N", type=int, default=None,
              help="initial replica count "
                   "[default: PATHWAY_TPU_FLEET_REPLICAS]")
@click.option("--host", type=str, default="127.0.0.1",
              help="router bind host")
@click.option("--port", type=int, default=0,
              help="router bind port (0 = ephemeral)")
@click.option("--health-interval", type=float, default=None, metavar="S",
              help="seconds between supervisor ticks "
                   "[default: PATHWAY_TPU_FLEET_HEALTH_MS / 1000]")
@click.option("--boot-grace", type=float, default=120.0, metavar="S",
              help="seconds a never-yet-ready replica may spend booting "
                   "(jax import + first jit) before failed health probes "
                   "count toward draining it")
@click.argument("program")
@click.argument("arguments", nargs=-1)
def fleet_serve(replicas, host, port, health_interval, boot_grace,
                program, arguments):
    """Run PROGRAM as N supervised replicas behind the affinity router.

    Each replica is spawned with the single-process env contract
    (``PATHWAY_PROCESSES=1``, its own ``PATHWAY_FIRST_PORT``) and must
    start a REST server on that port — the router health-checks
    ``/healthz`` + ``/readyz``, forwards ``/v1/pw_ai_answer`` and
    ``/v1/retrieve`` with prefix affinity, and the supervisor drains,
    respawns and autoscales off the per-replica SLO burn signal.

    Requires ``PATHWAY_TPU_FLEET=1`` (the kill switch keeps the
    single-server path byte-identical when off)."""
    import time as time_mod
    import uuid as uuid_mod

    from pathway_tpu import serving

    if not serving.fleet_enabled():
        click.echo("PATHWAY_TPU_FLEET=0: fleet serving is switched off "
                   "(single-server path unchanged)", err=True)
        raise SystemExit(2)

    run_id = str(uuid_mod.uuid4())
    next_index = [0]

    def factory(replica_id: str):
        from pathway_tpu.serving.replica import (
            HttpReplica, free_port, spawn_replica_process,
        )

        idx = next_index[0]
        next_index[0] += 1
        rport = free_port(host)
        proc = spawn_replica_process(
            [program, *arguments, "--port", str(rport)],
            replica_index=idx, port=rport, run_id=run_id,
        )
        return HttpReplica(replica_id, f"http://{host}:{rport}", proc=proc)

    manager = serving.build_fleet(
        factory, replicas=replicas, health_interval_s=health_interval,
        boot_grace_s=boot_grace,
    )
    router_srv = serving.RouterServer(
        manager.router, manager=manager, host=host, port=port,
    ).start()
    manager.run_in_thread()
    click.echo(
        f"fleet router on http://{host}:{router_srv.port} "
        f"({len(manager.router)} replicas, run {run_id})", err=True,
    )
    try:
        while True:
            time_mod.sleep(1.0)
    except KeyboardInterrupt:
        pass
    finally:
        router_srv.stop()
        manager.shutdown()


@fleet.command("stats")
@click.option("--url", type=str, required=True, metavar="URL",
              help="base URL of a running fleet router "
                   "(fetches URL/v1/fleet)")
@click.option("--as-json", is_flag=True, help="dump the raw state as JSON")
def fleet_stats(url, as_json):
    """One-shot fleet state: members, ring, burn, respawns, events."""
    import json
    import urllib.request

    endpoint = url.rstrip("/") + "/v1/fleet"
    with urllib.request.urlopen(endpoint, timeout=10.0) as resp:  # noqa: S310
        state = json.loads(resp.read().decode())
    if as_json:
        click.echo(json.dumps(state, indent=2, default=str))
        return
    click.echo(
        f"fleet size {state.get('size')} "
        f"(min {state.get('min')} / max {state.get('max')}), "
        f"burn {state.get('burn', 0.0):.2f}, "
        f"respawns {state.get('respawns', 0)}"
    )
    for rid, info in sorted((state.get("replicas") or {}).items()):
        click.echo(
            f"  {rid:<14} kind={info.get('kind', '?'):<7} "
            f"fails={info.get('consecutive_failures', 0)}"
        )
    events = state.get("events") or []
    if events:
        click.echo("recent events:")
        for kind, rid in events[-10:]:
            click.echo(f"  {kind} {rid if rid else ''}")


@fleet.command("watch")
@click.option("--url", type=str, required=True, metavar="URL",
              help="base URL of a running fleet router")
@click.option("--interval", type=float, default=2.0, show_default=True,
              help="seconds between polls")
@click.option("--iterations", type=int, default=0,
              help="stop after N polls (0 = run until interrupted)")
def fleet_watch(url, interval, iterations):
    """Poll a fleet router's ``/v1/fleet`` and print size/burn lines."""
    import json
    import time as time_mod
    import urllib.request

    endpoint = url.rstrip("/") + "/v1/fleet"
    n = 0
    try:
        while True:
            with urllib.request.urlopen(endpoint, timeout=10.0) as resp:  # noqa: S310
                state = json.loads(resp.read().decode())
            n += 1
            stamp = time_mod.strftime("%H:%M:%S")
            click.echo(
                f"[{stamp}] size={state.get('size')} "
                f"burn={state.get('burn', 0.0):.2f} "
                f"respawns={state.get('respawns', 0)} "
                f"members={','.join(state.get('ring_members') or [])}"
            )
            if iterations and n >= iterations:
                break
            time_mod.sleep(max(interval, 0.05))
    except KeyboardInterrupt:
        pass


@cli.group()
def airbyte() -> None:
    """Airbyte connector scaffolding (reference ``cli.py:airbyte``)."""


@airbyte.command("create-source")
@click.argument("connection")
@click.option(
    "--image",
    default="airbyte/source-faker:0.1.4",
    help="any public Docker Airbyte source image",
)
def create_source(connection, image):
    """Write a starter YAML connection config for an Airbyte source.
    Running the source itself needs docker + network (gated here); the
    scaffold is generated locally."""
    import pathlib

    path = pathlib.Path(connection)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(
        "source:\n"
        f"  docker_image: {image}\n"
        "  config:\n"
        "    # fill in source-specific configuration here\n"
        "streams: []\n"
    )
    click.echo(
        f"Connection `{path.stem}` with source `{image}` created successfully"
    )


def main() -> None:
    cli.main()


if __name__ == "__main__":
    main()
