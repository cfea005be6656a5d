"""CLI tests — spawn env contract (reference ``python/pathway/tests/cli/``)."""

import subprocess
import sys


# single os.write so concurrent workers can't interleave mid-line on the
# shared stdout pipe (atomic for writes < PIPE_BUF)
PRINT_ENV = (
    "import os;"
    "os.write(1, (' '.join([os.environ['PATHWAY_PROCESS_ID'],"
    " os.environ['PATHWAY_PROCESSES'], os.environ['PATHWAY_THREADS'],"
    " os.environ['PATHWAY_FIRST_PORT']]) + '\\n').encode())"
)


def test_spawn_sets_topology_env(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text(PRINT_ENV)
    out = subprocess.run(
        [sys.executable, "-m", "pathway_tpu", "spawn", "-t", "2", "-n", "2",
         "--first-port", "12345", sys.executable, str(script)],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    lines = sorted(out.stdout.strip().splitlines())
    assert lines == ["0 2 2 12345", "1 2 2 12345"]


def test_spawn_record_flag(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text(
        "import os;"
        "print(os.environ.get('PATHWAY_REPLAY_STORAGE'),"
        " os.environ.get('PATHWAY_SNAPSHOT_ACCESS'))"
    )
    out = subprocess.run(
        [sys.executable, "-m", "pathway_tpu", "spawn", "--record",
         "--record-path", "recdir", sys.executable, str(script)],
        capture_output=True, text=True, timeout=60,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "recdir record"


def test_spawn_from_env(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text(PRINT_ENV)
    out = subprocess.run(
        [sys.executable, "-m", "pathway_tpu", "spawn-from-env",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=60,
        env={"PATHWAY_SPAWN_ARGS": "-t 3", "PATH": "/usr/bin:/bin",
             "HOME": "/root"},
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "0 1 3 10000"


def test_spawn_refuses_children_that_would_share_chips(monkeypatch):
    """``spawn -n M`` assigns no chip to a child: on a TPU host M > 1
    children would all ask for the same chips and all but one fail or
    hang, so the launcher refuses up front — unless the children are
    held to the CPU, or there is only one of them."""
    import click
    import pytest

    from pathway_tpu import cli

    monkeypatch.setattr(cli, "_local_tpu_chips", lambda: 4)
    with pytest.raises(click.ClickException, match="4 TPU chip"):
        cli._check_children_can_have_devices(2, {})
    cli._check_children_can_have_devices(1, {})
    cli._check_children_can_have_devices(2, {"JAX_PLATFORMS": "cpu"})
    monkeypatch.setattr(cli, "_local_tpu_chips", lambda: 0)
    cli._check_children_can_have_devices(2, {})
