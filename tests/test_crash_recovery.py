"""Crash-consistency torture test — the scaled analog of the reference's
``integration_tests/wordcount`` recovery rig (kill/restart with persistent
storage, exactly-once final counts)."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROG = r"""
import json, os, sys, threading, time
import pathway_tpu as pw

class S(pw.Schema):
    word: str

src = os.environ["WC_SRC"]
out = os.environ["WC_OUT"]

t = pw.io.jsonlines.read(src, schema=S, mode="streaming",
                         refresh_interval=0.1, persistent_id="words")
counts = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
pw.io.jsonlines.write(counts, out)

# stop the (otherwise endless) streaming run once a marker file appears
def stopper():
    while not os.path.exists(os.environ["WC_STOP"]):
        time.sleep(0.1)
    for c in pw.G.connectors:
        c._stop.set()
        c.close()

threading.Thread(target=stopper, daemon=True).start()
pw.run()
"""


def _final_counts(path):
    net: dict = {}
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            net[rec["word"]] = net.get(rec["word"], 0) + (
                rec["c"] * (1 if rec["diff"] > 0 else -1)
            )
    return {k: v for k, v in net.items() if v}


@pytest.mark.timeout(120)
def test_sigkill_midrun_then_restart_exactly_once(tmp_path):
    src = tmp_path / "src"
    src.mkdir()
    store = tmp_path / "store"
    prog = tmp_path / "prog.py"
    prog.write_text(PROG)
    stop_marker = tmp_path / "stop"

    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        WC_SRC=str(src),
        WC_OUT=str(tmp_path / "out1.jsonl"),
        WC_STOP=str(stop_marker),
        PATHWAY_REPLAY_STORAGE=str(store),
        JAX_PLATFORMS="cpu",
        # kill windows are calibrated against cold-start pacing; a warm
        # persistent compile cache would let a cycle finish before its
        # SIGKILL, leaving the recovery path nothing to exercise
        JAX_ENABLE_COMPILATION_CACHE="false",
    )

    # phase 1: stream two files in, then SIGKILL without warning
    (src / "a.jsonl").write_text(
        "".join(json.dumps({"word": w}) + "\n" for w in ["cat", "dog", "cat"])
    )
    p = subprocess.Popen([sys.executable, str(prog)], env=env)
    try:
        deadline = time.time() + 60
        out1 = tmp_path / "out1.jsonl"
        while time.time() < deadline:
            if out1.exists() and _final_counts(out1).get("cat") == 2:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("phase 1 never produced counts")
        # more data arrives, give the connector a beat to commit it
        (src / "b.jsonl").write_text(
            "".join(json.dumps({"word": w}) + "\n" for w in ["cat", "bird"])
        )
        while time.time() < deadline:
            if _final_counts(out1).get("cat") == 3:
                break
            time.sleep(0.2)
        os.kill(p.pid, signal.SIGKILL)
    finally:
        p.wait(timeout=30)

    # phase 2: restart against the same store with the inputs still on disk
    # plus one new file; final counts must be exactly-once across the crash
    (src / "c.jsonl").write_text(json.dumps({"word": "dog"}) + "\n")
    env["WC_OUT"] = str(tmp_path / "out2.jsonl")
    stop_marker.write_text("")  # makes run() terminate after quiescing

    p2 = subprocess.Popen([sys.executable, str(prog)], env=env)
    p2.wait(timeout=60)
    assert p2.returncode == 0

    counts = _final_counts(tmp_path / "out2.jsonl")
    assert counts == {"cat": 3, "dog": 2, "bird": 1}


@pytest.mark.timeout(300)
def test_kill_restart_cycles_exactly_once(tmp_path):
    """Torture rig: repeated SIGKILL at varied points mid-stream, new data
    arriving between crashes, then one graceful run — final counts must be
    exactly-once (analog of the reference's
    ``integration_tests/wordcount/test_recovery.py`` kill/restart loop)."""
    import random

    rng = random.Random(7)
    src = tmp_path / "src"
    src.mkdir()
    store = tmp_path / "store"
    prog = tmp_path / "prog.py"
    prog.write_text(PROG)
    stop_marker = tmp_path / "stop"

    vocab = ["alpha", "beta", "gamma", "delta", "epsilon"]
    expected: dict[str, int] = {}

    def add_file(name: str, n: int) -> None:
        words = [rng.choice(vocab) for _ in range(n)]
        for w in words:
            expected[w] = expected.get(w, 0) + 1
        (src / name).write_text(
            "".join(json.dumps({"word": w}) + "\n" for w in words)
        )

    add_file("f0.jsonl", 2000)
    add_file("f1.jsonl", 2000)

    def env_for(cycle: int) -> dict:
        return dict(
            os.environ,
            PYTHONPATH=REPO,
            WC_SRC=str(src),
            WC_OUT=str(tmp_path / f"out{cycle}.jsonl"),
            WC_STOP=str(stop_marker),
            PATHWAY_REPLAY_STORAGE=str(store),
            JAX_PLATFORMS="cpu",
            JAX_ENABLE_COMPILATION_CACHE="false",  # cold pacing: see above
        )

    kill_delays = [1.0, 2.5, 4.0, 1.5]
    for cycle, delay in enumerate(kill_delays):
        p = subprocess.Popen([sys.executable, str(prog)], env=env_for(cycle))
        try:
            time.sleep(delay)
            os.kill(p.pid, signal.SIGKILL)
        finally:
            p.wait(timeout=30)
        # stream more data in between crashes
        add_file(f"g{cycle}.jsonl", 500)

    # final graceful run: quiesce after one full pass, then exit cleanly
    stop_marker.write_text("")
    final = len(kill_delays)
    p = subprocess.Popen([sys.executable, str(prog)], env=env_for(final))
    p.wait(timeout=120)
    assert p.returncode == 0

    counts = _final_counts(tmp_path / f"out{final}.jsonl")
    assert counts == expected


@pytest.mark.timeout(360)
def test_recovery_torture_at_scale(tmp_path):
    """Reference-scale recovery torture (mirroring
    ``integration_tests/wordcount/base.py`` which replays a multi-million
    line wordcount through kill/restart cycles): millions of jsonlines
    rows streamed through ``pw.run()`` with persistence, >= 3 SIGKILLs at
    staggered points, then one graceful run — the final counts must equal
    the batch truth EXACTLY (no loss, no double counting).

    Fixed 5M-row workload (the reference rig's scale), exact-equality
    assertion; the 360s cap is the budget on the 1-core gate box."""
    import numpy as np

    src = tmp_path / "src"
    src.mkdir()
    store = tmp_path / "store"
    prog = tmp_path / "prog.py"
    prog.write_text(PROG)
    stop_marker = tmp_path / "stop"

    rng = np.random.default_rng(11)
    vocab = np.array([f"w{i}" for i in range(4096)])
    n_rows, n_files = 5_000_000, 10
    per = n_rows // n_files
    expected: dict[str, int] = {}
    for fi in range(n_files):
        words = vocab[rng.integers(0, len(vocab), per)]
        uniq, cnt = np.unique(words, return_counts=True)
        for w, c in zip(uniq.tolist(), cnt.tolist()):
            expected[w] = expected.get(w, 0) + c
        (src / f"f{fi}.jsonl").write_text(
            "".join('{"word": "%s"}\n' % w for w in words.tolist())
        )

    def env_for(cycle: int) -> dict:
        return dict(
            os.environ,
            PYTHONPATH=REPO,
            WC_SRC=str(src),
            WC_OUT=str(tmp_path / f"out{cycle}.jsonl"),
            WC_STOP=str(stop_marker),
            PATHWAY_REPLAY_STORAGE=str(store),
            JAX_PLATFORMS="cpu",
            JAX_ENABLE_COMPILATION_CACHE="false",  # cold pacing: see above
        )

    # three SIGKILLs at staggered points mid-ingest (late enough that
    # real progress was snapshotted, early enough that work remains)
    for cycle, delay in enumerate((8.0, 12.0, 10.0)):
        p = subprocess.Popen([sys.executable, str(prog)], env=env_for(cycle))
        try:
            time.sleep(delay)
            os.kill(p.pid, signal.SIGKILL)
        finally:
            p.wait(timeout=60)

    stop_marker.write_text("")
    p = subprocess.Popen([sys.executable, str(prog)], env=env_for(3))
    p.wait(timeout=240)
    assert p.returncode == 0

    counts = _final_counts(tmp_path / "out3.jsonl")
    total = sum(counts.values())
    assert total == n_rows, f"streamed {total} rows, expected {n_rows}"
    assert counts == expected
    print(f"recovery torture: {n_rows} rows, 3 SIGKILLs, exactly-once")
