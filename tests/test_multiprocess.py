"""Multi-process TCP-exchange tests (reference: cluster mode over localhost,
``pathway spawn --processes``; integration_tests/wordcount). Each test spawns
real OS processes that connect a peer mesh, shard sources, exchange rows by
key before stateful operators, and write per-process output shards."""

from __future__ import annotations

import json
import os
import random
import socket
import subprocess
import sys
import textwrap


def _free_ports(n: int) -> int:
    """The first of ``n`` consecutive ports nobody holds, BELOW the range
    the kernel hands out by itself. Process ``pid`` of a cluster listens on
    ``PATHWAY_FIRST_PORT + pid``, so every one of them has to be free, not
    the first alone. ``bind(0)`` cannot find them: Linux gives it odd ports
    only and gives every outgoing connection an even one, so the port after
    a ``bind(0)`` port is where all the clients of all the tests running
    beside this one draw their source ports, and where those stay in
    TIME_WAIT for a minute: a listener cannot bind there (``SO_REUSEADDR``
    or not), process 1 dies on ``EADDRINUSE`` and process 0 waits its 60 s
    for a peer that never comes."""
    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            ephemeral = int(f.read().split()[0])
    except OSError:
        ephemeral = 32768
    # above the per-process MetricsServer's 20000 + process_id
    for _ in range(256):
        first = random.randrange(20100, ephemeral - n)
        held = []
        try:
            for port in range(first, first + n):
                s = socket.socket()
                held.append(s)
                # without SO_REUSEADDR, so that any holder refuses it
                s.bind(("127.0.0.1", port))
            return first
        except OSError:
            continue
        finally:
            for s in held:
                s.close()
    raise RuntimeError(f"no {n} consecutive free ports below {ephemeral}")


def _spawn(script: str, tmp_path, processes: int):
    procs = []
    port = _free_ports(processes)
    for pid in range(processes):
        repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = dict(
            os.environ,
            PATHWAY_PROCESSES=str(processes),
            PATHWAY_PROCESS_ID=str(pid),
            PATHWAY_FIRST_PORT=str(port),
            JAX_PLATFORMS="cpu",
            PYTHONPATH=repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", script],
                env=env,
                cwd=str(tmp_path),
                stdout=subprocess.PIPE,
                stderr=subprocess.PIPE,
                text=True,
            )
        )
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=180)
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, f"worker failed rc={rc}\nstdout:{out}\nstderr:{err}"
    return outs


def _read_shards(tmp_path, basename: str, processes: int):
    rows = []
    for pid in range(processes):
        fp = os.path.join(tmp_path, f"{basename}.{pid}")
        if not os.path.exists(fp):
            continue
        with open(fp) as f:
            for line in f:
                rows.append(json.loads(line))
    return rows


def test_two_process_wordcount(tmp_path):
    """Words from files sharded across 2 processes; groupby exchanges rows
    by group key so every word's count is complete on exactly one process."""
    data = tmp_path / "in"
    data.mkdir()
    # several files so both processes get a share (files shard by path hash)
    words = ["alpha", "beta", "gamma", "delta"]
    expected: dict[str, int] = {}
    for i in range(8):
        lines = [words[(i + j) % 4] for j in range(i + 1)]
        for w in lines:
            expected[w] = expected.get(w, 0) + 1
        (data / f"f{i}.jsonl").write_text(
            "".join(json.dumps({"word": w}) + "\n" for w in lines)
        )

    script = textwrap.dedent(
        """
        import pathway_tpu as pw

        class S(pw.Schema):
            word: str

        t = pw.io.jsonlines.read("in", schema=S, mode="static")
        counts = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
        pw.io.jsonlines.write(counts, "out.jsonl")
        pw.run()
        """
    )
    _spawn(script, tmp_path, processes=2)
    rows = _read_shards(tmp_path, "out.jsonl", 2)
    got: dict[str, int] = {}
    for r in rows:
        if r["diff"] > 0:
            got[r["word"]] = got.get(r["word"], 0) + r["c"] * r["diff"]
        else:
            got[r["word"]] = got.get(r["word"], 0) - r["c"] * (-r["diff"])
    # net value per word across shards must equal the true count
    final = {w: c for w, c in got.items() if c}
    assert final == expected

    # each word's final row must live on exactly ONE process (sharded state)
    owners: dict[str, set] = {}
    for pid in range(2):
        fp = os.path.join(tmp_path, f"out.jsonl.{pid}")
        if not os.path.exists(fp):
            continue
        with open(fp) as f:
            for line in f:
                r = json.loads(line)
                owners.setdefault(r["word"], set()).add(pid)
    for w, pids in owners.items():
        assert len(pids) == 1, f"word {w!r} appeared on processes {pids}"


def test_two_process_exchange_soak(tmp_path):
    """Exchange soak (VERDICT r5 item 7): enough rows that every epoch
    forces multiple TCP exchange flushes — a pipeline whose groupby AND
    join both reshuffle 120k rows across the 2-process mesh must produce
    byte-identical net results to the single-process run."""
    import numpy as np

    rng = np.random.default_rng(5)
    n_rows, n_users, n_files = 120_000, 500, 6
    data = tmp_path / "data"
    (data / "orders").mkdir(parents=True)
    (data / "users").mkdir()
    uids = rng.integers(0, n_users, n_rows)
    amounts = rng.integers(1, 100, n_rows)
    per = n_rows // n_files
    for fi in range(n_files):
        sl = slice(fi * per, (fi + 1) * per)
        (data / "orders" / f"f{fi}.jsonl").write_text(
            "".join(
                '{"uid": %d, "amount": %d}\n' % (u, a)
                for u, a in zip(uids[sl].tolist(), amounts[sl].tolist())
            )
        )
    (data / "users" / "users.jsonl").write_text(
        "".join(
            '{"uid": %d, "tier": "t%d"}\n' % (u, u % 7)
            for u in range(n_users)
        )
    )

    script = textwrap.dedent(
        """
        import pathway_tpu as pw

        class Orders(pw.Schema):
            uid: int
            amount: int

        class Users(pw.Schema):
            uid: int
            tier: str

        orders = pw.io.jsonlines.read("in/orders", schema=Orders,
                                      mode="static")
        users = pw.io.jsonlines.read("in/users", schema=Users,
                                     mode="static")
        j = orders.join(users, orders.uid == users.uid).select(
            orders.amount, users.tier
        )
        per_tier = j.groupby(j.tier).reduce(
            j.tier, total=pw.reducers.sum(j.amount),
            n=pw.reducers.count(),
        )
        pw.io.jsonlines.write(per_tier, "out.jsonl")
        pw.run()
        """
    )

    def net(rows):
        got: dict = {}
        for r in rows:
            sign = 1 if r["diff"] > 0 else -1
            key = r["tier"]
            t, n = got.get(key, (0, 0))
            got[key] = (t + sign * r["total"], n + sign * r["n"])
        return {k: v for k, v in got.items() if v != (0, 0)}

    for sub in ("multi", "single"):
        rd = tmp_path / sub
        rd.mkdir()
        (rd / "in").symlink_to(data)
    _spawn(script, tmp_path / "multi", processes=2)
    multi = net(_read_shards(tmp_path / "multi", "out.jsonl", 2))
    _spawn(script, tmp_path / "single", processes=1)
    single_rows = []
    with open(tmp_path / "single" / "out.jsonl") as f:
        single_rows = [json.loads(line) for line in f]
    single = net(single_rows)
    assert multi == single
    assert sum(n for _t, n in multi.values()) == n_rows
    assert len(multi) == 7


def test_two_process_join(tmp_path):
    """Join keys co-locate via exchange: matches happen even when the two
    sides of a key are read by different processes."""
    data_l = tmp_path / "left"
    data_r = tmp_path / "right"
    data_l.mkdir()
    data_r.mkdir()
    for i in range(6):
        (data_l / f"l{i}.jsonl").write_text(
            json.dumps({"k": f"key{i}", "x": i}) + "\n"
        )
        # different file names => likely a different owning process
        (data_r / f"zz_other_{i}.jsonl").write_text(
            json.dumps({"k": f"key{i}", "y": i * 10}) + "\n"
        )

    script = textwrap.dedent(
        """
        import pathway_tpu as pw

        class L(pw.Schema):
            k: str
            x: int

        class R(pw.Schema):
            k: str
            y: int

        lt = pw.io.jsonlines.read("left", schema=L, mode="static")
        rt = pw.io.jsonlines.read("right", schema=R, mode="static")
        j = lt.join(rt, lt.k == rt.k).select(lt.k, lt.x, rt.y)
        pw.io.jsonlines.write(j, "out.jsonl")
        pw.run()
        """
    )
    _spawn(script, tmp_path, processes=2)
    rows = [r for r in _read_shards(tmp_path, "out.jsonl", 2) if r["diff"] > 0]
    assert len(rows) == 6
    for r in rows:
        assert r["y"] == r["x"] * 10


def test_two_process_streaming_updates(tmp_path):
    """Streaming mode: files appear over time on both processes' shards;
    counts stay correct across exchanged updates and the final merged state
    matches the total stream."""
    data = tmp_path / "in"
    data.mkdir()
    (data / "seed0.jsonl").write_text(
        json.dumps({"word": "alpha"}) + "\n" + json.dumps({"word": "beta"}) + "\n"
    )

    script = textwrap.dedent(
        """
        import json, os, threading, time
        import pathway_tpu as pw

        class S(pw.Schema):
            word: str

        t = pw.io.jsonlines.read("in", schema=S, mode="streaming")
        counts = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
        pw.io.jsonlines.write(counts, "out.jsonl")

        def counts_now():
            # the net state over BOTH processes' shards, whole lines only
            net = {}
            for pid in range(2):
                try:
                    with open(f"out.jsonl.{pid}") as f:
                        lines = f.read().split("\\n")[:-1]
                except FileNotFoundError:
                    continue
                for r in map(json.loads, lines):
                    k = (r["word"], r["c"])
                    net[k] = net.get(k, 0) + r["diff"]
            return {w: c for (w, c), d in net.items() if d > 0}

        def wait_for(least):
            # a state, not a time: under six test workers a fixed sleep is
            # a guess at how long a peer takes to start and to poll (counts
            # only grow here, and a late process may find them grown)
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                now = counts_now()
                if all(now.get(w, 0) >= c for w, c in least.items()):
                    return
                time.sleep(0.05)

        def feeder():
            wait_for({"alpha": 1, "beta": 1})
            if os.environ["PATHWAY_PROCESS_ID"] == "0":
                # appears in one piece: the reader never sees half a file
                with open("late1.tmp", "w") as f:
                    f.write(json.dumps({"word": "alpha"}) + "\\n")
                    f.write(json.dumps({"word": "gamma"}) + "\\n")
                os.replace("late1.tmp", "in/late1.jsonl")
            wait_for({"alpha": 2, "beta": 1, "gamma": 1})
            for c in pw.G.connectors:
                c._stop.set()
                c.close()

        threading.Thread(target=feeder, daemon=True).start()
        pw.run()
        """
    )
    _spawn(script, tmp_path, processes=2)
    rows = _read_shards(tmp_path, "out.jsonl", 2)
    net: dict[tuple, int] = {}
    for r in rows:
        net[(r["word"], r["c"])] = net.get((r["word"], r["c"]), 0) + r["diff"]
    final = {w: c for (w, c), d in net.items() if d > 0}
    assert final == {"alpha": 2, "beta": 1, "gamma": 1}


def test_two_process_recovery_resume(tmp_path):
    """Persistence + cluster mode (the reference's recovery rig shape,
    integration_tests/wordcount): a 2-process persistent run, then a second
    2-process run with extra input resumes from snapshots and produces
    combined counts."""
    src = tmp_path / "src"
    src.mkdir()
    (src / "a.jsonl").write_text(
        "".join(json.dumps({"word": w}) + "\n" for w in ["cat", "dog", "cat"])
    )

    script_tpl = textwrap.dedent(
        """
        import pathway_tpu as pw

        class S(pw.Schema):
            word: str

        t = pw.io.jsonlines.read("src", schema=S, mode="static",
                                 persistent_id="words-src")
        counts = t.groupby(t.word).reduce(t.word, c=pw.reducers.count())
        pw.io.jsonlines.write(counts, "OUT")
        pw.run(persistence_config=pw.persistence.Config.simple_config(
            pw.persistence.Backend.filesystem("store")))
        """
    )
    _spawn(script_tpl.replace("OUT", "out1.jsonl"), tmp_path, processes=2)
    rows = _read_shards(tmp_path, "out1.jsonl", 2)
    net: dict[tuple, int] = {}
    for r in rows:
        net[(r["word"], r["c"])] = net.get((r["word"], r["c"]), 0) + r["diff"]
    assert {w: c for (w, c), d in net.items() if d > 0} == {"cat": 2, "dog": 1}

    (src / "b.jsonl").write_text(
        "".join(json.dumps({"word": w}) + "\n" for w in ["cat", "bird"])
    )
    _spawn(script_tpl.replace("OUT", "out2.jsonl"), tmp_path, processes=2)
    rows = _read_shards(tmp_path, "out2.jsonl", 2)
    net = {}
    for r in rows:
        net[(r["word"], r["c"])] = net.get((r["word"], r["c"]), 0) + r["diff"]
    assert {w: c for (w, c), d in net.items() if d > 0} == {
        "cat": 3, "dog": 1, "bird": 1,
    }


def test_two_process_knn_sees_full_corpus(tmp_path):
    """External-index additions broadcast to every process: a query owned by
    either process must retrieve the exact nearest doc regardless of which
    process read that doc's file. Queries arrive AFTER the docs (as-of-now
    semantics: a query only sees documents committed before it)."""
    import numpy as np

    data = tmp_path / "docs"
    data.mkdir()
    rng = np.random.default_rng(5)
    vecs = rng.normal(size=(20, 8))
    for i in range(20):
        (data / f"doc{i}.jsonl").write_text(
            json.dumps({"doc": f"d{i}", "vec": vecs[i].tolist()}) + "\n"
        )
    qdir = tmp_path / "qs"
    qdir.mkdir()
    # query payloads staged OUTSIDE the watched dir; the feeder moves them
    # in once the docs are ingested
    staged = tmp_path / "staged"
    staged.mkdir()
    for i, qi in enumerate((3, 7, 11, 16)):
        (staged / f"q{i}.jsonl").write_text(
            json.dumps({"qid": f"q{qi}", "qvec": (vecs[qi] + 1e-3).tolist()})
            + "\n"
        )

    script = textwrap.dedent(
        """
        import os, shutil, threading, time
        import pathway_tpu as pw
        from pathway_tpu.stdlib.indexing import BruteForceKnn, DataIndex

        class D(pw.Schema):
            doc: str
            vec: list

        class Q(pw.Schema):
            qid: str
            qvec: list

        docs = pw.io.jsonlines.read("docs", schema=D, mode="streaming")
        qs = pw.io.jsonlines.read("qs", schema=Q, mode="streaming")
        index = DataIndex(docs, BruteForceKnn(docs.vec, dimensions=8))
        res = index.query_as_of_now(qs.qvec, number_of_matches=1).select(
            pw.this.doc
        )
        joined = qs.join(res, qs.id == res.id, id=qs.id).select(
            qs.qid, hit=res.doc
        )
        pw.io.jsonlines.write(joined, "out.jsonl")

        def feeder():
            time.sleep(2.5)  # all doc files ingested + broadcast by now
            if os.environ["PATHWAY_PROCESS_ID"] == "0":
                for f in sorted(os.listdir("staged")):
                    shutil.move(os.path.join("staged", f),
                                os.path.join("qs", f))
            time.sleep(2.5)
            for c in pw.G.connectors:
                c._stop.set()
                c.close()

        threading.Thread(target=feeder, daemon=True).start()
        pw.run()
        """
    )
    _spawn(script, tmp_path, processes=2)
    rows = [r for r in _read_shards(tmp_path, "out.jsonl", 2) if r["diff"] > 0]
    assert len(rows) == 4
    for r in rows:
        hit = r["hit"]
        if isinstance(hit, (list, tuple)):
            hit = hit[0]
        assert hit == f"d{r['qid'][1:]}", rows


# Bellman-Ford-style relaxation body shared by the distributed-iterate
# tests (parameterized by output filename; edges come from the "edges" dir)
_RELAX_SCRIPT = """
import pathway_tpu as pw

class E(pw.Schema):
    u: int
    v: int
    w: float

edges = pw.io.jsonlines.read("edges", schema=E, mode="static")
verts = edges.select(n=edges.u).concat_reindex(edges.select(n=edges.v))
dist0 = verts.groupby(verts.n).reduce(
    verts.n, d=pw.if_else(verts.n == 0, 0.0, 1e18)
)

def relax(dist, edges):
    cand = dist.join(edges, dist.n == edges.u).select(
        n=edges.v, d=dist.d + edges.w
    )
    both = dist.select(dist.n, dist.d).concat_reindex(cand)
    nd = both.groupby(both.n).reduce(both.n, d=pw.reducers.min(both.d))
    return dict(dist=nd, edges=edges)

res = pw.iterate(relax, dist=dist0, edges=edges)
out = res.dist
pw.io.jsonlines.write(out.filter(out.d < 1e17), {out_file!r})
{extra}
pw.run(monitoring_level=pw.MonitoringLevel.NONE)
"""


def _write_edges(tmp_path, edges):
    data = tmp_path / "edges"
    data.mkdir()
    for i, (u, v, w) in enumerate(edges):
        (data / f"e{i}.jsonl").write_text(
            json.dumps({"u": u, "v": v, "w": w}) + "\n"
        )


def _net_distances(rows):
    """Fold an update stream of shard outputs into final {n: d} state (two
    processes' static commits may land in different epochs, so the sink
    legitimately logs intermediate relaxations with retractions). A vertex
    with MORE than one surviving distance means a lost retraction — fail
    loudly instead of letting dict insertion order pick a winner."""
    net: dict = {}
    for r in rows:
        net[(r["n"], r["d"])] = net.get((r["n"], r["d"]), 0) + r["diff"]
    out: dict = {}
    for (n, d), c in net.items():
        if c > 0:
            assert n not in out, (
                f"vertex {n} has several live distances ({out[n]}, {d}): "
                "a retraction was lost in the update stream"
            )
            out[n] = d
    return out


def test_two_process_iterate_shortest_paths(tmp_path):
    """pw.iterate under the exchange mesh (VERDICT item 8): a Bellman-Ford
    style relaxation whose groupby/join rounds span BOTH processes must
    converge to the same distances a single process computes."""
    # a chain 0->1->2->3->4->5 plus a shortcut 0->3; enough files that both
    # processes own a share of the edge set
    _write_edges(tmp_path, [(0, 1, 1.0), (1, 2, 1.0), (2, 3, 1.0),
                            (3, 4, 1.0), (4, 5, 1.0), (0, 3, 2.5)])
    script = _RELAX_SCRIPT.format(out_file="dists.jsonl", extra="")
    _spawn(script, tmp_path, 2)
    rows = _read_shards(tmp_path, "dists.jsonl", 2)
    got = _net_distances(rows)
    assert got == {0: 0.0, 1: 1.0, 2: 2.0, 3: 2.5, 4: 3.5, 5: 4.5}, got


def test_two_process_iterate_multi_output(tmp_path):
    """Multi-table iterate: one distributed fixpoint per epoch, sibling
    outputs served from the primary's cached results — both outputs must
    be complete and consistent across the mesh."""
    _write_edges(tmp_path, [(0, 1, 1.0), (1, 2, 1.0), (0, 2, 5.0)])
    script = _RELAX_SCRIPT.format(
        out_file="dist.jsonl",
        extra=(
            "pw.io.jsonlines.write(\n"
            "    res.edges.select(res.edges.u, res.edges.v), \"edges_out.jsonl\"\n"
            ")"
        ),
    )
    _spawn(script, tmp_path, 2)
    rows = _read_shards(tmp_path, "dist.jsonl", 2)
    dist = _net_distances(rows)
    assert dist == {0: 0.0, 1: 1.0, 2: 2.0}, dist
    eo = sorted(
        (r["u"], r["v"]) for r in _read_shards(tmp_path, "edges_out.jsonl", 2)
    )
    assert eo == [(0, 1), (0, 2), (1, 2)], eo


def test_two_process_two_thread_iterate(tmp_path, monkeypatch):
    """iterate under BOTH the exchange mesh and PATHWAY_THREADS=2: the
    primary/sibling design must hold when same-level operators step from
    worker threads (control tags and subgraph state are per-primary)."""
    _write_edges(tmp_path, [(0, 1, 1.0), (1, 2, 1.0), (2, 3, 1.0),
                            (0, 3, 10.0)])
    script = _RELAX_SCRIPT.format(out_file="dists.jsonl", extra="")
    monkeypatch.setenv("PATHWAY_THREADS", "2")
    _spawn(script, tmp_path, 2)
    rows = _read_shards(tmp_path, "dists.jsonl", 2)
    dist = _net_distances(rows)
    assert dist == {0: 0.0, 1: 1.0, 2: 2.0, 3: 3.0}, dist
    # final row of each vertex lives on exactly one shard
    finals: dict = {}
    for pid in range(2):
        fp = os.path.join(tmp_path, f"dists.jsonl.{pid}")
        if not os.path.exists(fp):
            continue
        with open(fp) as f:
            shard_rows = [json.loads(line) for line in f]
        for n in _net_distances(shard_rows):
            finals.setdefault(n, set()).add(pid)
    assert all(len(pids) == 1 for pids in finals.values()), finals
