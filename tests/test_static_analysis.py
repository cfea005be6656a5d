"""graft-lint (`pathway_tpu/analysis/`): one positive + one negative
fixture per rule through `analyze_source`, the registry-wide checks
through their injectable entry points, the runtime lock sanitizer
(seeded order inversion, unguarded write, clean threaded runs), and the
tier-1 gate: the repo itself must analyze clean against the checked-in
baseline, and the README rule table must be generated output."""

import json
import os
import re
import subprocess
import sys
import threading
import time
import types

import pytest

from pathway_tpu.analysis import core
from pathway_tpu.analysis import runtime as rt
from pathway_tpu.analysis.annotations import guarded_by
from pathway_tpu.analysis.core import Finding, analyze_source
from pathway_tpu.analysis.flag_hygiene import check_dead_flags
from pathway_tpu.analysis.kill_switch import (
    check_kill_switches,
    check_pinning_refs,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NS = types.SimpleNamespace


def _rules(findings):
    return sorted({f.rule for f in findings})


# ------------------------------------------------------------------ GL101


def test_gl101_host_effect_flagged():
    src = """
import jax
import time

@jax.jit
def f(x):
    t = time.perf_counter()
    print(x)
    return x + t
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL101"]
    msgs = [f.message for f in found]
    assert any("time.perf_counter" in m for m in msgs)
    assert any("print" in m for m in msgs)


def test_gl101_reaches_through_call_graph():
    """The helper is not decorated; it is reachable from the jit root."""
    src = """
import jax

def helper(x):
    print(x)
    return x

@jax.jit
def f(x):
    return helper(x)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL101"]
    assert found[0].symbol == "helper"


def test_gl101_clean_kernel():
    src = """
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    return jnp.sum(x * 2)
"""
    assert analyze_source(src) == []


def test_gl101_effect_outside_jit_is_fine():
    src = """
import time

def host_side():
    return time.perf_counter()
"""
    assert analyze_source(src) == []


# ------------------------------------------------------------------ GL102


def test_gl102_numpy_on_traced_param():
    src = """
import jax
import numpy as np

@jax.jit
def f(x):
    return np.sum(x)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL102"]
    assert "np.sum(x)" in found[0].message


def test_gl102_static_argnames_exempt():
    src = """
from functools import partial
import jax
import numpy as np

@partial(jax.jit, static_argnames=("shape",))
def f(x, shape):
    pad = np.zeros(shape)
    return x + pad.shape[0]
"""
    assert analyze_source(src) == []


# ------------------------------------------------------------------ GL103


def test_gl103_mutated_mutable_capture():
    src = """
import jax

_CACHE = {}

def warm(k, v):
    _CACHE[k] = v

@jax.jit
def f(x):
    return x + len(_CACHE)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL103"]
    assert "_CACHE" in found[0].message


def test_gl103_never_mutated_global_is_constant():
    src = """
import jax

_TABLE = [1, 2, 3]

@jax.jit
def f(x):
    return x + len(_TABLE)
"""
    assert analyze_source(src) == []


def test_gl101_shard_map_boundary_is_a_root():
    """A shard_map-mapped function traces under the SPMD per-shard view;
    host effects inside it are the same bug as inside jax.jit."""
    src = """
import jax

def mapped(x):
    print(x)
    return x

def outer(mesh, x, specs):
    return jax.shard_map(
        mapped, mesh=mesh, in_specs=specs, out_specs=specs
    )(x)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL101"]
    assert found[0].symbol == "mapped"


def test_gl101_shard_map_from_import_alias_is_a_root():
    """A from-import alias of ``jax.shard_map`` (how the parallel layer
    spells it) is the same trace boundary."""
    src = """
from jax import shard_map as smap

def mapped(x):
    print(x)
    return x

def outer(mesh, x, specs):
    return smap(
        mapped, mesh=mesh, in_specs=specs, out_specs=specs
    )(x)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL101"]
    assert found[0].symbol == "mapped"


def test_gl101_block_spec_index_map_is_a_root():
    """A BlockSpec index map runs under Pallas tracing (grid
    resolution), so host effects inside it are GL101 — both the 2nd
    positional arg and the index_map= keyword forms root it."""
    src = """
import jax.experimental.pallas as pl

def imap(b, kt):
    print(b)
    return (b, kt)

def kmap(b, kt):
    import time
    time.sleep(0)
    return (b, 0)

def body(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def call(x):
    return pl.pallas_call(
        body,
        grid=(2, 2),
        in_specs=[pl.BlockSpec((8, 8), imap)],
        out_specs=pl.BlockSpec((8, 8), index_map=kmap),
        out_shape=x,
    )(x)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL101"]
    assert {f.symbol for f in found} == {"imap", "kmap"}


def test_gl101_clean_block_spec_index_map():
    """A pure index map (the repo's named-top-level convention in
    models/flash_attention.py) stays clean."""
    src = """
import jax.experimental.pallas as pl

def imap(b, kt):
    return (b, 0, kt, 0)

def body(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def call(x):
    return pl.pallas_call(
        body,
        grid=(2, 2),
        in_specs=[pl.BlockSpec((8, 8), imap)],
        out_specs=pl.BlockSpec((8, 8), imap),
        out_shape=x,
    )(x)
"""
    assert analyze_source(src) == []


def test_gl101_clean_shard_map_body():
    src = """
import jax
import jax.numpy as jnp

def mapped(x):
    return jnp.sum(x) + jax.lax.axis_index("tp")

def outer(mesh, x, specs):
    return jax.shard_map(
        mapped, mesh=mesh, in_specs=specs, out_specs=specs
    )(x)
"""
    assert analyze_source(src) == []


# ------------------------------------------------------------------ GL201


def test_gl201_literal_env_read():
    src = """
import os

def mode():
    a = os.environ.get("PATHWAY_TPU_MODE", "0")
    b = os.getenv("PATHWAY_TPU_OTHER")
    c = os.environ["PATHWAY_LICENSE_KEY"]
    return a, b, c
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL201"]
    assert len(found) == 3


def test_gl201_config_module_exempt():
    src = """
import os

def read():
    return os.environ.get("PATHWAY_TPU_MODE")
"""
    assert analyze_source(src, path="pathway_tpu/internals/config.py") == []


def test_gl201_pragma_suppresses():
    src = """
import os

def mode():
    return os.environ.get("PATHWAY_TPU_MODE")  # graft-lint: allow[GL201] legacy shim
"""
    assert analyze_source(src) == []


def test_gl201_pathway_config_read_is_fine():
    src = """
from pathway_tpu.internals.config import pathway_config

def mode():
    return pathway_config.metrics
"""
    assert analyze_source(src) == []


# ------------------------------------------------------------------ GL202


def test_gl202_dynamic_and_bare_environ():
    src = """
import os

def snap():
    return dict(os.environ)

def read(name):
    return os.getenv(name)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL202"]
    assert len(found) == 2


def test_gl202_choke_points_are_fine():
    src = """
from pathway_tpu.internals.config import env_interpolate, environ_snapshot

def snap():
    return environ_snapshot(EXTRA="1")

def read(name):
    return env_interpolate(name)
"""
    assert analyze_source(src) == []


def test_gl202_aliased_import_caught():
    src = """
from os import environ as E

def snap():
    return "HOME" in E
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL202"]


# ------------------------------------------------------------------ GL203


def test_gl203_dead_flag_detected():
    flags = [
        NS(env="PATHWAY_TPU_LIVE_ATTR", attr="live_knob"),
        NS(env="PATHWAY_TPU_LIVE_ENV", attr="other_knob"),
        NS(env="PATHWAY_TPU_DEAD", attr="dead_knob"),
    ]
    texts = [
        ("pathway_tpu/x.py", "if pathway_config.live_knob:\n    pass\n"),
        ("tests/test_y.py", 'monkeypatch.setenv("PATHWAY_TPU_LIVE_ENV", "0")\n'),
    ]
    assert check_dead_flags(flags, texts) == [("PATHWAY_TPU_DEAD", "dead_knob")]


def test_gl203_attr_match_is_word_bounded():
    """`.dead_knob_extended` must not keep `dead_knob` alive."""
    flags = [NS(env="PATHWAY_TPU_DEAD", attr="dead_knob")]
    texts = [("pathway_tpu/x.py", "cfg.dead_knob_extended = 1\n")]
    assert check_dead_flags(flags, texts) == [("PATHWAY_TPU_DEAD", "dead_knob")]


# ------------------------------------------------------------------ GL204


def _tflag(env="PATHWAY_TPU_T", default=4, **spec):
    from pathway_tpu.internals.config import Flag, Tunable

    return Flag(
        env=env, attr="t", kind="int" if isinstance(default, int) else
        "float", default=default, doc="x", group="pipeline",
        tunable=Tunable(**spec),
    )


def test_gl204_healthy_specs_pass():
    from pathway_tpu.analysis.flag_hygiene import check_tunable_bounds

    flags = [
        _tflag(kind="int", lo=1, hi=8, log=True),
        _tflag(kind="int", lo=1, hi=8, step=1),
        _tflag(env="PATHWAY_TPU_C", kind="choice", choices=("4", "8")),
        NS(env="PATHWAY_TPU_PLAIN", tunable=None),  # untunable = exempt
    ]
    assert check_tunable_bounds(flags) == []


@pytest.mark.parametrize("spec,needle", [
    (dict(kind="int", hi=8), "lo and hi"),               # missing bound
    (dict(kind="int", lo=1, hi=float("inf")), "finite"),  # open-ended
    (dict(kind="int", lo=8, hi=1), "inverted"),           # lo >= hi
    (dict(kind="int", lo=1, hi=8, step=0), "step"),       # walks nowhere
    (dict(kind="float", lo=0.0, hi=8.0, log=True), "lo > 0"),
    (dict(kind="choice", choices=("4",)), ">= 2 choices"),
    (dict(kind="weird", lo=1, hi=8), "unknown tunable kind"),
])
def test_gl204_malformed_specs_flagged(spec, needle):
    from pathway_tpu.analysis.flag_hygiene import check_tunable_bounds

    bad = check_tunable_bounds([_tflag(**spec)])
    assert len(bad) == 1 and bad[0][0] == "PATHWAY_TPU_T"
    assert needle in bad[0][1], bad


def test_gl204_default_outside_space_flagged():
    from pathway_tpu.analysis.flag_hygiene import check_tunable_bounds

    bad = check_tunable_bounds(
        [_tflag(default=32, kind="int", lo=1, hi=8, step=1)]
    )
    assert bad and "outside" in bad[0][1]
    bad = check_tunable_bounds(
        [_tflag(default=3, kind="choice", choices=("4", "8"))]
    )
    assert bad and "not one of the choices" in bad[0][1]


def test_gl204_choice_default_compared_in_parsed_units():
    """A float flag defaulting to 0.0 with choices ("0", "16") is fine:
    membership is judged through the flag's parser, not raw strings."""
    from pathway_tpu.analysis.flag_hygiene import check_tunable_bounds

    flags = [_tflag(default=0.0, kind="choice", choices=("0", "16"))]
    assert check_tunable_bounds(flags) == []


def test_gl204_rule_registered():
    from pathway_tpu.analysis.core import RULES

    assert RULES["GL204"].name == "tunable-bounds"


# ------------------------------------------------------------------ GL301


def test_gl301_pinning_contract(tmp_path):
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir()
    (tests_dir / "test_pin.py").write_text(
        'def test_x(monkeypatch):\n'
        '    monkeypatch.setenv("PATHWAY_TPU_GOOD", "0")\n'
    )
    flags = [
        NS(env="PATHWAY_TPU_GOOD", kill_switch=True,
           pinned_by="tests/test_pin.py"),
        NS(env="PATHWAY_TPU_NOPIN", kill_switch=True, pinned_by=None),
        NS(env="PATHWAY_TPU_GONE", kill_switch=True,
           pinned_by="tests/test_gone.py"),
        NS(env="PATHWAY_TPU_STALE", kill_switch=True,
           pinned_by="tests/test_pin.py"),  # file exists, never references
        NS(env="PATHWAY_TPU_PLAIN", kill_switch=False, pinned_by=None),
    ]
    problems = dict(check_kill_switches(flags, str(tmp_path)))
    assert set(problems) == {
        "PATHWAY_TPU_NOPIN", "PATHWAY_TPU_GONE", "PATHWAY_TPU_STALE"
    }
    assert "does not exist" in problems["PATHWAY_TPU_GONE"]
    assert "never references" in problems["PATHWAY_TPU_STALE"]


def test_live_registry_kill_switches_all_pinned():
    from pathway_tpu.internals.config import FLAG_REGISTRY

    assert check_kill_switches(FLAG_REGISTRY, REPO_ROOT) == []
    # and the contract is actually exercised: the registry declares some
    assert sum(1 for f in FLAG_REGISTRY if f.kill_switch) >= 10


# ------------------------------------------------------------------ GL302


def test_gl302_prose_only_pin_rejected(tmp_path):
    """A pinning test that names the env var only in its docstring (or a
    comment) satisfies GL301's substring scan but pins nothing; the env
    var must appear in a CODE string literal — setenv arg, parametrize
    entry, env dict key all count."""
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir()
    (tests_dir / "test_code.py").write_text(
        'def test_x(monkeypatch):\n'
        '    monkeypatch.setenv("PATHWAY_TPU_CODE", "0")\n'
    )
    (tests_dir / "test_param.py").write_text(
        'import pytest\n'
        '@pytest.mark.parametrize("env", ["PATHWAY_TPU_PARAM"])\n'
        'def test_x(env):\n'
        '    pass\n'
    )
    (tests_dir / "test_prose.py").write_text(
        '"""Pins PATHWAY_TPU_PROSE byte-identical (it says here).\n'
        '"""\n'
        '# also mentions PATHWAY_TPU_PROSE in a comment\n'
        'def test_x():\n'
        '    """Inner docstring: PATHWAY_TPU_PROSE again."""\n'
        '    pass\n'
    )
    flags = [
        NS(env="PATHWAY_TPU_CODE", kill_switch=True,
           pinned_by="tests/test_code.py"),
        NS(env="PATHWAY_TPU_PARAM", kill_switch=True,
           pinned_by="tests/test_param.py"),
        NS(env="PATHWAY_TPU_PROSE", kill_switch=True,
           pinned_by="tests/test_prose.py"),
        # GL301's findings, not GL302's: missing file / missing reference
        NS(env="PATHWAY_TPU_GONE", kill_switch=True,
           pinned_by="tests/test_missing.py"),
        NS(env="PATHWAY_TPU_UNREF", kill_switch=True,
           pinned_by="tests/test_code.py"),
        NS(env="PATHWAY_TPU_NOPIN", kill_switch=True, pinned_by=None),
    ]
    problems = dict(check_pinning_refs(flags, str(tmp_path)))
    assert set(problems) == {"PATHWAY_TPU_PROSE"}
    assert "only in" in problems["PATHWAY_TPU_PROSE"]


def test_gl302_live_registry_pins_are_code():
    """Every declared kill switch's pinning test uses its env var in
    actual code today — keep it that way."""
    from pathway_tpu.internals.config import FLAG_REGISTRY

    assert check_pinning_refs(FLAG_REGISTRY, REPO_ROOT) == []


def test_gl302_rule_registered():
    assert "GL302" in core.RULES
    assert "prose" in core.RULES["GL302"].summary


# ------------------------------------------------------------------ GL401


def test_gl401_unguarded_class_field():
    src = """
import threading
from pathway_tpu.analysis.annotations import guarded_by

@guarded_by(items="_lock")
class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []

    def bad(self):
        self.items.append(1)

    def good(self):
        with self._lock:
            self.items.append(2)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL401"]
    assert len(found) == 1
    assert found[0].symbol == "Box.bad"


def test_gl401_assumes_held_exempt():
    src = """
import threading
from pathway_tpu.analysis.annotations import assumes_held, guarded_by

@guarded_by(items="_lock")
class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []

    @assumes_held("_lock")
    def _push(self, x):
        self.items.append(x)

    def push(self, x):
        with self._lock:
            self._push(x)
"""
    assert analyze_source(src) == []


def test_gl401_nested_closure_does_not_inherit_lock():
    """A callback defined under `with self._lock:` runs later, without
    the lock — its guarded access must still be flagged."""
    src = """
import threading
from pathway_tpu.analysis.annotations import guarded_by

@guarded_by(items="_lock")
class Box:
    def __init__(self):
        self._lock = threading.Lock()
        self.items = []

    def sched(self):
        with self._lock:
            def cb():
                self.items.append(1)
            return cb
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL401"]


def test_gl401_module_global():
    src = """
import threading

_GUARDED_BY = {"_ring": "_ring_lock"}

_ring_lock = threading.Lock()
_ring = []

def bad():
    return list(_ring)

def good():
    with _ring_lock:
        return list(_ring)
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL401"]
    assert len(found) == 1
    assert found[0].symbol == "bad"


# ------------------------------------------------------------------ GL402


def test_gl402_lock_never_assigned():
    src = """
from pathway_tpu.analysis.annotations import guarded_by

@guarded_by(items="_lock")
class Box:
    def __init__(self):
        self.items = []
"""
    found = analyze_source(src)
    assert "GL402" in _rules(found)


def test_gl402_module_lock_never_bound():
    src = """
_GUARDED_BY = {"_x": "_missing_lock"}

_x = []
"""
    found = analyze_source(src)
    assert _rules(found) == ["GL402"]


# ------------------------------------------------- fingerprints, baseline


def test_fingerprint_ignores_line_number():
    a = Finding("GL201", "pathway_tpu/x.py", 10, "msg", "sym")
    b = Finding("GL201", "pathway_tpu/x.py", 99, "msg", "sym")
    c = Finding("GL202", "pathway_tpu/x.py", 10, "msg", "sym")
    assert a.fingerprint == b.fingerprint
    assert a.fingerprint != c.fingerprint


def test_baseline_roundtrip(tmp_path):
    f1 = Finding("GL201", "pathway_tpu/x.py", 10, "msg one", "a")
    f2 = Finding("GL203", "pathway_tpu/internals/config.py", 3, "msg two", "b")
    path = str(tmp_path / "baseline.json")
    core.save_baseline([f1], path)
    baseline = core.load_baseline(path)
    new, old = core.split_baselined([f1, f2], baseline)
    assert [f.rule for f in new] == ["GL203"]
    assert [f.rule for f in old] == ["GL201"]
    # saved entries drop the churning line number
    entries = json.load(open(path, encoding="utf-8"))
    assert entries and "line" not in entries[0]


# --------------------------------------------------------- tier-1 gates


def test_repo_analyzes_clean():
    """THE gate: the package passes its own analyzer against the
    checked-in baseline. New findings fail tier-1 here."""
    findings = core.check(REPO_ROOT)
    baseline = core.load_baseline()
    new, _old = core.split_baselined(findings, baseline)
    assert not new, "new graft-lint findings:\n" + "\n".join(
        f.render() for f in new
    )


def test_readme_rules_table_is_generated_output():
    path = os.path.join(REPO_ROOT, "README.md")
    text = open(path, encoding="utf-8").read()
    m = re.search(
        r"<!-- analysis:rules -->\n(.*?)<!-- /analysis:rules -->", text, re.S
    )
    assert m, "README missing <!-- analysis:rules --> block"
    assert m.group(1).strip() == core.render_rules_table().strip()


def test_cli_json_output():
    proc = subprocess.run(
        [sys.executable, "-m", "pathway_tpu.analysis", "check",
         "--format", "json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    out = json.loads(proc.stdout)
    assert set(out) == {"findings", "baselined", "ok"}
    assert out["ok"] is (proc.returncode == 0)
    for e in out["findings"]:
        assert {"rule", "path", "line", "fingerprint"} <= set(e)


# ------------------------------------------------------- runtime harness


@pytest.fixture
def sanitizer(monkeypatch):
    monkeypatch.setenv("PATHWAY_TPU_LOCK_SANITIZER", "1")
    rt.reset()
    yield rt
    rt.disable()
    rt.reset()


def test_make_lock_plain_when_off(monkeypatch):
    """Compiled out: flag off returns stdlib locks, no wrapper."""
    monkeypatch.setenv("PATHWAY_TPU_LOCK_SANITIZER", "0")
    assert isinstance(rt.make_lock("t.off"), type(threading.Lock()))
    assert isinstance(rt.make_lock("t.off", rlock=True),
                      type(threading.RLock()))


def test_seeded_order_inversion_detected(sanitizer):
    a = sanitizer.make_lock("t_inv.A")
    b = sanitizer.make_lock("t_inv.B")
    assert isinstance(a, rt.SanitizedLock)
    with a:
        with b:
            pass
    with b:
        with a:
            pass
    inv = sanitizer.reports("order-inversion")
    assert inv, "seeded A->B then B->A inversion not detected"
    assert inv[0]["first"] == "t_inv.B" and inv[0]["second"] == "t_inv.A"


def test_consistent_order_is_clean(sanitizer):
    a = sanitizer.make_lock("t_ord.A")
    b = sanitizer.make_lock("t_ord.B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert sanitizer.reports() == []


def test_reentrant_rlock_no_false_inversion(sanitizer):
    r = sanitizer.make_lock("t_re.R", rlock=True)
    b = sanitizer.make_lock("t_re.B")
    with r:
        with r:  # re-entrant: no self-edge
            with b:
                pass
    with r:
        with b:
            pass
    assert sanitizer.reports() == []


def test_unguarded_write_detected(sanitizer):
    @guarded_by(value="_lock")
    class _Guinea:
        def __init__(self):
            self._lock = sanitizer.make_lock("t_guinea.lock")
            self.value = 0

        def good(self):
            with self._lock:
                self.value = 1

        def bad(self):
            self.value = 2

    g = _Guinea()  # construction precedes enable(): no reports
    sanitizer.enable()
    g.good()
    assert sanitizer.reports("unguarded-write") == []
    g.bad()
    reps = sanitizer.reports("unguarded-write")
    assert reps and reps[0]["field"] == "value"
    assert reps[0]["lock"] == "t_guinea.lock"


def test_condition_wait_release_reacquire_traced(sanitizer):
    """`threading.Condition` over a sanitized lock: wait() releases and
    reacquires through the `_release_save`/`_acquire_restore` protocol
    without tripping the order graph or deadlocking."""
    cond = threading.Condition(sanitizer.make_lock("t_cond.lock"))
    hits = []

    def waiter():
        with cond:
            while not hits:
                cond.wait(timeout=5)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cond:
        hits.append(1)
        cond.notify_all()
    t.join(5)
    assert not t.is_alive()
    assert sanitizer.reports() == []


def test_threaded_registry_hammer_clean(sanitizer, monkeypatch):
    """8 writers on one MetricsRegistry under the sanitizer: counts
    exact, zero sanitizer reports — the shipped locking really is
    disciplined under concurrency, not just lexically."""
    monkeypatch.setenv("PATHWAY_TPU_METRICS", "1")
    from pathway_tpu.engine.probes import MetricsRegistry

    reg = MetricsRegistry()
    assert isinstance(reg._lock, rt.SanitizedLock)
    N = 200

    def writer(i):
        for _ in range(N):
            reg.counter_add("hammer_total", 1.0, worker=str(i))
            reg.observe("hammer_seconds", 0.001, worker=str(i))

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    total = sum(reg.labelled("hammer_total", "worker").values())
    assert total == 8 * N
    assert sanitizer.reports() == []


def test_query_server_under_sanitizer_clean(sanitizer):
    """Concurrent submits through the QueryServer's Condition + stats
    lock: results intact, no inversions, no unguarded writes."""
    from pathway_tpu.ops.query_server import QueryServer

    class _FakePipe:
        reranker = None

        def retrieve(self, texts, k):
            return [f"{t}:{k}" for t in texts]

    sanitizer.enable()
    try:
        with QueryServer(_FakePipe(), tick_ms=1.0, max_batch=8,
                         queue_bound=16) as srv:
            results = {}

            def client(i):
                req = srv.submit(f"q{i}", 3)
                results[i] = req.wait(30)

            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(12)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30)
        assert results == {i: f"q{i}:3" for i in range(12)}
    finally:
        sanitizer.disable()
    assert sanitizer.reports() == []
