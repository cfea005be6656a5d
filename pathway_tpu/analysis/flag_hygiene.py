"""GL2xx — flag-hygiene pass.

The repo's contract since PR 2: every environment knob is declared once
in ``internals/config.py``'s ``FLAG_REGISTRY`` and read through
``pathway_config``. This pass makes the contract total:

* **GL201** — a *literal* ``PATHWAY*`` env read anywhere outside
  ``internals/config.py`` (``os.environ["PATHWAY_TPU_X"]``,
  ``os.environ.get(...)``, ``os.getenv(...)``, including
  ``from os import environ`` aliases) is an error: the knob bypasses
  registration, typing, clamping, and the README tables.
* **GL202** — any *other* ``os.environ`` / ``os.getenv`` use outside
  ``internals/config.py`` (dynamic keys, ``in os.environ`` membership,
  whole-environment copies for subprocesses). These go through the
  audited choke points ``config.env_interpolate`` /
  ``config.environ_snapshot`` instead, so "who reads the environment"
  stays a one-file question.
* **GL203** — a ``FLAG_REGISTRY`` entry nobody reads: its ``attr`` is
  never accessed in the package (outside config.py) and its env name
  never appears in package/tests sources. Dead flags are lies in
  the docs; delete them or wire them up.
* **GL204** — a flag carrying a ``tunable`` search spec whose space is
  broken: missing/non-finite bounds, an inverted range, a non-positive
  step, an empty or single-rung candidate ladder, or a default outside
  the declared space. The autotuner trusts these specs; a malformed one
  would search garbage (or nothing).

GL203/GL204 are registry-wide, so they only fire on full-package runs
(they need ``internals/config.py`` in the scanned set); unit tests
exercise :func:`check_dead_flags` / :func:`check_tunable_bounds`
directly with synthetic registries.
"""

from __future__ import annotations

import ast
import os
import re

from pathway_tpu.analysis.core import Finding, ModuleSource, PackageCtx

CONFIG_PATH = "pathway_tpu/internals/config.py"


def _env_aliases(src: ModuleSource) -> tuple[set[str], set[str], set[str]]:
    """(os-module aliases, `environ` aliases, `getenv` aliases)."""
    os_names: set[str] = set()
    environ_names: set[str] = set()
    getenv_names: set[str] = set()
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "os":
                    os_names.add(a.asname or "os")
        elif isinstance(node, ast.ImportFrom) and node.module == "os":
            for a in node.names:
                if a.name == "environ":
                    environ_names.add(a.asname or "environ")
                elif a.name == "getenv":
                    getenv_names.add(a.asname or "getenv")
    return os_names, environ_names, getenv_names


def _literal_pathway_key(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        if node.value.startswith("PATHWAY"):
            return node.value
    return None


def run(ctx: PackageCtx) -> list[Finding]:
    findings: list[Finding] = []
    for src in ctx.modules:
        if src.path == CONFIG_PATH:
            continue
        _check_module(findings, src)

    config = ctx.module(CONFIG_PATH)
    if config is not None and ctx.registry_checks:
        findings.extend(_dead_flags_on_repo(ctx, config))
        findings.extend(_tunable_bounds_on_repo(config))
    return findings


def _check_module(out: list[Finding], src: ModuleSource) -> None:
    os_names, environ_names, getenv_names = _env_aliases(src)
    if not (os_names or environ_names or getenv_names):
        return

    def is_environ(node: ast.AST) -> bool:
        if (
            isinstance(node, ast.Attribute)
            and node.attr == "environ"
            and isinstance(node.value, ast.Name)
            and node.value.id in os_names
        ):
            return True
        return isinstance(node, ast.Name) and node.id in environ_names

    def is_getenv(node: ast.AST) -> bool:
        if (
            isinstance(node, ast.Attribute)
            and node.attr == "getenv"
            and isinstance(node.value, ast.Name)
            and node.value.id in os_names
        ):
            return True
        return isinstance(node, ast.Name) and node.id in getenv_names

    flagged: set[int] = set()  # id() of environ nodes already reported

    def emit(rule: str, node: ast.AST, detail: str) -> None:
        src.emit(out, rule, node, detail)

    for node in ast.walk(src.tree):
        # os.environ[KEY] / environ.get(KEY) / os.getenv(KEY)
        if isinstance(node, ast.Subscript) and is_environ(node.value):
            flagged.add(id(node.value))
            key = _literal_pathway_key(node.slice)
            if key:
                emit("GL201", node,
                     f"literal env read `{key}` outside internals/config.py "
                     "— declare it in FLAG_REGISTRY and read "
                     "`pathway_config`")
            else:
                emit("GL202", node,
                     "dynamic `os.environ[...]` outside internals/config.py "
                     "— use `config.env_interpolate`")
        elif isinstance(node, ast.Call):
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in ("get", "setdefault", "pop")
                and is_environ(f.value)
            ):
                flagged.add(id(f.value))
                key = node.args and _literal_pathway_key(node.args[0]) or None
                if key:
                    emit("GL201", node,
                         f"literal env read `{key}` outside "
                         "internals/config.py — declare it in FLAG_REGISTRY "
                         "and read `pathway_config`")
                else:
                    emit("GL202", node,
                         f"`os.environ.{f.attr}(...)` outside "
                         "internals/config.py — use `config.env_interpolate`")
            elif is_getenv(f):
                key = node.args and _literal_pathway_key(node.args[0]) or None
                if key:
                    emit("GL201", node,
                         f"literal env read `{key}` outside "
                         "internals/config.py — declare it in FLAG_REGISTRY "
                         "and read `pathway_config`")
                else:
                    emit("GL202", node,
                         "`os.getenv(...)` outside internals/config.py — "
                         "use `config.env_interpolate`")

    # bare os.environ touches not covered above (copies, membership,
    # iteration, passing the mapping around)
    for node in ast.walk(src.tree):
        if is_environ(node) and id(node) not in flagged:
            # skip the inner `os.environ` of already-flagged parents:
            # only Attribute/Name nodes reach here
            emit("GL202", node,
                 "`os.environ` used outside internals/config.py — use "
                 "`config.environ_snapshot` / `config.env_interpolate`")


# --------------------------------------------------------------------- #
# GL203 dead flags


def check_dead_flags(flags, texts) -> list[tuple[str, str | None]]:
    """Registry entries with no reader. ``flags`` is an iterable with
    ``.env`` / ``.attr``; ``texts`` is ``[(path, source_text), ...]`` of
    everything that may legitimately read a flag (package minus
    config.py, tests/). Returns ``[(env, attr), ...]`` dead."""
    dead: list[tuple[str, str | None]] = []
    for flag in flags:
        attr_re = re.compile(r"\." + re.escape(flag.attr) + r"\b")
        live = False
        for _path, text in texts:
            if flag.env in text:
                live = True
                break
            if attr_re.search(text):
                live = True
                break
        if not live:
            dead.append((flag.env, flag.attr))
    return dead


def _registry_line(config: ModuleSource, env: str) -> int:
    needle = f'"{env}"'
    for i, line in enumerate(config.lines, start=1):
        if needle in line:
            return i
    return 1


def _dead_flags_on_repo(
    ctx: PackageCtx, config: ModuleSource
) -> list[Finding]:
    from pathway_tpu.internals.config import FLAG_REGISTRY

    texts: list[tuple[str, str]] = [
        (m.path, m.text) for m in ctx.modules if m.path != CONFIG_PATH
    ]
    tests_dir = os.path.join(ctx.repo_root, "tests")
    if os.path.isdir(tests_dir):
        for fn in sorted(os.listdir(tests_dir)):
            if fn.endswith(".py"):
                with open(os.path.join(tests_dir, fn), encoding="utf-8") as f:
                    texts.append((f"tests/{fn}", f.read()))

    findings: list[Finding] = []
    for env, attr in check_dead_flags(FLAG_REGISTRY, texts):
        line = _registry_line(config, env)
        node = ast.Constant(value=env)
        node.lineno = line
        config.emit(
            findings, "GL203", node,
            f"flag `{env}` (attr `{attr}`) is never read by package or "
            "tests — delete it or wire it up",
            env,
        )
    return findings


# --------------------------------------------------------------------- #
# GL204 tunable bounds


def check_tunable_bounds(flags) -> list[tuple[str, str]]:
    """Malformed ``Tunable`` search specs. ``flags`` is an iterable with
    ``.env`` / ``.tunable`` (``None`` = not tunable) where a spec has
    ``.kind`` / ``.lo`` / ``.hi`` / ``.step`` / ``.log`` / ``.choices``
    / ``.candidates()``, and the flag parses raw values via
    ``.parse_raw`` and renders its default via ``.render_default``.
    Returns ``[(env, problem), ...]``."""
    import math

    bad: list[tuple[str, str]] = []
    for flag in flags:
        spec = getattr(flag, "tunable", None)
        if spec is None:
            continue
        env = flag.env

        def problem(msg: str, env=env) -> None:
            bad.append((env, msg))

        if spec.kind == "choice":
            if len(spec.choices) < 2:
                problem("choice spec needs >= 2 choices")
                continue
        elif spec.kind in ("int", "float"):
            if spec.lo is None or spec.hi is None:
                problem(f"{spec.kind} spec must declare lo and hi")
                continue
            lo, hi = float(spec.lo), float(spec.hi)
            if not (math.isfinite(lo) and math.isfinite(hi)):
                problem("bounds must be finite")
                continue
            if lo >= hi:
                problem(f"inverted/empty range [{lo}, {hi}]")
                continue
            if spec.log:
                if lo <= 0:
                    problem("log ladder needs lo > 0")
                    continue
            elif spec.step is not None and float(spec.step) <= 0:
                problem(f"non-positive step {spec.step}")
                continue
        else:
            problem(f"unknown tunable kind {spec.kind!r}")
            continue

        try:
            cands = spec.candidates()
        except Exception as exc:  # noqa: BLE001 - report, don't crash
            problem(f"candidates() raised {type(exc).__name__}: {exc}")
            continue
        if len(cands) < 2:
            problem(f"degenerate candidate ladder ({len(cands)} rung)")
            continue
        # every rung must round-trip through the flag's own parser
        try:
            parsed = [flag.parse_raw(c) for c in cands]
        except Exception as exc:  # noqa: BLE001 - report, don't crash
            problem(f"candidate fails flag parser: {exc}")
            continue
        # the default must live inside the declared space (compare in
        # parsed units: choice "0" on a float flag means 0.0)
        default = flag.parse_raw(flag.render_default())
        if spec.kind == "choice":
            if default not in parsed:
                problem(
                    f"default {default!r} is not one of the choices"
                )
        else:
            lo, hi = float(spec.lo), float(spec.hi)
            try:
                dv = float(default)
            except (TypeError, ValueError):
                problem(
                    f"non-numeric default {default!r} on a {spec.kind} range"
                )
                continue
            if not (lo <= dv <= hi):
                problem(f"default {dv} outside [{lo}, {hi}]")
    return bad


def _tunable_bounds_on_repo(config: ModuleSource) -> list[Finding]:
    from pathway_tpu.internals.config import FLAG_REGISTRY

    findings: list[Finding] = []
    for env, msg in check_tunable_bounds(FLAG_REGISTRY):
        node = ast.Constant(value=env)
        node.lineno = _registry_line(config, env)
        config.emit(
            findings, "GL204", node,
            f"flag `{env}` has a malformed tunable spec: {msg}",
            env,
        )
    return findings
