"""GL1xx — jit-purity pass.

Finds every function reachable from a ``jax.jit`` boundary — decorator
forms (``@jax.jit``, ``@jit``, ``@partial(jax.jit, ...)``) and call
forms (``jax.jit(f)``, ``jax.jit(partial(mod.f, ...))``) — plus
``pl.pallas_call(kernel, ...)`` boundaries (a Pallas kernel body is
traced exactly like a jitted function, so host effects inside it are
the same bug), ``pl.BlockSpec(shape, index_map)`` index-map functions
(an index map runs at trace/grid-resolution time inside the Pallas
machinery — the flash/paged kernels name theirs as top-level functions
precisely so this pass can see them) and ``shard_map`` boundaries
(the serving mesh's paged-attention seam: the mapped function traces
under the SPMD per-shard view) —
then walks the call graph across modules
(import-alias resolution, absolute and relative) and flags, inside the
reachable set:

* **GL101** host-side effects: ``print``, ``time.*``, ``os.environ`` /
  ``os.getenv``, ``pathway_config.*`` reads, and calls into the
  observability modules (``engine.probes`` / ``engine.tracing`` /
  ``analysis.runtime``). All of these run at *trace* time, not run
  time: the value is frozen into the jaxpr, or the side effect fires
  once per retrace instead of once per call.
* **GL102** ``np.*`` calls on a traced parameter of the jit entry
  function itself (parameters named in ``static_argnames`` are
  concrete and exempt). NumPy on a tracer either fails or forces a
  host round-trip.
* **GL103** closure capture of a module-level mutable that the module
  also mutates — the traced snapshot silently diverges from the live
  object.

Reachability is intraprocedural-per-function / interprocedural-by-name:
top-level functions only, resolved through ``import x as y`` and
``from x import f as g``. Method calls and dynamic dispatch are out of
scope — the repo's jitted kernels are top-level functions by
convention, which this pass now enforces de facto.
"""

from __future__ import annotations

import ast

from pathway_tpu.analysis.core import Finding, ModuleSource, PackageCtx

_TIME_FNS = {
    "time", "perf_counter", "perf_counter_ns", "monotonic", "sleep",
    "process_time", "time_ns", "monotonic_ns",
}
_NUMPY_MODULES = {"numpy"}
_EFFECT_MODULES = (
    "pathway_tpu.engine.probes",
    "pathway_tpu.engine.tracing",
    "pathway_tpu.analysis.runtime",
)
_MUTATOR_METHODS = {
    "append", "extend", "insert", "add", "update", "setdefault",
    "pop", "popitem", "remove", "discard", "clear", "appendleft",
}


def _module_name(path: str) -> str:
    # "pathway_tpu/ops/knn.py" -> "pathway_tpu.ops.knn";
    # ".../__init__.py" -> package name
    mod = path[:-3] if path.endswith(".py") else path
    parts = mod.split("/")
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class _Imports:
    """Per-module import resolution: local alias -> what it names."""

    def __init__(self, src: ModuleSource):
        self.mod_alias: dict[str, str] = {}  # name -> imported module
        self.from_names: dict[str, tuple[str, str]] = {}  # name -> (mod, orig)
        pkg_parts = _module_name(src.path).split(".")
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.mod_alias[a.asname or a.name.split(".")[0]] = a.name
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    # relative: drop the module's own name + (level-1) more
                    anchor = pkg_parts[: len(pkg_parts) - node.level]
                    base = ".".join(anchor + ([base] if base else []))
                for a in node.names:
                    if a.name == "*":
                        continue
                    local = a.asname or a.name
                    self.from_names[local] = (base, a.name)
                    # `from pkg import submodule` also binds a module
                    self.mod_alias.setdefault(local, f"{base}.{a.name}")

    def module_of(self, name: str) -> str | None:
        if name in self.from_names:
            return self.from_names[name][0]
        return self.mod_alias.get(name)


def _dotted(node: ast.AST) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jax_jit(node: ast.AST, imps: _Imports) -> bool:
    d = _dotted(node)
    if d is None:
        return False
    head, _, tail = d.partition(".")
    if tail == "jit" and imps.mod_alias.get(head) == "jax":
        return True
    if not tail and imps.from_names.get(head) == ("jax", "jit"):
        return True
    return False


def _is_pallas_call(node: ast.AST, imps: _Imports) -> bool:
    """``pl.pallas_call`` / ``pallas.pallas_call`` / a bare
    ``pallas_call`` from-import — the kernel argument is a trace
    boundary exactly like ``jax.jit``'s."""
    d = _dotted(node)
    if d is None:
        return False
    head, _, tail = d.partition(".")
    if tail == "pallas_call" and (
        imps.mod_alias.get(head) in ("jax.experimental.pallas",
                                     "jax.experimental.pallas.tpu")
    ):
        return True
    if not tail and imps.from_names.get(head, ("", ""))[1] == "pallas_call":
        return True
    return False


def _is_block_spec(node: ast.AST, imps: _Imports) -> bool:
    """``pl.BlockSpec`` / ``pallas.BlockSpec`` / a bare ``BlockSpec``
    from-import — its index-map argument runs under Pallas tracing, so
    it is a GL1xx root exactly like a kernel body."""
    d = _dotted(node)
    if d is None:
        return False
    head, _, tail = d.partition(".")
    if tail == "BlockSpec" and (
        imps.mod_alias.get(head) in ("jax.experimental.pallas",
                                     "jax.experimental.pallas.tpu")
    ):
        return True
    if not tail and imps.from_names.get(head, ("", ""))[1] == "BlockSpec":
        return True
    return False


def _block_spec_index_map(call: ast.Call) -> ast.AST | None:
    """The index-map operand of a BlockSpec call: 2nd positional arg or
    the ``index_map=`` keyword."""
    if len(call.args) >= 2:
        return call.args[1]
    for kw in call.keywords:
        if kw.arg == "index_map":
            return kw.value
    return None


def _is_shard_map(node: ast.AST, imps: _Imports) -> bool:
    """``jax.shard_map`` / ``jax.experimental.shard_map.shard_map``
    (or any from-import alias of either) — the mapped function is a
    trace boundary exactly like
    ``jax.jit``'s argument, and it additionally runs under the SPMD
    per-shard view, so the GL1xx purity rules apply to its body (the
    serving mesh routes paged attention through this seam)."""
    d = _dotted(node)
    if d is None:
        return False
    head, _, tail = d.partition(".")
    if tail == "shard_map" and imps.mod_alias.get(head) in (
        "jax", "jax.experimental.shard_map"
    ):
        return True
    if not tail:
        return imps.from_names.get(head, ("", ""))[1] == "shard_map"
    return False


def _is_partial(node: ast.AST, imps: _Imports) -> bool:
    d = _dotted(node)
    if d is None:
        return False
    if d == "partial" and imps.from_names.get("partial", ("", ""))[1] == "partial":
        return True
    head, _, tail = d.partition(".")
    return tail == "partial" and imps.mod_alias.get(head) == "functools"


def _static_argnames(call: ast.Call | None) -> set[str]:
    names: set[str] = set()
    if call is None:
        return names
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                    names.add(sub.value)
    return names


class _FuncRef:
    __slots__ = ("src", "node", "entry", "static")

    def __init__(self, src: ModuleSource, node: ast.FunctionDef):
        self.src = src
        self.node = node
        self.entry = False  # directly wrapped by jax.jit
        self.static: set[str] = set()  # static_argnames at the boundary


def _target_of_jit_arg(
    arg: ast.AST, imps: _Imports, defs: dict[str, ast.FunctionDef],
) -> tuple[str | None, str | None, ast.Call | None]:
    """Resolve `jax.jit(ARG)` to (module, func_name, partial_call)."""
    pcall = None
    if isinstance(arg, ast.Call) and _is_partial(arg.func, imps) and arg.args:
        pcall = arg
        arg = arg.args[0]
    if isinstance(arg, ast.Name):
        if arg.id in defs:
            return None, arg.id, pcall  # local
        if arg.id in imps.from_names:
            mod, orig = imps.from_names[arg.id]
            return mod, orig, pcall
        return None, None, pcall
    if isinstance(arg, ast.Attribute) and isinstance(arg.value, ast.Name):
        mod = imps.module_of(arg.value.id)
        if mod:
            return mod, arg.attr, pcall
    return None, None, pcall


def _call_edges(
    fn: ast.FunctionDef, imps: _Imports, defs: dict[str, ast.FunctionDef],
):
    """(module|None, name) pairs for every resolvable call in fn."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name):
            if f.id in defs:
                yield None, f.id
            elif f.id in imps.from_names:
                mod, orig = imps.from_names[f.id]
                yield mod, orig
        elif isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            mod = imps.module_of(f.value.id)
            if mod:
                yield mod, f.attr


def _module_mutated_names(src: ModuleSource) -> set[str]:
    """Module-level names the module itself mutates somewhere."""
    out: set[str] = set()
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Call):
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in _MUTATOR_METHODS
                and isinstance(f.value, ast.Name)
            ):
                out.add(f.value.id)
        elif isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target] if isinstance(node, ast.AugAssign)
                else node.targets
            )
            for t in targets:
                if isinstance(t, ast.Subscript) and isinstance(
                    t.value, ast.Name
                ):
                    out.add(t.value.id)
        elif isinstance(node, ast.Global):
            out.update(node.names)
    return out


def _module_mutable_globals(src: ModuleSource) -> dict[str, int]:
    """Top-level names bound to mutable literals -> lineno."""
    out: dict[str, int] = {}
    for node in src.tree.body:
        if isinstance(node, ast.Assign):
            val = node.value
            mutable = isinstance(
                val, (ast.List, ast.Dict, ast.Set, ast.ListComp, ast.DictComp,
                      ast.SetComp)
            ) or (
                isinstance(val, ast.Call)
                and isinstance(val.func, ast.Name)
                and val.func.id in ("list", "dict", "set", "bytearray",
                                    "defaultdict", "deque")
            )
            if mutable:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out[t.id] = node.lineno
    return out


def _local_names(fn: ast.FunctionDef) -> set[str]:
    """Names bound inside fn (params, assignments, comprehensions,...)."""
    bound: set[str] = set()
    a = fn.args
    for arg in (
        list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
        + ([a.vararg] if a.vararg else []) + ([a.kwarg] if a.kwarg else [])
    ):
        bound.add(arg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(
            node.ctx, (ast.Store, ast.Del)
        ):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node is not fn:
                bound.add(node.name)
        elif isinstance(node, ast.comprehension):
            for sub in ast.walk(node.target):
                if isinstance(sub, ast.Name):
                    bound.add(sub.id)
    return bound


def _collect_roots(
    by_name: dict[str, dict[str, _FuncRef]],
    imports: dict[str, _Imports],
    sources: dict[str, ModuleSource],
) -> list[_FuncRef]:
    roots: list[_FuncRef] = []
    for mod, src in sources.items():
        imps = imports[mod]
        defs = {n: r.node for n, r in by_name.get(mod, {}).items()}
        # decorator form
        for name, ref in by_name.get(mod, {}).items():
            for dec in ref.node.decorator_list:
                call = dec if isinstance(dec, ast.Call) else None
                target = call.func if call else dec
                if _is_jax_jit(target, imps):
                    ref.entry = True
                    ref.static |= _static_argnames(call)
                    roots.append(ref)
                elif call is not None and _is_partial(target, imps):
                    if call.args and _is_jax_jit(call.args[0], imps):
                        ref.entry = True
                        ref.static |= _static_argnames(call)
                        roots.append(ref)
        # call form: jax.jit(f) / jax.jit(partial(mod.f, ...)) /
        # pl.pallas_call(kernel, ...) / shard_map(f, mesh=..., ...) /
        # pl.BlockSpec(shape, index_map)
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            if _is_block_spec(node.func, imps):
                arg = _block_spec_index_map(node)
            elif (_is_jax_jit(node.func, imps)
                  or _is_pallas_call(node.func, imps)
                  or _is_shard_map(node.func, imps)):
                arg = node.args[0] if node.args else None
            else:
                continue
            if arg is None:
                continue
            tmod, fname, _pcall = _target_of_jit_arg(arg, imps, defs)
            owner = tmod or mod
            ref = by_name.get(owner, {}).get(fname or "")
            if ref is not None:
                ref.entry = True
                ref.static |= _static_argnames(node)
                roots.append(ref)
    return roots


def run(ctx: PackageCtx) -> list[Finding]:
    sources = {_module_name(m.path): m for m in ctx.modules}
    imports = {mod: _Imports(src) for mod, src in sources.items()}
    by_name: dict[str, dict[str, _FuncRef]] = {}
    for mod, src in sources.items():
        by_name[mod] = {
            node.name: _FuncRef(src, node)
            for node in src.tree.body
            if isinstance(node, ast.FunctionDef)
        }

    roots = _collect_roots(by_name, imports, sources)

    # BFS over the name-resolved call graph
    reachable: dict[tuple[str, str], _FuncRef] = {}
    frontier = [
        (_module_name(r.src.path), r.node.name, r) for r in roots
    ]
    while frontier:
        mod, name, ref = frontier.pop()
        key = (mod, name)
        if key in reachable:
            continue
        reachable[key] = ref
        imps = imports[mod]
        defs = {n: r.node for n, r in by_name.get(mod, {}).items()}
        for cmod, cname in _call_edges(ref.node, imps, defs):
            owner = cmod or mod
            cref = by_name.get(owner, {}).get(cname)
            if cref is not None and (owner, cname) not in reachable:
                frontier.append((owner, cname, cref))

    findings: list[Finding] = []
    mutated_cache: dict[str, set[str]] = {}
    mutables_cache: dict[str, dict[str, int]] = {}

    for (mod, name), ref in sorted(reachable.items()):
        src, fn, imps = ref.src, ref.node, imports[mod]
        _check_host_effects(findings, src, fn, imps, name)
        if ref.entry:
            _check_numpy_on_traced(findings, src, fn, imps, name, ref.static)
        if mod not in mutated_cache:
            mutated_cache[mod] = _module_mutated_names(src)
            mutables_cache[mod] = _module_mutable_globals(src)
        _check_mutable_capture(
            findings, src, fn, imps, name,
            mutables_cache[mod], mutated_cache[mod],
        )
    return findings


def _check_host_effects(
    out: list[Finding], src: ModuleSource, fn: ast.FunctionDef,
    imps: _Imports, fname: str,
) -> None:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id == "print":
                src.emit(out, "GL101", node,
                         "`print` inside jit-reachable function",
                         fname, fn.lineno)
            elif isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
                owner = imps.module_of(f.value.id)
                if owner == "time" and f.attr in _TIME_FNS:
                    src.emit(out, "GL101", node,
                             f"`time.{f.attr}` inside jit-reachable function",
                             fname, fn.lineno)
                elif owner == "os" and f.attr == "getenv":
                    src.emit(out, "GL101", node,
                             "`os.getenv` inside jit-reachable function",
                             fname, fn.lineno)
                elif owner and owner.startswith(_EFFECT_MODULES):
                    src.emit(
                        out, "GL101", node,
                        f"observability call `{f.value.id}.{f.attr}` inside "
                        "jit-reachable function",
                        fname, fn.lineno,
                    )
            if isinstance(f, ast.Name) and f.id in imps.from_names:
                owner, _orig = imps.from_names[f.id]
                if owner.startswith(_EFFECT_MODULES):
                    src.emit(
                        out, "GL101", node,
                        f"observability call `{f.id}` inside jit-reachable "
                        "function",
                        fname, fn.lineno,
                    )
        elif isinstance(node, ast.Attribute) and isinstance(
            node.value, ast.Name
        ):
            owner = imps.module_of(node.value.id)
            if owner == "os" and node.attr == "environ":
                src.emit(out, "GL101", node,
                         "`os.environ` inside jit-reachable function",
                         fname, fn.lineno)
            elif (
                node.value.id == "pathway_config"
                and imps.from_names.get("pathway_config", ("", ""))[0]
                == "pathway_tpu.internals.config"
            ):
                src.emit(
                    out, "GL101", node,
                    f"config read `pathway_config.{node.attr}` inside "
                    "jit-reachable function (frozen at trace time)",
                    fname, fn.lineno,
                )


def _check_numpy_on_traced(
    out: list[Finding], src: ModuleSource, fn: ast.FunctionDef,
    imps: _Imports, fname: str, static: set[str],
) -> None:
    a = fn.args
    params = {arg.arg for arg in list(a.posonlyargs) + list(a.args)
              + list(a.kwonlyargs)}
    traced = params - static
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)):
            continue
        if imps.module_of(f.value.id) not in _NUMPY_MODULES:
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name) and arg.id in traced:
                src.emit(
                    out, "GL102", node,
                    f"`{f.value.id}.{f.attr}({arg.id})` on traced parameter "
                    f"`{arg.id}` of jitted `{fname}`",
                    fname, fn.lineno,
                )
                break


def _check_mutable_capture(
    out: list[Finding], src: ModuleSource, fn: ast.FunctionDef,
    imps: _Imports, fname: str,
    mutables: dict[str, int], mutated: set[str],
) -> None:
    if not mutables:
        return
    bound = _local_names(fn)
    seen: set[str] = set()
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load)):
            continue
        nm = node.id
        if nm in bound or nm in seen or nm not in mutables:
            continue
        if nm not in mutated:
            continue  # never mutated -> effectively constant, fine
        seen.add(nm)
        src.emit(
            out, "GL103", node,
            f"jit-reachable `{fname}` captures module-level mutable `{nm}` "
            f"(mutated elsewhere in {src.path}) — value is frozen at trace "
            "time",
            fname, fn.lineno,
        )
