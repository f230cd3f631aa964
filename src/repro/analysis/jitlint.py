"""AST jit-safety / determinism linter (DESIGN.md §14).

Rule engine over the repo's python source. The load-bearing rule is
SYNC001 — host-synchronizing calls inside *jit regions*: the serving hot
loop's ≤1/K host-syncs-per-token contract (§7) dies silently if someone
adds an ``.item()`` three calls deep inside the jitted macro-step. The
linter builds a cross-module call graph, seeds it with every jit root it
can see (``jax.jit`` / ``lax.scan``-family bodies / ``pallas_call``
kernels / ``custom_vjp`` functions), propagates reachability, and flags
host syncs only inside reachable code.

Rules:

SYNC001  host sync reachable from a jit region: ``.item()``,
         ``.tolist()``, ``.block_until_ready()``, ``np.asarray``/
         ``np.array``/``np.copy``, ``jax.device_get``, and
         ``float()``/``int()``/``bool()`` applied directly to a function
         parameter (the static approximation of "cast on a tracer").
RNG001   unseeded randomness anywhere: ``np.random.default_rng()`` with
         no seed, legacy global-state ``np.random.<draw>()``, stdlib
         ``random.<draw>()``. Generalizes the old tests-only conftest
         guard to src/ and benchmarks/.
CLK001   wall-clock read (``time.time``/``perf_counter``/``monotonic``,
         ``datetime.now``) inside the serving package anywhere but the
         injectable-clock surface — a *default parameter value* is the
         surface (``clock=time.perf_counter``); a call in a body bypasses
         the injection and breaks the §12 FakeClock restore drills.
TAG001   two fold_in substream-tag constants (``*TAG*`` int assignments,
         e.g. ``SPEC_TAG_DRAFT``) in the same package share a value —
         the §13 substreams would collide and stop being independent.

Static analysis is approximate by design: name resolution follows
module-level ``import``/``from import`` bindings across the scanned
set; method calls and dynamic dispatch are not followed. False positives
go in the committed suppressions baseline with a reason.
"""
from __future__ import annotations

import ast
import dataclasses
import os

from repro.analysis.findings import Finding, relpath

# Call targets that make a traced function a *root* whose callee runs
# under jit/scan/pallas: (dotted-suffix, positional index of the fn arg).
_ROOT_CALLS = {
    "jax.jit": 0, "jit": 0,
    "jax.lax.scan": 0, "lax.scan": 0,
    "jax.lax.while_loop": 0, "lax.while_loop": 0,   # cond fn
    "jax.lax.fori_loop": 2, "lax.fori_loop": 2,
    "jax.lax.map": 0, "lax.map": 0,
    "pl.pallas_call": 0, "pallas_call": 0,
    "jax.custom_vjp": 0, "custom_vjp": 0,
    "jax.custom_jvp": 0, "custom_jvp": 0,
    "jax.eval_shape": 0,
}
# while_loop's body is arg 1, cond arg 0; switch/cond take several fns.
_MULTI_FN_ROOTS = {
    "jax.lax.while_loop": (0, 1), "lax.while_loop": (0, 1),
    "jax.lax.cond": (1, 2), "lax.cond": (1, 2),
    "jax.lax.switch": None, "lax.switch": None,   # all args from 1 on
}

_HOST_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
_HOST_SYNC_CALLS = {
    "np.asarray", "np.array", "np.copy", "numpy.asarray", "numpy.array",
    "jax.device_get", "jax.block_until_ready",
}
_CAST_NAMES = {"float", "int", "bool"}

_NP_RANDOM_GLOBAL_DRAWS = {
    "rand", "randn", "randint", "random", "random_sample", "sample",
    "choice", "shuffle", "permutation", "uniform", "normal",
    "standard_normal", "beta", "binomial", "poisson", "seed",
}
_STDLIB_RANDOM_DRAWS = {
    "random", "randint", "randrange", "uniform", "choice", "choices",
    "shuffle", "sample", "gauss", "normalvariate", "seed", "betavariate",
}
_CLOCK_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic",
    "time.process_time", "time.time_ns", "time.perf_counter_ns",
    "time.monotonic_ns", "datetime.now", "datetime.utcnow",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}


@dataclasses.dataclass
class Options:
    """Scan configuration (defaults match the repo contract)."""

    # CLK001 applies under these repo-relative path prefixes only: the
    # serving stack must route every wall read through the injectable
    # clock; benchmarks legitimately measure wall time.
    clock_paths: tuple = ("src/repro/serving/",)
    # Paths skipped entirely (known-bad lint fixtures, caches).
    exclude_parts: tuple = ("__pycache__", "tests/fixtures/")


def _dotted(node: ast.AST) -> str | None:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _match_suffix(dotted: str | None, table) -> str | None:
    """Return the table key that equals ``dotted`` exactly."""
    if dotted is None:
        return None
    return dotted if dotted in table else None


@dataclasses.dataclass
class _FnInfo:
    node: ast.AST                 # FunctionDef / AsyncFunctionDef / Lambda
    module: "_ModuleInfo"
    name: str                     # "" for lambdas
    reachable: bool = False


@dataclasses.dataclass
class _ModuleInfo:
    path: str                     # repo-relative posix
    dotted: str                   # e.g. "repro.serving.engine"
    tree: ast.Module = None
    # module-level binding -> dotted module it refers to
    import_mods: dict = dataclasses.field(default_factory=dict)
    # local name -> (module dotted, original name)
    import_names: dict = dataclasses.field(default_factory=dict)
    # top-level function name -> _FnInfo
    functions: dict = dataclasses.field(default_factory=dict)


def _module_dotted(rel: str) -> str:
    """Map a repo-relative path to the dotted module name used in
    imports (``src/repro/x.py`` -> ``repro.x``; ``tests/x.py`` ->
    ``tests.x``)."""
    p = rel[:-3] if rel.endswith(".py") else rel
    if p.startswith("src/"):
        p = p[len("src/"):]
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


class _Graph:
    """Cross-module call graph with jit-reachability propagation."""

    def __init__(self, modules: list[_ModuleInfo]):
        self.modules = {m.dotted: m for m in modules}
        self.fn_of_node: dict[int, _FnInfo] = {}
        for m in modules:
            for fn in m.functions.values():
                self.fn_of_node[id(fn.node)] = fn

    def resolve(self, mod: _ModuleInfo, dotted: str) -> _FnInfo | None:
        """Resolve a call target 'f' or 'alias.f' to a scanned function."""
        parts = dotted.split(".")
        if len(parts) == 1:
            name = parts[0]
            if name in mod.functions:
                return mod.functions[name]
            if name in mod.import_names:
                src_mod, orig = mod.import_names[name]
                target = self.modules.get(src_mod)
                if target:
                    return target.functions.get(orig)
            return None
        if len(parts) == 2 and parts[0] in mod.import_mods:
            target = self.modules.get(mod.import_mods[parts[0]])
            if target:
                return target.functions.get(parts[1])
        return None


def _collect_module(path: str, rel: str, src: str | None = None):
    if src is None:
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
    tree = ast.parse(src, filename=rel)
    info = _ModuleInfo(path=rel, dotted=_module_dotted(rel), tree=tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                info.import_mods[a.asname or a.name.split(".")[0]] = a.name
        elif isinstance(node, ast.ImportFrom) and node.module:
            if node.level:
                continue                     # no relative imports in repo
            for a in node.names:
                bound = a.asname or a.name
                # `from pkg import mod` may bind a module; record both
                # interpretations — resolution tries functions first.
                info.import_mods.setdefault(bound,
                                            f"{node.module}.{a.name}")
                info.import_names[bound] = (node.module, a.name)
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            info.functions[node.name] = _FnInfo(node=node, module=info,
                                                name=node.name)
    return info


_SCALAR_ANNOTATIONS = {"bool", "int", "float", "str", "bytes", "None"}


def _is_scalar_annotation(ann: ast.AST | None) -> bool:
    """True when an annotation names only static python scalars (e.g.
    ``bool``, ``int | None``) — such a parameter is never a tracer, so
    casting it is not a host sync."""
    if ann is None:
        return False
    if isinstance(ann, ast.Name):
        return ann.id in _SCALAR_ANNOTATIONS
    if isinstance(ann, ast.Constant):
        return ann.value is None or isinstance(ann.value, str)
    if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
        return (_is_scalar_annotation(ann.left)
                and _is_scalar_annotation(ann.right))
    if (isinstance(ann, ast.Subscript)
            and _dotted(ann.value) in ("Optional", "typing.Optional")):
        return _is_scalar_annotation(ann.slice)
    return False


def _fn_args(node) -> set[str]:
    """Parameter names that could be tracers (scalar-annotated params
    are excluded — see :func:`_is_scalar_annotation`)."""
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.Lambda)):
        a = node.args
        args = a.posonlyargs + a.args + a.kwonlyargs
        names = [x.arg for x in args
                 if not _is_scalar_annotation(getattr(x, "annotation",
                                                      None))]
        if a.vararg:
            names.append(a.vararg.arg)
        if a.kwarg:
            names.append(a.kwarg.arg)
        return set(names)
    return set()


def _fn_targets(call: ast.Call) -> list[ast.AST]:
    """Function-valued arguments of a jit-root call."""
    dotted = _dotted(call.func)
    keys = []
    if dotted in _MULTI_FN_ROOTS:
        idxs = _MULTI_FN_ROOTS[dotted]
        if idxs is None:
            keys = list(range(1, len(call.args)))
        else:
            keys = [i for i in idxs if i < len(call.args)]
    elif dotted in _ROOT_CALLS:
        i = _ROOT_CALLS[dotted]
        if i < len(call.args):
            keys = [i]
    out = []
    for i in keys:
        arg = call.args[i]
        # functools.partial(f, ...) / partial(f, ...): unwrap to f.
        if (isinstance(arg, ast.Call)
                and _dotted(arg.func) in ("functools.partial", "partial")
                and arg.args):
            arg = arg.args[0]
        out.append(arg)
    return out


def _decorated_as_root(node) -> bool:
    for dec in getattr(node, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        dotted = _dotted(target)
        if dotted in ("jax.jit", "jit", "jax.custom_vjp", "custom_vjp",
                      "jax.custom_jvp", "custom_jvp"):
            return True
        # @functools.partial(jax.jit, ...) and friends.
        if (isinstance(dec, ast.Call)
                and _dotted(dec.func) in ("functools.partial", "partial")
                and dec.args and _dotted(dec.args[0]) in (
                    "jax.jit", "jit", "jax.custom_vjp", "custom_vjp",
                    "jax.custom_jvp", "custom_jvp")):
            return True
    return False


def _propagate_reachability(graph: _Graph):
    """Seed jit roots, then close over same/cross-module calls."""
    work: list[_FnInfo] = []

    def seed(fninfo):
        if fninfo and not fninfo.reachable:
            fninfo.reachable = True
            work.append(fninfo)

    for mod in graph.modules.values():
        # Decorator roots.
        for fn in mod.functions.values():
            if _decorated_as_root(fn.node):
                seed(fn)
        # Call-site roots (jax.jit(f), lax.scan(f,...), f.defvjp(a, b)).
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            if dotted and dotted.endswith(".defvjp"):
                for arg in node.args:
                    t = _dotted(arg)
                    if t:
                        seed(graph.resolve(mod, t))
                continue
            for target in _fn_targets(node):
                if isinstance(target, ast.Lambda):
                    # Anonymous jit region: treat the lambda body as its
                    # own reachable function.
                    fn = _FnInfo(node=target, module=mod, name="<lambda>")
                    graph.fn_of_node[id(target)] = fn
                    seed(fn)
                else:
                    t = _dotted(target)
                    if t:
                        seed(graph.resolve(mod, t))

    while work:
        fn = work.pop()
        for node in ast.walk(fn.node):
            if isinstance(node, ast.Call):
                t = _dotted(node.func)
                if t:
                    callee = graph.resolve(fn.module, t)
                    seed(callee)


def _scan_host_syncs(graph: _Graph) -> list[Finding]:
    out = []
    for fn in list(graph.fn_of_node.values()):
        if not fn.reachable:
            continue
        params = _fn_args(fn.node)
        body = (fn.node.body if not isinstance(fn.node, ast.Lambda)
                else [fn.node.body])
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                dotted = _dotted(node.func)
                where = fn.name or "<lambda>"
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr in _HOST_SYNC_ATTRS
                        and not node.args):
                    out.append(Finding(
                        rule="SYNC001", path=fn.module.path,
                        line=node.lineno, symbol=where,
                        message=(f".{node.func.attr}() host sync inside "
                                 f"jit-reachable {where}()")))
                elif dotted in _HOST_SYNC_CALLS:
                    out.append(Finding(
                        rule="SYNC001", path=fn.module.path,
                        line=node.lineno, symbol=where,
                        message=(f"{dotted}() host materialization "
                                 f"inside jit-reachable {where}()")))
                elif (dotted in _CAST_NAMES and len(node.args) == 1
                      and isinstance(node.args[0], ast.Name)
                      and node.args[0].id in params):
                    out.append(Finding(
                        rule="SYNC001", path=fn.module.path,
                        line=node.lineno, symbol=where,
                        message=(f"{dotted}({node.args[0].id}) casts a "
                                 f"parameter of jit-reachable {where}() "
                                 f"— host sync on a tracer")))
    return out


def _scan_rng(mod: _ModuleInfo) -> list[Finding]:
    out = []
    # Which local names are the stdlib `random` module?
    stdlib_random = {alias for alias, m in mod.import_mods.items()
                     if m == "random"}
    np_aliases = {alias for alias, m in mod.import_mods.items()
                  if m in ("numpy", "numpy.random")}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if not dotted:
            continue
        parts = dotted.split(".")
        # np.random.default_rng() with no seed (or an explicit None).
        if parts[-1] == "default_rng" and (
                parts[0] in np_aliases or "random" in parts[:-1]):
            seed_kw = next((k for k in node.keywords
                            if k.arg in ("seed", None)), None)
            unseeded = not node.args and seed_kw is None
            explicit_none = (node.args
                             and isinstance(node.args[0], ast.Constant)
                             and node.args[0].value is None)
            if unseeded or explicit_none:
                out.append(Finding(
                    rule="RNG001", path=mod.path, line=node.lineno,
                    symbol="default_rng",
                    message="np.random.default_rng() without an explicit "
                            "seed — unpinned randomness"))
        # Legacy global-state numpy draws: np.random.<draw>(...).
        elif (len(parts) >= 3 and parts[0] in np_aliases
              and parts[-2] == "random"
              and parts[-1] in _NP_RANDOM_GLOBAL_DRAWS):
            out.append(Finding(
                rule="RNG001", path=mod.path, line=node.lineno,
                symbol=f"np.random.{parts[-1]}",
                message=f"global-state np.random.{parts[-1]}() — use a "
                        f"seeded np.random.default_rng(seed)"))
        # Bare stdlib random.<draw>(...).
        elif (len(parts) == 2 and parts[0] in stdlib_random
              and parts[1] in _STDLIB_RANDOM_DRAWS):
            out.append(Finding(
                rule="RNG001", path=mod.path, line=node.lineno,
                symbol=f"random.{parts[1]}",
                message=f"stdlib random.{parts[1]}() draws from hidden "
                        f"global state — use a seeded generator"))
    return out


def _scan_clock(mod: _ModuleInfo, opts: Options) -> list[Finding]:
    if not any(mod.path.startswith(p) for p in opts.clock_paths):
        return []
    out = []
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if dotted in _CLOCK_CALLS:
            out.append(Finding(
                rule="CLK001", path=mod.path, line=node.lineno,
                symbol=dotted,
                message=(f"{dotted}() wall-clock read in the serving "
                         f"stack — route through the injectable clock "
                         f"(a default parameter value is the only "
                         f"allowed reference)")))
    return out


def _scan_tags(modules: list[_ModuleInfo]) -> list[Finding]:
    out = []
    by_dir: dict[str, dict[int, tuple[str, str, int]]] = {}
    for mod in modules:
        d = os.path.dirname(mod.path)
        seen = by_dir.setdefault(d, {})
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            t = node.targets[0]
            if not (isinstance(t, ast.Name) and "TAG" in t.id
                    and t.id.isupper()):
                continue
            if not (isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, int)):
                continue
            val = node.value.value
            if val in seen and seen[val][0] != t.id:
                prev_name, prev_path, prev_line = seen[val]
                out.append(Finding(
                    rule="TAG001", path=mod.path, line=node.lineno,
                    symbol=t.id,
                    message=(f"substream tag {t.id}={val} collides with "
                             f"{prev_name} ({prev_path}:{prev_line}) — "
                             f"fold_in substreams would coincide")))
            else:
                seen.setdefault(val, (t.id, mod.path, node.lineno))
    return out


def iter_python_files(root: str, subdirs, opts: Options):
    for sub in subdirs:
        base = os.path.join(root, sub)
        for dirpath, _dirnames, filenames in os.walk(base):
            for fname in sorted(filenames):
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                rel = relpath(full, root)
                if any(part in rel for part in opts.exclude_parts):
                    continue
                yield full, rel


def scan(root: str, subdirs=("src", "benchmarks", "tests", "tools"),
         opts: Options | None = None,
         extra_sources: list[tuple[str, str]] | None = None
         ) -> list[Finding]:
    """Run every jitlint rule; returns findings sorted by location.

    ``extra_sources`` is a list of (repo-relative-path, source-text)
    pairs scanned *in addition* to the on-disk tree (fixture tests use
    it to inject known-bad snippets without touching the real scan).
    """
    opts = opts or Options()
    modules: list[_ModuleInfo] = []
    for full, rel in iter_python_files(root, subdirs, opts):
        modules.append(_collect_module(full, rel))
    for rel, src in (extra_sources or []):
        modules.append(_collect_module(rel, rel, src=src))
    graph = _Graph(modules)
    _propagate_reachability(graph)
    findings = _scan_host_syncs(graph)
    for mod in modules:
        findings += _scan_rng(mod)
        findings += _scan_clock(mod, opts)
    findings += _scan_tags(modules)
    return sorted(findings, key=lambda f: (f.path, f.line, f.rule))
