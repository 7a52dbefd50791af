"""AST trace-purity lint over the jit-path packages.

What a runtime test can only pin one instance of, this lints as a class:

* **host-sync** — ``.item()`` / ``float()`` / ``int()`` / ``bool()`` /
  ``np.asarray()`` on JAX array expressions. Inside a jitted program
  these force a device sync (or a ``ConcretizationTypeError`` at best);
  on the round path they serialize the dispatch pipeline the fused-scan
  work spent five PRs removing.
* **np-on-jax** — ``np.*`` math applied to JAX values: silently falls
  back to host numpy via ``__array__``, a hidden transfer + f64
  promotion hazard.
* **nondeterminism** — ``time.*``, ``np.random.*``, ``random.*``,
  ``print`` inside traced code: trace-time effects that bake one
  trace's value into the compiled program (and differ across SPMD
  processes — the replay/determinism contracts of ``robust/faults.py``
  assume none exist).
* **tracer-branch** — Python ``if``/``while`` on a traced predicate
  (``if jnp.any(x):``) where ``lax.cond`` is the house style.
* **bare-assert** — ``assert`` on a contract path (``python -O`` strips
  it, ADVICE r5). Contract paths are **auto-discovered**: every module
  in the package except the reviewed ``NON_CONTRACT_ALLOWLIST`` — the
  hand-maintained 31-entry list of the old ``tests/test_no_bare_assert``
  had already drifted (``algorithms/ditto.py``, the ``comm/`` backends,
  and the newer ``robust/`` modules were unlisted).
* **donation-use-after** — reading a state variable after passing it to
  a DONATING entry point (``_round_jit`` / ``_finetune_jit`` /
  ``_global_mask_jit`` / ``run_round`` / ``run_rounds_fused``) on a
  driver path. Under the state-ownership protocol (``donate_state``)
  those calls consume their first argument — a later read hits a
  deleted buffer at runtime (or silently works only while donation is
  off). Drivers either rebind the variable in the same statement
  (``state, m = algo.run_round(state, r)``), read what they need
  BEFORE the call, or borrow via ``clone_state``. Conservative
  name-tracking: only ``x.<entry>(var, ...)`` call sites with >= 2
  positional args mark ``var``; the window closes at the next
  rebinding of ``var``.
* **deprecated-timer** — imports of the ``utils.profiling.Timer`` shim.
* **xfail hygiene** — every ``pytest.mark.xfail`` in ``tests/`` carries
  a non-empty ``reason=`` and an entry in the committed xfail ledger,
  so test debt grows only by deliberate ledger edits.

Traced-context discovery is static and deliberately conservative (the
Tricorder near-zero-false-positive bar): a function is *traced* when it
is (a) decorated with / wrapped by ``jax.jit`` (incl. ``partial``), (b)
passed by name to a tracing higher-order function (``vmap``, ``grad``,
``lax.scan/cond/map/while_loop``, ``shard_map``, ...), (c) defined
inside a traced function, or (d) reachable from a traced function
through the package-wide call graph (same-module calls, ``self.method``
calls resolved by method name across the package, and imported-name
calls resolved through the import table). Host-side drivers — the
seeded ``sample_client_indexes`` draw, the fused-block wall timers, the
bench harnesses — are none of these and stay lintable-clean by
construction. The traced-only rules (nondeterminism, tracer-branch)
apply inside traced functions; the host-sync family is module-wide in
the jit-path packages (a deliberate host sync there is exactly what the
baseline file exists to pin).
"""
from __future__ import annotations

import ast
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .findings import Finding

#: packages whose modules get the MODULE-WIDE host-sync family. The
#: traced-context rules run everywhere the discovery proves a function
#: traced — obs/ computes in-jit, models/data are traced into rounds —
#: but their host halves (export, loaders) legitimately sync, so the
#: module-wide sweep stays scoped to the hot-path packages.
JIT_PATH_PACKAGES = ("algorithms", "parallel", "robust", "ops", "core")

#: non-contract modules where bare ``assert`` is allowed, with the
#: reviewed reason. Everything else in the package is a contract path.
#: Keys ending in ``/`` are directory prefixes (codegen output dirs may
#: not exist on a fresh checkout — ``comm/_generated/`` is gitignored
#: and populated by the grpc codegen, so it cannot be pinned by exact
#: file path).
NON_CONTRACT_ALLOWLIST = {
    "nas/visualize.py": "DOT-source visualization helper; never on a "
                        "training or data-integrity path",
    "comm/_generated/": "grpc codegen output (gitignored; present "
                        "only after codegen runs)",
}


def _allowlisted(rel: str) -> bool:
    posix = rel.replace(os.sep, "/")
    for entry in NON_CONTRACT_ALLOWLIST:
        if entry.endswith("/"):
            if posix.startswith(entry):
                return True
        elif posix == entry:
            return True
    return False

#: higher-order functions whose function-valued arguments are traced
_TRACING_HOFS = {
    "jax.jit", "jit", "jax.vmap", "vmap", "jax.pmap", "pmap",
    "jax.grad", "jax.value_and_grad", "jax.jacfwd", "jax.jacrev",
    "jax.checkpoint", "jax.remat", "jax.eval_shape", "jax.make_jaxpr",
    "jax.lax.scan", "lax.scan", "jax.lax.map", "lax.map",
    "jax.lax.cond", "lax.cond", "jax.lax.switch", "lax.switch",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.associative_scan", "lax.associative_scan",
    "shard_map", "jax.shard_map",
}

#: dotted roots that mark an expression as a JAX array computation
_JAX_CALL_ROOTS = ("jnp.", "jax.numpy.", "lax.", "jax.lax.", "jax.nn.",
                   "jax.random.", "jax.tree_util.", "jax.scipy.")

#: jnp/lax attributes that are static predicates (trace-time Python
#: values, not tracers) — legal in Python ``if``
_STATIC_PREDICATES = {"issubdtype", "isdtype", "result_type", "dtype",
                      "promote_types", "iinfo", "finfo", "isscalar"}

#: np.* functions whose application to a JAX value is a hidden
#: host transfer (np math silently accepts jax arrays via __array__)
_NP_MATH = {
    "mean", "sum", "max", "min", "abs", "sqrt", "exp", "log", "dot",
    "matmul", "argmax", "argmin", "median", "std", "var", "prod",
    "concatenate", "stack", "where", "clip", "linalg", "norm", "sort",
    "cumsum", "tanh", "allclose", "array_equal", "isnan", "isinf",
    "isfinite", "any", "all", "maximum", "minimum", "percentile",
}

#: call roots that are nondeterministic / host-effectful under trace
_NONDET_ROOTS = ("time.", "np.random.", "numpy.random.", "random.",
                 "os.urandom")

#: method names that DONATE their first argument under the state-
#: ownership protocol (FedAlgorithm donate_state — algorithms/base.py).
#: Matched as attribute calls with >= 2 positional args so unrelated
#: same-named methods (comm.cross_silo.run_round(round_idx)) stay out.
_DONATING_ENTRIES = frozenset({
    "_round_jit", "_finetune_jit", "_global_mask_jit",
    "run_round", "run_rounds_fused",
})


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of a Name/Attribute chain ('' if not)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _contains_jax_call(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            d = _dotted(sub.func)
            if d.startswith(_JAX_CALL_ROOTS):
                return True
    return False


def _src_line(source_lines: Sequence[str], lineno: int) -> str:
    if 1 <= lineno <= len(source_lines):
        return source_lines[lineno - 1].strip()
    return ""


class _Module:
    """One parsed module: its functions, import table, and call edges."""

    def __init__(self, rel: str, source: str):
        self.rel = rel
        self.source_lines = source.splitlines()
        self.tree = ast.parse(source, filename=rel)
        # qualname -> FunctionDef/AsyncFunctionDef/Lambda
        self.functions: Dict[str, ast.AST] = {}
        # function-name (last path component) -> qualnames defining it
        self.by_name: Dict[str, List[str]] = {}
        # imported name -> (module string, original name, level)
        self.imports: Dict[str, Tuple[str, str, int]] = {}
        self._index()

    def _index(self) -> None:
        def visit(node: ast.AST, prefix: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qn = f"{prefix}{child.name}"
                    self.functions[qn] = child
                    self.by_name.setdefault(child.name, []).append(qn)
                    visit(child, qn + ".")
                elif isinstance(child, ast.ClassDef):
                    visit(child, f"{prefix}{child.name}.")
                else:
                    visit(child, prefix)

        visit(self.tree, "")
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                for alias in node.names:
                    self.imports[alias.asname or alias.name] = (
                        node.module, alias.name, node.level)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    self.imports[alias.asname or alias.name] = (
                        alias.name, "", 0)


class PackageLint:
    """Whole-package lint: parse every module once, discover the traced
    set by fixpoint over the package call graph, then apply the rules."""

    def __init__(self, pkg_root: str):
        self.pkg_root = os.path.abspath(pkg_root)
        self.pkg_name = os.path.basename(self.pkg_root)
        self.modules: Dict[str, _Module] = {}
        for rel in sorted(self._iter_py()):
            try:
                with open(os.path.join(self.pkg_root, rel)) as f:
                    self.modules[rel] = _Module(rel, f.read())
            except SyntaxError as e:
                raise ValueError(f"unparseable module {rel}: {e}") from e
        # (module rel, qualname) marked traced
        self.traced: Set[Tuple[str, str]] = set()
        self._discover_traced()

    # -- module discovery ---------------------------------------------------
    def _iter_py(self) -> Iterable[str]:
        for dirpath, dirs, files in os.walk(self.pkg_root):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                if f.endswith(".py"):
                    yield os.path.relpath(
                        os.path.join(dirpath, f), self.pkg_root)

    def contract_modules(self) -> List[str]:
        """Auto-discovered contract paths: every module except the
        reviewed non-contract allowlist."""
        return [rel for rel in sorted(self.modules)
                if not _allowlisted(rel)]

    # -- traced-set discovery -----------------------------------------------
    def _discover_traced(self) -> None:
        roots: Set[Tuple[str, str]] = set()
        for rel, mod in self.modules.items():
            for qn, fn in mod.functions.items():
                if self._has_tracing_decorator(fn):
                    roots.add((rel, qn))
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                d = _dotted(node.func)
                if d in _TRACING_HOFS or (
                        d in ("partial", "functools.partial")
                        and node.args
                        and _dotted(node.args[0]) in _TRACING_HOFS):
                    for arg in list(node.args) + [
                            kw.value for kw in node.keywords]:
                        nm = _dotted(arg)
                        for qn in mod.by_name.get(nm, ()):
                            roots.add((rel, qn))
        # nested defs of a traced function are traced
        closure = set(roots)
        for rel, qn in list(closure):
            mod = self.modules[rel]
            for other in mod.functions:
                if other.startswith(qn + "."):
                    closure.add((rel, other))
        # fixpoint over the package call graph
        changed = True
        while changed:
            changed = False
            for rel, qn in list(closure):
                for callee in self._callees(rel, qn):
                    if callee not in closure:
                        closure.add(callee)
                        changed = True
                        # nested defs of a newly traced fn
                        crel, cqn = callee
                        for other in self.modules[crel].functions:
                            if other.startswith(cqn + "."):
                                closure.add((crel, other))
        self.traced = closure

    @staticmethod
    def _has_tracing_decorator(fn: ast.AST) -> bool:
        for dec in getattr(fn, "decorator_list", ()):
            d = _dotted(dec)
            if d in _TRACING_HOFS:
                return True
            if isinstance(dec, ast.Call):
                dc = _dotted(dec.func)
                if dc in _TRACING_HOFS:
                    return True
                if dc in ("partial", "functools.partial") and dec.args \
                        and _dotted(dec.args[0]) in _TRACING_HOFS:
                    return True
        return False

    def _resolve_import(self, rel: str, module: str, level: int,
                        name: str) -> Optional[Tuple[str, str]]:
        """(module rel, qualname) of an imported function, if it lives
        in this package."""
        if level:
            base = os.path.dirname(rel)
            for _ in range(level - 1):
                base = os.path.dirname(base)
            target = os.path.join(base, *module.split("."))
        elif module.split(".")[0] == self.pkg_name:
            target = os.path.join(*module.split(".")[1:]) \
                if "." in module else ""
        else:
            return None
        for cand in (target + ".py",
                     os.path.join(target, "__init__.py") if target
                     else "__init__.py"):
            cand = os.path.normpath(cand)
            mod = self.modules.get(cand)
            if mod is not None and name in mod.by_name:
                return (cand, mod.by_name[name][0])
        return None

    def _callees(self, rel: str, qn: str) -> Iterable[Tuple[str, str]]:
        mod = self.modules[rel]
        fn = mod.functions.get(qn)
        if fn is None:
            return
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if not d:
                continue
            parts = d.split(".")
            if len(parts) == 1:
                # same-module function, or a from-import
                for cq in mod.by_name.get(parts[0], ()):
                    yield (rel, cq)
                if parts[0] in mod.imports:
                    m, orig, lvl = mod.imports[parts[0]]
                    hit = self._resolve_import(rel, m, lvl,
                                               orig or parts[0])
                    if hit:
                        yield hit
            elif parts[0] in ("self", "cls") and len(parts) == 2:
                # method call: resolve by method name package-wide
                # (class hierarchies span modules — FedAvg.round_fn
                # calls base._train_selected_weighted)
                for orel, omod in self.modules.items():
                    for cq in omod.by_name.get(parts[1], ()):
                        if "." in cq:  # methods only
                            yield (orel, cq)
            elif parts[0] in mod.imports and len(parts) == 2:
                m, orig, lvl = mod.imports[parts[0]]
                if orig:  # "from x import y as alias" then alias.attr
                    continue
                hit = self._resolve_import(rel, m, lvl, parts[1])
                if hit:
                    yield hit

    # -- rules --------------------------------------------------------------
    def _enclosing_traced(self, rel: str) -> List[ast.AST]:
        return [self.modules[rel].functions[qn]
                for r, qn in self.traced if r == rel]

    def lint(self, changed: Optional[Set[str]] = None) -> List[Finding]:
        """All findings for the package. ``changed`` (module rel paths)
        restricts the report for --changed-only runs."""
        out: List[Finding] = []
        for rel, mod in sorted(self.modules.items()):
            if changed is not None and rel not in changed:
                continue
            out.extend(self._lint_module(rel, mod))
        return out

    def _finding(self, mod: _Module, rule: str, node: ast.AST,
                 message: str) -> Finding:
        line = getattr(node, "lineno", 0)
        return Finding(
            rule=rule, file=f"{self.pkg_name}/{mod.rel}", line=line,
            message=message, detail=_src_line(mod.source_lines, line))

    def _lint_module(self, rel: str, mod: _Module) -> List[Finding]:
        out: List[Finding] = []
        top = rel.split(os.sep)[0]
        jit_path = top in JIT_PATH_PACKAGES

        # bare-assert: auto-discovered contract paths
        if not _allowlisted(rel):
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Assert):
                    out.append(self._finding(
                        mod, "bare-assert", node,
                        "bare assert on a contract path (python -O "
                        "strips it; raise ValueError/RuntimeError "
                        "instead)"))

        # deprecated-timer: the utils.profiling.Timer shim
        if rel != os.path.join("utils", "profiling.py"):
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.ImportFrom) and node.module and \
                        node.module.endswith("utils.profiling") and \
                        any(a.name == "Timer" for a in node.names):
                    out.append(self._finding(
                        mod, "deprecated-timer", node,
                        "utils.profiling.Timer is a deprecated shim; "
                        "use obs.metrics.SectionTimer"))
                elif isinstance(node, ast.Attribute) and \
                        node.attr == "Timer" and \
                        _dotted(node).endswith("profiling.Timer"):
                    out.append(self._finding(
                        mod, "deprecated-timer", node,
                        "utils.profiling.Timer is a deprecated shim; "
                        "use obs.metrics.SectionTimer"))

        # module-wide host-sync family (jit-path packages)
        if jit_path:
            out.extend(self._host_sync_rules(mod, mod.tree))

        # use-after-donation: every module (driver paths call the
        # donating entry points from algorithms/, experiments/, utils/).
        # functions dict lists nested defs separately AND walks reach
        # them through their parents — dedupe by (rule, line)
        dseen: Set[Tuple[str, int]] = set()
        for fn in mod.functions.values():
            for f in self._donation_rules(mod, fn):
                if (f.rule, f.line) not in dseen:
                    dseen.add((f.rule, f.line))
                    out.append(f)

        # traced-context rules: EVERY module — the traced set is proven
        # by discovery (decorated/wrapped/HOF/fixpoint), so a traced
        # model forward in models/ or a data transform reached from
        # _round_jit is in scope regardless of its package
        seen: Set[Tuple[str, int]] = {(f.rule, f.line) for f in out}
        for fn in self._enclosing_traced(rel):
            for f in self._traced_rules(mod, fn):
                if (f.rule, f.line) not in seen:
                    seen.add((f.rule, f.line))
                    out.append(f)
        return out

    def _host_sync_rules(self, mod: _Module,
                         scope: ast.AST) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call):
                continue
            d = _dotted(node.func)
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item" and not node.args:
                out.append(self._finding(
                    mod, "host-sync", node,
                    ".item() forces a device->host sync; on the round "
                    "path keep values on device (or pin deliberately "
                    "in the baseline)"))
            elif d in ("float", "int", "bool") and node.args and \
                    _contains_jax_call(node.args[0]):
                out.append(self._finding(
                    mod, "host-sync", node,
                    f"{d}() on a JAX expression blocks on the device; "
                    "use jnp dtype casts under trace, or pin the "
                    "deliberate host readout in the baseline"))
            elif d in ("np.asarray", "np.array", "numpy.asarray",
                       "numpy.array") and node.args and \
                    _contains_jax_call(node.args[0]):
                out.append(self._finding(
                    mod, "host-sync", node,
                    "np.asarray on a JAX expression is a hidden "
                    "device->host transfer"))
            elif d.startswith(("np.", "numpy.")) and \
                    d.split(".")[1] in _NP_MATH and \
                    any(_contains_jax_call(a) for a in node.args):
                out.append(self._finding(
                    mod, "np-on-jax", node,
                    f"{d} on a JAX expression computes on host via "
                    "__array__ (hidden transfer + f64 promotion); "
                    "use the jnp equivalent"))
        return out

    def _donation_rules(self, mod: _Module, fn: ast.AST) -> List[Finding]:
        """Use-after-donation within one function body: a Name passed
        as the first of >= 2 positional args to a donating entry point
        is invalid from the end of that call until its next rebinding;
        any Name load in that window is a finding. Same-statement tuple
        rebinds (``state, m = self.run_round(state, r)``) close the
        window immediately; reads hoisted ABOVE the call, clones, and
        conditional-expression args are all clean by construction."""
        # every line at which each name is (re)bound
        binds: Dict[str, List[int]] = {}

        def bind(target: ast.AST, line: int) -> None:
            if isinstance(target, ast.Name):
                binds.setdefault(target.id, []).append(line)
            elif isinstance(target, (ast.Tuple, ast.List)):
                for elt in target.elts:
                    bind(elt, line)
            elif isinstance(target, ast.Starred):
                bind(target.value, line)

        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    bind(t, node.lineno)
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                bind(node.target, node.lineno)
            elif isinstance(node, ast.NamedExpr):
                bind(node.target, node.lineno)
            elif isinstance(node, ast.For):
                bind(node.target, node.lineno)
            elif isinstance(node, ast.withitem) and \
                    node.optional_vars is not None:
                bind(node.optional_vars, getattr(
                    node.optional_vars, "lineno", 0))

        out: List[Finding] = []
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _DONATING_ENTRIES
                    and len(node.args) >= 2
                    and isinstance(node.args[0], ast.Name)):
                continue
            var = node.args[0].id
            call_end = getattr(node, "end_lineno", node.lineno)
            rebinds = [ln for ln in binds.get(var, [])
                       if ln >= node.lineno]
            if rebinds and min(rebinds) <= call_end:
                continue  # rebound by the call's own statement
            window_end = min(rebinds) if rebinds else float("inf")
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Name) and sub.id == var and \
                        isinstance(sub.ctx, ast.Load) and \
                        call_end < sub.lineno < window_end:
                    out.append(self._finding(
                        mod, "donation-use-after", sub,
                        f"{var!r} is read after being passed to "
                        f"donating entry point .{node.func.attr} "
                        f"(line {node.lineno}) — under donate_state "
                        "the call consumed it; read before the call, "
                        "rebind in the same statement, or borrow via "
                        "clone_state"))
                    break  # one finding per donated window
        return out

    def _traced_rules(self, mod: _Module, fn: ast.AST) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = _dotted(node.func)
                if d == "print" or d.startswith(_NONDET_ROOTS):
                    out.append(self._finding(
                        mod, "nondeterminism", node,
                        f"{d}() inside traced code runs at trace time "
                        "only (and differs across SPMD processes); "
                        "hoist to the host driver or use jax.random / "
                        "jax.debug.print"))
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "item" and not node.args:
                    out.append(self._finding(
                        mod, "host-sync", node,
                        ".item() inside traced code breaks the trace "
                        "(ConcretizationTypeError) or forces a sync"))
                elif d in ("float", "int", "bool") and node.args and \
                        _contains_jax_call(node.args[0]):
                    out.append(self._finding(
                        mod, "host-sync", node,
                        f"{d}() on a JAX expression inside traced code "
                        "concretizes the tracer; use jnp casts"))
            elif isinstance(node, (ast.If, ast.While)):
                for sub in ast.walk(node.test):
                    if isinstance(sub, ast.Call):
                        d = _dotted(sub.func)
                        if d.startswith(_JAX_CALL_ROOTS) and \
                                d.split(".")[-1] not in \
                                _STATIC_PREDICATES:
                            out.append(self._finding(
                                mod, "tracer-branch", node,
                                f"Python branch on traced predicate "
                                f"{d}(...): use lax.cond/lax.select "
                                "(a data-dependent Python branch "
                                "fails under jit; a trace-time one "
                                "bakes in one trace's value)"))
                            break
        return out


# -- xfail hygiene ----------------------------------------------------------

XFAIL_LEDGER_VERSION = 1


def _is_xfail_mark(node: ast.AST) -> bool:
    target = node.func if isinstance(node, ast.Call) else node
    return _dotted(target).endswith("mark.xfail")


def _xfail_marks(tree: ast.AST):
    """Yield (mark node, owner qualname) for every ``pytest.mark.xfail``
    usage — decorators, ``pytest.param(..., marks=...)`` inside
    parametrize lists, and module-level ``pytestmark`` assignments all
    count (each is the standard spelling of the same test debt). The
    qualname includes enclosing classes (``Class.test_x``) so two
    same-named tests in different classes cannot share a ledger pin;
    marks outside any function/class pin as ``<module>``."""
    def scan_expr(node: ast.AST, owner: str):
        # a Call mark also contains its mark.xfail Attribute child;
        # both match and share a line — scan_xfails dedupes by
        # (id, line), with the Call (which carries reason=) seen first
        for sub in ast.walk(node):
            if isinstance(sub, (ast.Call, ast.Attribute)) and \
                    _is_xfail_mark(sub):
                yield sub, owner

    def visit(node: ast.AST, prefix: str, owner: str):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef, ast.ClassDef)):
                qn = f"{prefix}{child.name}"
                for dec in child.decorator_list:
                    yield from scan_expr(dec, qn)
                yield from visit(child, qn + ".", qn)
            else:
                if isinstance(child, (ast.Assign, ast.Expr)):
                    yield from scan_expr(child, owner)
                yield from visit(child, prefix, owner)

    yield from visit(tree, "", "<module>")


def scan_xfails(tests_dir: str) -> List[dict]:
    """Every ``pytest.mark.xfail`` site under ``tests/`` (recursive):
    id, reason, line. Ids are ``<relpath>::<qualified owner>`` —
    stable across line drift. De-duplicated per (id, line, column): a
    Call mark and its inner ``mark.xfail`` attribute share a position
    and count once, while two distinct marks on one source line (a
    one-line parametrize list) keep separate columns and both count."""
    sites = []
    seen = set()
    for dirpath, dirs, files in os.walk(tests_dir):
        dirs[:] = [d for d in dirs
                   if d not in ("__pycache__", ".pytest_cache")]
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, fname),
                                  tests_dir).replace(os.sep, "/")
            with open(os.path.join(dirpath, fname)) as f:
                try:
                    tree = ast.parse(f.read(), filename=rel)
                except SyntaxError:
                    continue  # collection errors are pytest's to report
            for mark, owner in _xfail_marks(tree):
                reason = ""
                if isinstance(mark, ast.Call):
                    for kw in mark.keywords:
                        if kw.arg == "reason" and \
                                isinstance(kw.value, ast.Constant):
                            reason = str(kw.value.value)
                key = (f"{rel}::{owner}", mark.lineno,
                       mark.col_offset)
                if key in seen:
                    continue
                seen.add(key)
                sites.append({"id": key[0], "reason": reason.strip(),
                              "line": mark.lineno, "ledger": True})
            # imperative pytest.xfail("why") calls: runtime-conditional
            # (often environment-gated), so they need a reason but not
            # a ledger pin
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and \
                        _dotted(node.func) == "pytest.xfail":
                    reason = ""
                    if node.args and isinstance(node.args[0],
                                                ast.Constant):
                        reason = str(node.args[0].value)
                    sites.append({"id": f"{rel}::line{node.lineno}",
                                  "reason": reason.strip(),
                                  "line": node.lineno,
                                  "ledger": False})
    return sites


def load_xfail_ledger(path: str) -> Dict[str, str]:
    """``id -> pinned reason``; schema errors raise ValueError (gate
    exit 2)."""
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(f"unreadable xfail ledger {path}: {e}") from e
    if not isinstance(doc, dict) or \
            doc.get("version") != XFAIL_LEDGER_VERSION:
        raise ValueError(f"xfail ledger {path}: bad version")
    out: Dict[str, str] = {}
    for e in doc.get("entries", ()):
        # validate like load_baseline: a malformed entry must surface
        # as ValueError -> gate exit 2, never a KeyError traceback
        if not isinstance(e, dict) or "id" not in e:
            raise ValueError(
                f"xfail ledger {path}: every entry needs an id, "
                f"got {e!r}")
        out[str(e["id"])] = str(e.get("reason", ""))
    return out


def check_xfails(tests_dir: str, ledger_path: str) -> List[Finding]:
    """xfail hygiene: non-empty reasons, and the site set must equal the
    committed ledger — new test debt requires a deliberate ledger edit,
    and a fixed test requires deleting its pin."""
    out: List[Finding] = []
    sites = scan_xfails(tests_dir)
    ledger = load_xfail_ledger(ledger_path)
    seen = set()
    for s in sites:
        if not s["reason"]:
            out.append(Finding(
                rule="xfail-reason", file=f"tests/{s['id'].split('::')[0]}",
                line=s["line"], detail=s["id"],
                message=f"{s['id']}: xfail without a non-empty reason "
                        "(say why it fails and what unblocks it)"))
        if not s.get("ledger", True):
            continue  # imperative pytest.xfail: reason-only
        seen.add(s["id"])
        if s["id"] not in ledger:
            out.append(Finding(
                rule="xfail-ledger", file=f"tests/{s['id'].split('::')[0]}",
                line=s["line"], detail=s["id"],
                message=f"{s['id']}: xfail not pinned in the ledger "
                        f"({os.path.basename(ledger_path)}) — new test "
                        "debt requires a deliberate ledger entry"))
    for lid in ledger:
        if lid not in seen:
            out.append(Finding(
                rule="xfail-ledger", file="", line=0, detail=lid,
                message=f"ledger entry {lid!r} matches no xfail in "
                        "tests/ (fixed? delete its pin)"))
    return out
