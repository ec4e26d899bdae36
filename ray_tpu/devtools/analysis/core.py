"""Core of the framework-aware static analyzer.

Plugin architecture: each checker is a subclass of :class:`Checker`
registered in ``checkers/__init__.py``; the :func:`run` driver parses
every target file once (AST + comment map via ``tokenize``) and hands the
shared :class:`SourceModule` to each enabled checker.  Findings carry a
*stable key* (no line numbers) so the baseline survives unrelated edits.

Annotation conventions (see docs/static-analysis.md):

  ``# guarded_by: _lock``     on an attribute (or module global) assignment:
                              every later read/write must happen inside a
                              ``with <owner>.<_lock>`` scope (or between
                              ``acquire()``/``release()``).
  ``# requires_lock: _lock``  on a ``def`` line: the method assumes its
                              caller holds the lock (``*_locked`` method
                              names get this implicitly).
  ``# blocking_ok: reason``   suppress a blocking-in-handler finding.
  ``# lockstep_ok: reason``   suppress a collective-divergence finding.
  ``# pairs_with: name``      on a ``def`` line: every call to this method
                              must be reversed by ``name`` on the same
                              receiver before every exit (strict).  On a
                              call line: that call site carries the same
                              obligation (the reverse may also match the
                              call's assignment target).
  ``# detached_ok: reason``   on an ``asyncio.create_task``/``ensure_future``
                              line: the task is intentionally unawaited.
  ``# owned_by_thread: name`` on an attribute assignment: the attribute is
                              owned by the thread running method ``name``
                              (or an external thread when ``name`` is not a
                              method) — cross-thread access without a lock
                              is flagged.
  ``# analysis: ignore[check-id] reason``
                              suppress any finding on that line.

The analyzer is pure AST + tokenize — it never imports the code under
analysis, so it is safe to run on broken trees and fast enough for tier-1
(<10s over the whole package, enforced by tests/test_analysis_static.py).
"""

from __future__ import annotations

import ast
import fnmatch
import io
import os
import re
import time
import tokenize
from dataclasses import dataclass, field
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Set, Tuple

_MARKER_RE = re.compile(
    r"#\s*(guarded_by|requires_lock|blocking_ok|lockstep_ok"
    r"|pairs_with|detached_ok|owned_by_thread)\s*:\s*(\S[^#]*)")
_IGNORE_RE = re.compile(r"#\s*analysis:\s*ignore\[([a-z0-9_,\- ]+)\]")


@dataclass(frozen=True)
class Finding:
    """One violation.  ``key`` is the stable identity used for baselining:
    check + file + enclosing symbol + detail, deliberately line-free."""

    check: str
    path: str  # repo-relative, '/'-separated
    line: int
    symbol: str  # "Class.method", "function", or "<module>"
    message: str
    detail: str  # stable discriminator (attr/point/span/metric name)

    @property
    def key(self) -> str:
        return f"{self.check}:{self.path}:{self.symbol}:{self.detail}"

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.check}] {self.message}"


class SourceModule:
    """One parsed file: AST + per-line comment map + annotation indexes."""

    def __init__(self, abspath: str, relpath: str, text: str):
        self.abspath = abspath
        self.path = relpath.replace(os.sep, "/")
        self.text = text
        self.tree = ast.parse(text, filename=relpath)
        #: line -> full comment text ("# ..."), from tokenize (comments
        #: inside string literals never leak in).
        self.comments: Dict[int, str] = {}
        try:
            for tok in tokenize.generate_tokens(io.StringIO(text).readline):
                if tok.type == tokenize.COMMENT:
                    self.comments[tok.start[0]] = tok.string
        except (tokenize.TokenError, IndentationError):  # pragma: no cover
            pass

    def marker(self, line: int, name: str) -> Optional[str]:
        """Value of ``# <name>: <value>`` on ``line`` (stripped), or None."""
        comment = self.comments.get(line)
        if not comment:
            return None
        m = _MARKER_RE.search(comment)
        if m and m.group(1) == name:
            return m.group(2).strip()
        return None

    def marker_near(self, line: int, name: str) -> Optional[str]:
        """Like :meth:`marker`, but also accepts the marker on its own
        comment line directly above (the usual lint-suppression layout
        when the flagged line is too long to annotate inline)."""
        return self.marker(line, name) or self.marker(line - 1, name)

    def ignored_checks(self, line: int) -> Set[str]:
        comment = self.comments.get(line)
        if not comment:
            return set()
        m = _IGNORE_RE.search(comment)
        if not m:
            return set()
        return {c.strip() for c in m.group(1).split(",") if c.strip()}


# --------------------------------------------------------------- annotations

@dataclass
class GuardMap:
    """guarded_by/requires_lock annotations for one module."""

    #: class qualname -> {attr name -> lock attr name}
    class_guards: Dict[str, Dict[str, str]] = field(default_factory=dict)
    #: class qualname -> {method name -> lock attr name} (caller must hold)
    requires_lock: Dict[str, Dict[str, str]] = field(default_factory=dict)
    #: module-global name -> module-global lock name
    module_guards: Dict[str, str] = field(default_factory=dict)


def _assign_names(node: ast.stmt) -> Iterator[ast.expr]:
    if isinstance(node, ast.Assign):
        yield from node.targets
    elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
        yield node.target


def collect_guards(module: SourceModule) -> GuardMap:
    guards = GuardMap()
    for node in module.tree.body:
        for target in _assign_names(node):
            if isinstance(target, ast.Name):
                lock = module.marker(node.lineno, "guarded_by")
                if lock:
                    guards.module_guards[target.id] = lock
    for cls in ast.walk(module.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        attr_guards: Dict[str, str] = {}
        req: Dict[str, str] = {}
        for node in ast.walk(cls):
            for target in _assign_names(node):
                if (isinstance(target, ast.Attribute)
                        and isinstance(target.value, ast.Name)
                        and target.value.id == "self"):
                    lock = module.marker(node.lineno, "guarded_by")
                    if lock:
                        attr_guards[target.attr] = lock
        default_lock = None
        locks = set(attr_guards.values())
        if len(locks) == 1:
            default_lock = next(iter(locks))
        for fn in cls.body:
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            lock = module.marker(fn.lineno, "requires_lock")
            if lock is None and fn.name.endswith("_locked"):
                lock = default_lock
            if lock is not None:
                req[fn.name] = lock
        if attr_guards:
            guards.class_guards[cls.name] = attr_guards
        if req:
            guards.requires_lock[cls.name] = req
    return guards


def _thread_target_name(call: ast.Call) -> Optional[str]:
    """``self._pump`` -> "_pump" for ``threading.Thread(target=self._pump)``
    and ``threading.Timer(delay, self._fire)``; None otherwise."""
    func = call.func
    name = func.attr if isinstance(func, ast.Attribute) else (
        func.id if isinstance(func, ast.Name) else None)
    if name not in ("Thread", "Timer"):
        return None
    target: Optional[ast.expr] = None
    for kw in call.keywords:
        if kw.arg in ("target", "function"):
            target = kw.value
    if target is None and name == "Timer" and len(call.args) >= 2:
        target = call.args[1]
    if (isinstance(target, ast.Attribute)
            and isinstance(target.value, ast.Name)
            and target.value.id == "self"):
        return target.attr
    return None


def collect_thread_targets(module: SourceModule) -> Dict[str, Set[str]]:
    """class name -> method names spawned as thread entry points anywhere in
    that class (``threading.Thread(target=self._x)`` / ``Timer(.., self._x)``).

    Methods listed here run on their own thread; the cross-thread-ownership
    checker treats everything else in the class as "some other thread"."""
    out: Dict[str, Set[str]] = {}
    for cls in ast.walk(module.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        entries: Set[str] = set()
        for node in ast.walk(cls):
            if isinstance(node, ast.Call):
                target = _thread_target_name(node)
                if target is not None:
                    entries.add(target)
        if entries:
            out[cls.name] = entries
    return out


# ------------------------------------------------------------------ context

@dataclass
class AnalysisContext:
    """Shared state handed to every checker.

    Registries are loaded once (AST-extracted from the package sources, no
    imports) by ``load_registries``; fixture tests inject their own."""

    root: str = "."
    fault_points: Optional[Set[str]] = None
    span_names: Optional[Set[str]] = None
    span_prefixes: Optional[Tuple[str, ...]] = None
    scope_names: Optional[Set[str]] = None
    slo_objectives: Optional[Set[str]] = None
    metric_prefixes: Tuple[str, ...] = ("ray_tpu_", "serve_")
    #: set when the scan covers the whole package — enables aggregate
    #: (cross-module) checks like "registered fault point never consulted"
    full_package: bool = False
    #: scratch space for aggregating checkers (keyed by checker name)
    scratch: Dict[str, object] = field(default_factory=dict)


def _extract_literal_dict_keys(tree: ast.AST, var_name: str) -> Set[str]:
    for node in ast.walk(tree):
        for target in _assign_names(node):
            if isinstance(target, ast.Name) and target.id == var_name:
                value = getattr(node, "value", None)
                if isinstance(value, ast.Dict):
                    return {k.value for k in value.keys
                            if isinstance(k, ast.Constant)
                            and isinstance(k.value, str)}
    return set()


def load_registries(ctx: AnalysisContext, package_dir: str) -> None:
    """Fill ctx's fault-point, span, scope and SLO-objective registries from the
    package sources (AST only — the analyzer never imports the analyzed
    code)."""
    fi = os.path.join(package_dir, "_private", "fault_injection.py")
    tr = os.path.join(package_dir, "util", "tracing.py")
    sl = os.path.join(package_dir, "serve", "slo.py")
    if ctx.fault_points is None and os.path.exists(fi):
        with open(fi, encoding="utf-8") as f:
            ctx.fault_points = _extract_literal_dict_keys(
                ast.parse(f.read()), "FAULT_POINTS")
    if ctx.scope_names is None and os.path.exists(tr):
        with open(tr, encoding="utf-8") as f:
            ctx.scope_names = _extract_literal_dict_keys(
                ast.parse(f.read()), "SCOPE_REGISTRY")
    if ctx.span_names is None and os.path.exists(tr):
        with open(tr, encoding="utf-8") as f:
            names = _extract_literal_dict_keys(ast.parse(f.read()),
                                               "SPAN_REGISTRY")
        # Prefix entries end in "::" (task::, submit::) or "_" (dynamic
        # bucket families like serve.ttft_<bucket>).
        ctx.span_prefixes = tuple(sorted(
            n for n in names if n.endswith("::") or n.endswith("_")))
        ctx.span_names = {n for n in names
                          if not (n.endswith("::") or n.endswith("_"))}
    if ctx.slo_objectives is None and os.path.exists(sl):
        with open(sl, encoding="utf-8") as f:
            ctx.slo_objectives = _extract_literal_dict_keys(
                ast.parse(f.read()), "SLO_OBJECTIVES")


# ------------------------------------------------------------------ checker

class Checker:
    name: str = ""
    description: str = ""

    def collect(self, module: SourceModule, ctx: AnalysisContext) -> None:
        """Pre-pass over every module before any ``check_module`` call —
        lets cross-module declarations (``# pairs_with:`` on a ``def``)
        reach call sites in other files.  Contributions go in
        ``ctx.scratch``; must be deterministic and idempotent per module."""

    def check_module(self, module: SourceModule,
                     ctx: AnalysisContext) -> Iterator[Finding]:
        raise NotImplementedError

    def finalize(self, ctx: AnalysisContext) -> Iterator[Finding]:
        """Aggregate findings after every module was scanned (only called
        when ctx.full_package)."""
        return iter(())


# ------------------------------------------------------------------- driver

DEFAULT_EXCLUDE = ("*/__pycache__/*",)


def iter_python_files(paths: Sequence[str],
                      exclude: Sequence[str] = ()) -> Iterator[str]:
    patterns = tuple(exclude) + DEFAULT_EXCLUDE
    seen = set()

    def excluded(p: str) -> bool:
        q = p.replace(os.sep, "/")
        return any(fnmatch.fnmatch(q, pat) or fnmatch.fnmatch(
            os.path.basename(q), pat) for pat in patterns)

    for path in paths:
        if os.path.isfile(path):
            if path.endswith(".py") and not excluded(path) and path not in seen:
                seen.add(path)
                yield path
        else:
            for dirpath, dirnames, filenames in os.walk(path):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for fn in sorted(filenames):
                    full = os.path.join(dirpath, fn)
                    if fn.endswith(".py") and not excluded(full) \
                            and full not in seen:
                        seen.add(full)
                        yield full


def parse_module(abspath: str, root: str) -> Optional[SourceModule]:
    rel = os.path.relpath(abspath, root)
    try:
        with open(abspath, encoding="utf-8") as f:
            text = f.read()
        return SourceModule(abspath, rel, text)
    except (SyntaxError, UnicodeDecodeError, OSError):
        return None


def analyze_source(text: str, checkers: Sequence[Checker],
                   ctx: Optional[AnalysisContext] = None,
                   path: str = "<fixture>.py") -> List[Finding]:
    """Analyze one source string — the fixture-test entry point."""
    ctx = ctx or AnalysisContext()
    module = SourceModule(path, path, text)
    out: List[Finding] = []
    for checker in checkers:
        checker.collect(module, ctx)
    for checker in checkers:
        for finding in checker.check_module(module, ctx):
            if checker.name in module.ignored_checks(finding.line):
                continue
            out.append(finding)
    return out


def run(paths: Sequence[str], checkers: Sequence[Checker],
        root: Optional[str] = None, exclude: Sequence[str] = (),
        ctx: Optional[AnalysisContext] = None) -> Tuple[List[Finding], dict]:
    """Run ``checkers`` over every .py file under ``paths``.

    Returns (findings, stats).  Inline ``# analysis: ignore[...]``
    suppressions are applied here; baseline suppression is the caller's
    job (scripts/analyze.py / baseline.py).
    """
    root = root or os.getcwd()
    ctx = ctx or AnalysisContext(root=root)
    t0 = time.monotonic()
    files = list(iter_python_files(paths, exclude))
    # Aggregate (cross-module) checks only make sense when the scan spans
    # the package: key off the fault-injection module being included.
    ctx.full_package = any(
        f.replace(os.sep, "/").endswith("_private/fault_injection.py")
        for f in files)
    package_dir = None
    for f in files:
        norm = f.replace(os.sep, "/")
        if norm.endswith("ray_tpu/_private/fault_injection.py"):
            package_dir = os.path.dirname(os.path.dirname(f))
            break
    if package_dir is None:
        # Fall back to a ray_tpu package next to the scan root (lets
        # `analyze.py scripts/` resolve registries too).
        candidate = os.path.join(root, "ray_tpu")
        if os.path.isdir(candidate):
            package_dir = candidate
    if package_dir is not None:
        load_registries(ctx, package_dir)

    # Two passes: collect (cross-module declarations such as def-site
    # ``# pairs_with:``) over every module first, then check.  Modules are
    # parsed once and kept — the package comfortably fits in memory and the
    # incremental cache (cache.py) depends on the same structure.
    findings: List[Finding] = []
    modules: List[SourceModule] = []
    for abspath in files:
        module = parse_module(abspath, root)
        if module is not None:
            modules.append(module)
    parsed = len(modules)
    for module in modules:
        for checker in checkers:
            checker.collect(module, ctx)
    for module in modules:
        for checker in checkers:
            for finding in checker.check_module(module, ctx):
                if checker.name in module.ignored_checks(finding.line):
                    continue
                findings.append(finding)
    if ctx.full_package:
        for checker in checkers:
            findings.extend(checker.finalize(ctx))
    stats = {"files": parsed, "seconds": time.monotonic() - t0,
             "checks": [c.name for c in checkers]}
    findings.sort(key=lambda f: (f.path, f.line, f.check))
    return findings, stats
