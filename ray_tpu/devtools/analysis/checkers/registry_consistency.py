"""Checker 4 — registry consistency.

Every stringly-typed name the framework consults at runtime must appear
in its declared registry, so a typo'd fault point silently never fires,
a misprefixed metric, or an unregistered span name breaks CI instead of
an operator's dashboard:

* fault points — ``fault_injection.check("x")`` / ``injector.fires("x")``
  call sites must name a key of ``fault_injection.FAULT_POINTS`` (and,
  scanning the whole package, every registered point must be consulted
  somewhere: a dead registry row is a lie about coverage);
* span names — ``tracing.span("x")`` / ``annotate("x")`` /
  ``record_span[_batch]("x")`` / ``device_telemetry.setup_span("x")`` /
  ``record_setup_span("x")`` must name a key of
  ``tracing.SPAN_REGISTRY``; dynamic f-string names must start with a
  registered prefix entry (``...::`` or trailing-``_`` families like
  ``serve.ttft_``);
* scope names — ``jax.named_scope("x")`` (the names inside the compiled
  step that ``TrainStep.anatomy()`` and the benchmark's ``step.*_ms``
  metrics read) must name a key of ``tracing.SCOPE_REGISTRY``, and every
  registered scope must be opened somewhere;
* step counters — ``step_counter("x")`` (the name under which a loss hands
  a data-dependent fact out of the compiled step, which the step profiler's
  rows and the benchmark's readers then carry) must name a key of
  ``tracing.STEP_COUNTER_REGISTRY``, and every registered counter must be
  named somewhere;
* SLO objectives — ``SLOObjective("x", ...)`` call sites must name a key
  of ``serve.slo.SLO_OBJECTIVES``, and every registered objective must be
  wired into the watchdog's evaluation path (an objective nobody can
  evaluate is a lie about coverage);
* metric declarations — ``Counter/Gauge/Histogram("name", "help")`` with
  a literal name must be ``ray_tpu_``/``serve_`` prefixed, carry help
  text, and be declared at exactly one source site (the static half of
  the old ``scripts/check_metrics.py``).

The *runtime* half of the metrics lint (walks the live process registry,
catching dynamically-built declarations the AST cannot see) lives here
too as :func:`collect_runtime_metric_violations`; ``scripts/
check_metrics.py`` is now a thin shim over it.
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple

from ray_tpu.devtools.analysis import core

METRIC_CTORS = ("Counter", "Gauge", "Histogram")
#: the metric library itself declares no metrics; skip it and the analyzer
_METRIC_EXEMPT = ("ray_tpu/util/metrics.py", "ray_tpu/devtools/")
_FAULT_RECEIVERS = ("fault_injection", "injector", "inj")
_SPAN_FUNCS = ("span", "annotate", "record_span", "record_span_batch",
               # device_telemetry's: the same spans, also rows of the
               # set-up's account
               "setup_span", "record_setup_span")


def _first_arg_str(call: ast.Call) -> Optional[str]:
    if not call.args:
        return None
    arg = call.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return None


def _fstring_prefix(call: ast.Call) -> Optional[str]:
    """Literal head of an f-string first arg ('submit::' of
    f"submit::{name}"), or None."""
    if not call.args or not isinstance(call.args[0], ast.JoinedStr):
        return None
    values = call.args[0].values
    if values and isinstance(values[0], ast.Constant) \
            and isinstance(values[0].value, str):
        return values[0].value
    return None


class RegistryConsistencyChecker(core.Checker):
    name = "registry-consistency"
    description = ("fault points / span names / metric declarations that "
                   "don't match their registries")

    # ----------------------------------------------------------- per-module
    def check_module(self, module: core.SourceModule,
                     ctx: core.AnalysisContext) -> Iterator[core.Finding]:
        consulted: Set[str] = ctx.scratch.setdefault(
            "fault_points_consulted", set())
        spans_used: Set[str] = ctx.scratch.setdefault("spans_used", set())
        scopes_used: Set[str] = ctx.scratch.setdefault("scopes_used", set())
        counters_used: Set[str] = ctx.scratch.setdefault(
            "step_counters_used", set())
        metric_sites: Dict[str, List[Tuple[str, int]]] = ctx.scratch.setdefault(
            "metric_sites", {})
        in_fault_module = module.path.endswith("fault_injection.py")
        metric_exempt = any(module.path.startswith(p) or module.path == p
                            for p in _METRIC_EXEMPT) \
            or any(s in module.path for s in _METRIC_EXEMPT)

        # SLO objectives "in use": ctor call sites anywhere, plus the
        # watchdog's own evaluation wiring in serve/slo.py (dict keys /
        # comparisons naming an objective beyond its registry declaration
        # — e.g. _LATENCY_SERIES keys, the "availability" branch).
        if module.path.endswith("serve/slo.py") and ctx.slo_objectives:
            used: Set[str] = ctx.scratch.setdefault(
                "slo_objectives_used", set())
            decl_counts: Dict[str, int] = {}
            for node in ast.walk(module.tree):
                for target in core._assign_names(node):
                    if isinstance(target, ast.Name) \
                            and target.id == "SLO_OBJECTIVES":
                        value = getattr(node, "value", None)
                        if isinstance(value, ast.Dict):
                            for k in value.keys:
                                if isinstance(k, ast.Constant) \
                                        and isinstance(k.value, str):
                                    decl_counts[k.value] = \
                                        decl_counts.get(k.value, 0) + 1
            totals: Dict[str, int] = {}
            for node in ast.walk(module.tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and node.value in ctx.slo_objectives:
                    totals[node.value] = totals.get(node.value, 0) + 1
            for name in ctx.slo_objectives:
                if totals.get(name, 0) > decl_counts.get(name, 0):
                    used.add(name)

        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            # --- fault points ------------------------------------------
            if isinstance(func, ast.Attribute) and func.attr in ("check",
                                                                 "fires"):
                recv = func.value
                recv_name = recv.id if isinstance(recv, ast.Name) else None
                plausible = (func.attr == "fires"
                             or recv_name in _FAULT_RECEIVERS)
                point = _first_arg_str(node)
                if plausible and point is not None and not in_fault_module:
                    consulted.add(point)
                    if ctx.fault_points is not None \
                            and point not in ctx.fault_points:
                        yield core.Finding(
                            check=self.name, path=module.path,
                            line=node.lineno, symbol="<fault-point>",
                            detail=f"fault:{point}",
                            message=(f"fault point '{point}' is not "
                                     f"declared in fault_injection."
                                     f"FAULT_POINTS"))
            # --- spans --------------------------------------------------
            # --- SLO objectives ----------------------------------------
            ctor_name = None
            if isinstance(func, ast.Name):
                ctor_name = func.id
            elif isinstance(func, ast.Attribute):
                ctor_name = func.attr
            if ctor_name == "SLOObjective" \
                    and ctx.slo_objectives is not None:
                obj_name = _first_arg_str(node)
                if obj_name is None:
                    for kw in node.keywords:
                        if kw.arg == "name" \
                                and isinstance(kw.value, ast.Constant) \
                                and isinstance(kw.value.value, str):
                            obj_name = kw.value.value
                if obj_name is not None:
                    ctx.scratch.setdefault("slo_objectives_used",
                                           set()).add(obj_name)
                    if obj_name not in ctx.slo_objectives:
                        yield core.Finding(
                            check=self.name, path=module.path,
                            line=node.lineno, symbol="<slo-objective>",
                            detail=f"slo:{obj_name}",
                            message=(f"SLO objective '{obj_name}' is not "
                                     f"declared in serve.slo."
                                     f"SLO_OBJECTIVES"))
            span_func = None
            if isinstance(func, ast.Attribute) and func.attr in _SPAN_FUNCS:
                span_func = func.attr
            elif isinstance(func, ast.Name) and func.id in _SPAN_FUNCS:
                span_func = func.id
            if span_func is not None and ctx.span_names is not None:
                literal = _first_arg_str(node)
                prefix = _fstring_prefix(node)
                if literal is not None:
                    spans_used.add(literal)
                    if literal not in ctx.span_names:
                        yield core.Finding(
                            check=self.name, path=module.path,
                            line=node.lineno, symbol="<span>",
                            detail=f"span:{literal}",
                            message=(f"span name '{literal}' is not "
                                     f"declared in tracing.SPAN_REGISTRY"))
                elif prefix is not None:
                    prefixes = ctx.span_prefixes or ()
                    match = next((p for p in prefixes
                                  if prefix.startswith(p)), None)
                    if match is not None:
                        spans_used.add(match)
                    else:
                        yield core.Finding(
                            check=self.name, path=module.path,
                            line=node.lineno, symbol="<span>",
                            detail=f"span:{prefix}",
                            message=(f"dynamic span name f'{prefix}...' "
                                     f"matches no prefix entry ('::' or "
                                     f"trailing '_') in "
                                     f"tracing.SPAN_REGISTRY"))
            # --- scopes inside the compiled step ------------------------
            if ctx.scope_names is not None and (
                    (isinstance(func, ast.Attribute)
                     and func.attr == "named_scope")
                    or (isinstance(func, ast.Name)
                        and func.id == "named_scope")):
                scope = _first_arg_str(node)
                if scope is not None:
                    scopes_used.add(scope)
                    if scope not in ctx.scope_names:
                        yield core.Finding(
                            check=self.name, path=module.path,
                            line=node.lineno, symbol="<scope>",
                            detail=f"scope:{scope}",
                            message=(f"named_scope '{scope}' is not "
                                     f"declared in tracing.SCOPE_REGISTRY"))
            # --- counters that leave the compiled step ------------------
            if ctx.step_counter_names is not None and (
                    (isinstance(func, ast.Attribute)
                     and func.attr == "step_counter")
                    or (isinstance(func, ast.Name)
                        and func.id == "step_counter")):
                counter = _first_arg_str(node)
                if counter is not None:
                    counters_used.add(counter)
                    if counter not in ctx.step_counter_names:
                        yield core.Finding(
                            check=self.name, path=module.path,
                            line=node.lineno, symbol="<step-counter>",
                            detail=f"step-counter:{counter}",
                            message=(f"step counter '{counter}' is not "
                                     f"declared in "
                                     f"tracing.STEP_COUNTER_REGISTRY"))
            # --- metric declarations -----------------------------------
            ctor = None
            if isinstance(func, ast.Name) and func.id in METRIC_CTORS:
                ctor = func.id
            elif isinstance(func, ast.Attribute) and func.attr in METRIC_CTORS:
                ctor = func.attr
            if ctor is not None and not metric_exempt:
                mname = _first_arg_str(node)
                if mname is None:
                    continue
                metric_sites.setdefault(mname, []).append(
                    (module.path, node.lineno))
                if not mname.startswith(ctx.metric_prefixes):
                    yield core.Finding(
                        check=self.name, path=module.path, line=node.lineno,
                        symbol="<metric>", detail=f"metric-prefix:{mname}",
                        message=(f"metric '{mname}' is not prefixed with "
                                 f"one of {ctx.metric_prefixes}"))
                help_text = None
                if len(node.args) > 1 and isinstance(node.args[1], ast.Constant):
                    help_text = node.args[1].value
                if len(node.args) > 1 and isinstance(node.args[1], ast.Constant) \
                        and (not isinstance(help_text, str)
                             or not help_text.strip()):
                    yield core.Finding(
                        check=self.name, path=module.path, line=node.lineno,
                        symbol="<metric>", detail=f"metric-help:{mname}",
                        message=f"metric '{mname}' has blank help text")

    # ------------------------------------------------------------ aggregate
    def finalize(self, ctx: core.AnalysisContext) -> Iterator[core.Finding]:
        consulted = ctx.scratch.get("fault_points_consulted", set())
        if ctx.fault_points:
            for point in sorted(ctx.fault_points - consulted):
                yield core.Finding(
                    check=self.name,
                    path="ray_tpu/_private/fault_injection.py", line=1,
                    symbol="<fault-point>", detail=f"fault-unused:{point}",
                    message=(f"FAULT_POINTS entry '{point}' is never "
                             f"consulted by any check()/fires() call site"))
        spans_used = ctx.scratch.get("spans_used", set())
        if ctx.span_names:
            declared = set(ctx.span_names) | set(ctx.span_prefixes or ())
            for span in sorted(declared - spans_used):
                yield core.Finding(
                    check=self.name, path="ray_tpu/util/tracing.py", line=1,
                    symbol="<span>", detail=f"span-unused:{span}",
                    message=(f"SPAN_REGISTRY entry '{span}' is never opened "
                             f"by any span()/record_span call site"))
        scopes_used = ctx.scratch.get("scopes_used", set())
        for scope in sorted((ctx.scope_names or set()) - scopes_used):
            yield core.Finding(
                check=self.name, path="ray_tpu/util/tracing.py", line=1,
                symbol="<scope>", detail=f"scope-unused:{scope}",
                message=(f"SCOPE_REGISTRY entry '{scope}' is never opened "
                         f"by any named_scope call site"))
        counters_used = ctx.scratch.get("step_counters_used", set())
        for counter in sorted((ctx.step_counter_names or set())
                              - counters_used):
            yield core.Finding(
                check=self.name, path="ray_tpu/util/tracing.py", line=1,
                symbol="<step-counter>",
                detail=f"step-counter-unused:{counter}",
                message=(f"STEP_COUNTER_REGISTRY entry '{counter}' is never "
                         f"named by any step_counter call site"))
        slo_used = ctx.scratch.get("slo_objectives_used", set())
        if ctx.slo_objectives:
            for name in sorted(ctx.slo_objectives - slo_used):
                yield core.Finding(
                    check=self.name, path="ray_tpu/serve/slo.py", line=1,
                    symbol="<slo-objective>", detail=f"slo-unused:{name}",
                    message=(f"SLO_OBJECTIVES entry '{name}' is neither "
                             f"constructed at any SLOObjective call site "
                             f"nor wired into the watchdog evaluation"))
        for mname, sites in sorted(
                ctx.scratch.get("metric_sites", {}).items()):
            distinct = sorted(set(sites))
            if len(distinct) > 1:
                yield core.Finding(
                    check=self.name, path=distinct[0][0], line=distinct[0][1],
                    symbol="<metric>", detail=f"metric-dup:{mname}",
                    message=(f"metric '{mname}' declared at "
                             f"{len(distinct)} sites: "
                             + ", ".join(f"{p}:{l}" for p, l in distinct)))


# --------------------------------------------------------------- runtime lint
#: Every module that declares internal metrics at import time (module-level
#: Counter/Gauge/Histogram instances).  Keep in sync with new declarations —
#: a metric declared in a module not imported here is invisible to the
#: runtime lint (the static pass above sees it regardless).
METRIC_MODULES = (
    "ray_tpu._private.metrics_agent",
    "ray_tpu.serve.metrics",
    "ray_tpu.serve.router",
    "ray_tpu.serve.compiled_router",
    "ray_tpu.serve.batching",
    "ray_tpu.serve.continuous",
    "ray_tpu.serve.multiplex",
    "ray_tpu.serve.llm.metrics",
    "ray_tpu.serve.autoscaling",
    "ray_tpu.serve.deployment_state",
    "ray_tpu.checkpoint.metrics",
    "ray_tpu.train.metrics",
    "ray_tpu.data.ingest.metrics",
    "ray_tpu.util.flight_recorder",
    "ray_tpu.util.watchdog",
    "ray_tpu.util.device_telemetry",
    "ray_tpu.autoscaler.metrics",
)

ALLOWED_PREFIXES = ("ray_tpu_", "serve_")

#: Windowed accessor (dotted path under ray_tpu.serve) -> the registry
#: metric whose series it reads from the TimeSeriesAggregator.  The
#: runtime lint verifies the accessor exists AND its series matches a
#: declared metric name, so renaming a metric cannot silently strand an
#: accessor on a dead series (the SLO watchdog and the ROADMAP item 1
#: autoscaler consume these).
ACCESSOR_SERIES = {
    "metrics.request_rate": "serve_requests_total",
    "metrics.ttft_p99": "ray_tpu_llm_ttft_seconds",
    "metrics.inter_token_p99": "ray_tpu_llm_inter_token_seconds",
    "metrics.kv_utilization": "ray_tpu_llm_kv_blocks_in_use",
    "metrics.batch_occupancy": "ray_tpu_llm_batch_occupancy",
    "metrics.goodput_tokens_per_s": "ray_tpu_llm_decode_tokens_total",
    "metrics.recompute_waste_tokens_per_s":
        "ray_tpu_llm_recompute_tokens_total",
    "metrics.acceptance_rate": "ray_tpu_llm_spec_accepted_tokens_total",
    "metrics.prefix_hit_rate": "ray_tpu_llm_prefix_hit_tokens_total",
    "device.transfer_bw": "ray_tpu_device_transfer_bytes_total",
}


def _import_metric_modules() -> None:
    import importlib

    for mod in METRIC_MODULES:
        importlib.import_module(mod)
    # The runtime gauges are created lazily on first scrape; force them so
    # their names/help get linted too.
    from ray_tpu._private import metrics_agent

    metrics_agent._internal_gauges()


def collect_runtime_metric_violations() -> List[str]:
    """Walk the live process metric registry (catches declarations the AST
    pass cannot see: names built at runtime, metrics created in loops) and
    return violation strings — the old ``scripts/check_metrics.py`` body."""
    _import_metric_modules()

    import ray_tpu
    from ray_tpu.util import metrics as um

    pkg_root = os.path.realpath(os.path.dirname(ray_tpu.__file__))
    violations: List[str] = []
    # name -> {declaration file:line} for duplicate detection.  Multiple
    # *instances* from one site (e.g. a metric built per replica in a loop)
    # are legal; the same name from two different lines is a conflict.
    sites_by_name: Dict[str, set] = {}

    for group in um.registry().collect():
        for metric in group:
            declared_at = getattr(metric, "_declared_at", "<unknown>")
            decl_file = declared_at.rsplit(":", 1)[0]
            if not os.path.realpath(decl_file).startswith(pkg_root + os.sep):
                continue  # user/test metric sharing the process registry
            sites_by_name.setdefault(metric.name, set()).add(declared_at)
            if not (metric._description or "").strip():
                violations.append(
                    f"{metric.name}: missing help text ({declared_at})")
            if not metric.name.startswith(ALLOWED_PREFIXES):
                violations.append(
                    f"{metric.name}: internal metric not prefixed with one "
                    f"of {ALLOWED_PREFIXES} ({declared_at})")

    for name, sites in sorted(sites_by_name.items()):
        if len(sites) > 1:
            violations.append(
                f"{name}: declared at {len(sites)} sites: "
                + ", ".join(sorted(sites)))

    # Windowed-accessor wiring: each ACCESSOR_SERIES entry must resolve to
    # a callable under ray_tpu.serve and read a series that a declared
    # metric actually feeds (renames can't strand an accessor silently).
    from ray_tpu import serve as _serve

    for accessor, series in sorted(ACCESSOR_SERIES.items()):
        obj: Any = _serve
        for part in accessor.split("."):
            obj = getattr(obj, part, None)
            if obj is None:
                break
        if not callable(obj):
            violations.append(
                f"serve.{accessor}: accessor registered in ACCESSOR_SERIES "
                f"does not resolve to a callable")
        if series not in sites_by_name:
            violations.append(
                f"serve.{accessor}: reads series {series!r} which matches "
                f"no declared in-package metric")
    return violations
