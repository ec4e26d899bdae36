"""Distributed tracing: spans around task submit/execute.

Counterpart of the reference's OpenTelemetry integration
(ref: util/tracing/tracing_helper.py — _OpenTelemetryProxy:34,
_is_tracing_enabled:92): opt-in via `enable_tracing()`; when on, every task
submission opens a submit span and every execution opens an execute span
parented on the submitter's span — the trace context rides inside the
TaskSpec exactly like the reference propagates it in its TaskSpec proto.
Spans go to a pluggable exporter (default: in-memory buffer; any callable
taking a span dict works, e.g. one that forwards to an OTLP client).

Every ``span()`` is also a ``jax.profiler.TraceAnnotation`` once jax is in
the process, whether or not ``enable_tracing()`` was called: jax makes the
annotation a no-op while no profile is open, and with one open (the
benchmark's, ``jax.profiler.trace``, an operator's xprof) the span lands on
its thread's line of ``/host:CPU``, on the device trace's clock.
``annotate()`` is the same annotation without the exporter's span.  Work
*inside* the compiled step is named by ``jax.named_scope`` with the names of
:data:`SCOPE_REGISTRY`.
"""

from __future__ import annotations

import contextvars
import itertools
import random
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from collections import deque

_enabled = False
_exporter: Optional[Callable[[dict], None]] = None
#: Default exporter: bounded ring buffer (2 spans/task would otherwise grow
#: without limit in a long-running driver).
_BUFFER_MAX = 100_000
_buffer: "deque" = deque(maxlen=_BUFFER_MAX)
_buffer_lock = threading.Lock()
_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_span", default=None)

# Span/trace id generation sits on the serve hot path (several spans per
# request, mostly on the proxy/replica event loops), so uuid4's ~2us of
# os.urandom per id is real QPS: ids here are a random per-process base
# XOR a golden-ratio-mixed atomic counter — ~0.1us, unique within the
# process (odd-constant multiply is a bijection mod 2**64) and across
# processes by the base; the mix spreads the counter into the high bits so
# id prefixes (e.g. the per-trace timeline lanes keyed on trace_id[:8])
# still differ.  Tracing ids need uniqueness, not unpredictability.
_ID_BASE = random.SystemRandom().getrandbits(64)
_id_counter = itertools.count(1)  # next() is atomic under the GIL
_MASK64 = (1 << 64) - 1
_GOLDEN = 0x9E3779B97F4A7C15


#: Canonical registry of span names the framework opens: name -> what the
#: span covers.  Entries ending in ``::`` or ``_`` are prefixes for
#: dynamic names (``f"task::{name}"``, ``f"serve.ttft_{bucket}"``).  The
#: static analyzer (registry-consistency checker) enforces that every
#: span()/record_span call site uses a registered name and that no
#: registered name is dead — dashboards and trace queries key on these
#: strings, so a typo'd name is an invisible gap.
SPAN_REGISTRY: Dict[str, str] = {
    "submit::": "driver-side task submission (suffix: task name)",
    "task::": "worker-side task execution (suffix: task name)",
    "serve.http_request": "proxy: full HTTP request lifetime",
    "serve.route": "router: replica pick + dispatch",
    "serve.compiled_route": "router: compiled-path dispatch -> response "
                            "demux, per request (batch-exported)",
    "serve.compiled_batch": "replica: compiled-loop vectorized execution, "
                            "per request (batch-exported)",
    "serve.replica": "replica: user-handler execution",
    "serve.queue_wait": "batching: enqueue -> batch formation, per request",
    "serve.batch_execute": "batching: vectorized user call, per request",
    "serve.stream_emit": "proxy: one streamed chunk emission",
    "serve.prefill": "llm: prompt prefill into the paged KV cache",
    "serve.decode": "llm: one decode micro-batch pass (single model key)",
    "serve.kv_handoff": "llm: KV-page export/import between prefill and "
                        "decode pools",
    "serve.ttft_": "llm: one TTFT attribution bucket (suffix: queue | "
                   "admission | prefill | handoff | residual)",
    "serve.preempt_recompute": "llm: prefill re-run of already-generated "
                               "tokens after a preemption",
    "serve.slo_burn": "slo: one deployment's burn episode, alert -> clear",
    "checkpoint.save": "writer: shard serialize + persist",
    "checkpoint.commit": "coordinator: commit phase up to atomic rename",
    "checkpoint.restore": "restore_pytree entry",
    "data.ingest": "ingest: one source shard, first pull -> last block out",
    "data.locality_claim": "ingest: one locality-aware shard claim "
                           "(attrs: preferred, local)",
    "data.prefetch": "ingest: host->device transfer dispatch, per batch",
    "data.pump": "ingest: one batch pulled out of the pipeline by the "
                 "ingest-prefetch pump thread",
    "runtime.init": "ray_tpu.init(): the runtime's start, entry to return "
                    "(a row of device_telemetry.setup_account())",
    "train.fit_setup": "trainer: fit() entry to the moment the first "
                       "worker enters the loop function (placement group, "
                       "workers, dataset split, sessions; attrs: workers, "
                       "worker_mode); a row of the set-up's account",
    "train.first_batch": "ingest: a shard's first epoch, the consumer's "
                         "first pull to the first batch in its hands (on "
                         "the device under a device_sharding; attrs: "
                         "bytes); a row of the set-up's account",
    "train.step": "profiler: one training step, report() to report()",
    "train.data_wait": "profiler: step blocked on the input pipeline (live "
                       "around the starved wait in HostPrefetcher)",
    "train.dispatch": "TrainStep: one call of the jitted train step, host "
                      "side (enqueue; trace+compile on a first call)",
    "train.first_call": "TrainStep: a call that built or loaded an "
                        "executable (trace, lower, compile or cache load, "
                        "dispatch), recorded after the fact, a row of the "
                        "set-up's account (attrs: label, the phases "
                        "trace_s, lower_s, compile_s, other_s, "
                        "cache_load_s, cache, and what the traced code "
                        "noted of itself: the keys of "
                        "util/first_call.py's first_call.KEYS)",
    "train.report": "session: one train.report() call, step boundary "
                    "included",
    "train.step_done": "profiler: its resolver thread's wait for one "
                       "dispatched step's sentinel (TrainStep hands it "
                       "over); the end is when the step finished on the "
                       "device, the row's done",
    "train.init_params": "create_sharded_state: parameters initialised "
                         "into their sharded layout (attrs: label and the "
                         "phases, as train.first_call); a row of the "
                         "set-up's account",
    "train.init_opt_state": "create_sharded_state: optimizer state derived "
                            "from the parameters (attrs and account as "
                            "train.init_params)",
    "train.result_drain": "controller: one pass over the workers' report "
                          "queues (its thread shares the GIL with the step "
                          "loop)",
    "train.h2d": "profiler: host->device batch transfer within a step",
    "train.collective": "profiler: gradient-sync rendezvous within a step",
    "train.ckpt_block": "profiler: device->host snapshot blocking a step",
    "train.elastic": "controller: elastic recovery, failure -> resumed",
    "train.stall": "watchdog: detected progress stall, last progress -> "
                   "detection (status ERROR)",
    "forensics.dump": "flight recorder: one postmortem dump, trigger -> "
                      "file written",
    "watchdog.tick": "hang watchdog: one detection pass on its thread",
    "xla.compile": "device telemetry: one executable built or loaded, from "
                   "the start of its trace to the executable (attrs: "
                   "label, trigger, trace_s, lower_s, compile_s, cache, "
                   "cache_load_s)",
    "xla.compile_storm": "device telemetry: recompile storm episode, first "
                         "windowed recompile -> detection (status ERROR)",
    "device.transfer": "device telemetry: one timed host<->device "
                       "transfer (attrs: direction, src, bytes)",
    "cluster.autoscale": "cluster autoscaler: one control tick, signal "
                         "collection -> reconcile",
}


#: Names the program gives its own work *inside* the compiled train step
#: (``jax.named_scope``): they reach the compiled module's ``op_name``
#: metadata and nothing else — no instruction changes.  ``TrainStep.
#: anatomy()`` (parallel/train_state.py) maps every instruction to the
#: innermost of these on its path, and the benchmark's ``step.*_ms`` metrics
#: sum device time by them.  Same static check as the spans: every
#: ``named_scope("x")`` under ``ray_tpu/`` names an entry, and no entry is
#: dead.
SCOPE_REGISTRY: Dict[str, str] = {
    "embed": "token (and position) embedding lookup",
    "attn": "attention part of a block: norm, qkv, RoPE, kernel, the "
            "output gate where the layer has one, out-projection",
    "attn_kernel": "the attention kernel call itself, nested inside attn "
                   "(splash/ring/ulysses/XLA, with its layout changes)",
    "latent": "latent-attention layer (models/mla.py), nested inside attn: "
              "the two down-projections, the latent norms, the two "
              "up-projections, the rotary passes, assembling k (all of the "
              "mixer but the pre-norm, the kernel and the out-projection)",
    "window": "window-attention layer (models/window.py), nested inside attn: "
              "all of the layer but the kernel call (the pre-norm, the "
              "projections at the window layers' head count, the rotary "
              "passes, the gate, the out-projection), so that a stack of "
              "full and window layers parts them",
    "mlp": "MLP part of a block: norm to down-projection (with experts: "
           "the norm, the weights' casts and the residual add around the "
           "three scopes below)",
    "router": "expert layer (models/moe.py), nested inside mlp: router "
              "logits, softmax, top-k, the two router losses",
    "moe_dispatch": "expert layer, nested inside mlp: sort of the (token, "
                    "slot) pairs, group sizes, the gathers into expert "
                    "order and back, the weighted sum",
    "experts": "expert layer, nested inside mlp: the grouped matmuls and "
               "the activation between them",
    "moe_held": "expert layer that holds a share of the experts, nested "
                "inside mlp in place of experts: the grouped matmuls over "
                "the held groups and the activation between them",
    "shared_expert": "expert layer with a shared expert (models/moe.py), "
                     "nested inside mlp: the dense MLP every token passes "
                     "(two matrices, three with a gate), and its sum with "
                     "the routed part",
    "ssm": "a Mamba-2 layer (models/mamba2.py): norm, in-projection, the "
           "gate, the grouped norm, out-projection (around the two scopes "
           "below)",
    "ssm_conv": "Mamba-2 layer, nested inside ssm: the causal depthwise "
                "convolution over positions, its bias and the silu",
    "ssm_scan": "Mamba-2 layer, nested inside ssm: the chunked state-space "
                "scan (ops/ssd.py), whatever implements it",
    "kda": "a KDA layer (models/kda.py), the gated delta rule with a "
           "per-channel decay: norm, projections, L2 norms, the decay's and "
           "the output's low-rank gates, beta, the head norm, "
           "out-projection (around the two scopes below)",
    "kda_conv": "KDA layer, nested inside kda: the three causal depthwise "
                "convolutions over positions and the silu",
    "kda_scan": "KDA layer, nested inside kda: the chunked delta-rule scan "
                "(ops/kda.py), whatever implements it",
    "shortconv": "a gated short-convolution layer (models/shortconv.py): "
                 "norm, in-projection, out-projection (around the scope "
                 "below)",
    "shortconv_gate": "gated short-convolution layer, nested inside "
                      "shortconv: the input gate, the causal depthwise taps "
                      "over positions and the output gate, one pass forward "
                      "and one backward, which makes the gate and the taps "
                      "again",
    "gdn": "a gated-delta-net layer (models/gdn.py), the gated delta rule "
           "with one decay a head: projections, L2 norms, the decay, beta, "
           "the head norm, the output gate, out-projection and the norm "
           "(around the two scopes below)",
    "gdn_conv": "gated-delta-net layer, nested inside gdn: the three causal "
                "depthwise convolutions over positions and the silu",
    "gdn_scan": "gated-delta-net layer, nested inside gdn: the chunked "
                "delta-rule scan (ops/gdn.py), whatever implements it",
    "noise": "block-diffusion training (models/block_diffusion.py): the "
             "draw of the masked positions, the noised copy, the "
             "concatenation with the clean one, the loss weights",
    "lm_head": "final norm, logits, loss (a looped stack's, "
               "models/looped.py: after every pass)",
    "exit_gate": "a looped stack's exit gate (models/looped.py): the gate's "
                 "product with each pass's normed state, the sigmoids, the "
                 "exit distribution, its entropy and the combination of the "
                 "passes' cross-entropies into the loss, all float32",
    "mtp": "multi-token-prediction module (models/hybrid.py): the norms of "
           "the next token's embedding and of the last layer's output, "
           "their join and its projection; the module's block opens its "
           "kinds' own scopes",
    "mtp_head": "multi-token-prediction module: its final norm and its pass "
                "through the shared head, logits and loss",
    "mhc": "a sub-layer's hyper-connections over a residual of several "
           "streams (models/streams.py), around the two scopes below; the "
           "sub-layer's branch runs between the read and the write under "
           "its kind's own scopes",
    "mhc_maps": "hyper-connections, nested inside mhc: the norm over every "
                "stream's lanes, the product with phi, the two sigmoids and "
                "the Sinkhorn turns that normalise the stream map; where "
                "the kernels run (ops/streams_kernel.py) what is left of "
                "them outside: phi's cast and transpose, alpha's expansion, "
                "the last sums of the parameters' cotangents",
    "mhc_mix": "hyper-connections, nested inside mhc: the read that mixes "
               "the streams into the branch's input and the write that "
               "mixes them among themselves and adds the branch's output "
               "to each: the model's residual add; where the kernels run "
               "their four passes, the maps made inside the read's",
    "optimizer": "optimizer.update + apply_updates (gradient clipping is "
                 "inside the optax chain, so inside the scope)",
}


#: Data-dependent facts that leave the compiled train step: name -> what it
#: counts.  A loss names one with :func:`step_counter` in the dict it returns
#: beside its scalar; ``parallel.train_state.make_train_step`` writes it to a
#: ``jax`` ref the step closes over (an aliased parameter of the compiled
#: program: no fourth output, no host callback), ``TrainStep`` reads the ref
#: after each dispatch, and the step profiler's resolver thread puts the
#: value into the step's row under the same name.  Same static check as the
#: spans and scopes: every ``step_counter("x")`` under ``ray_tpu/`` names an
#: entry, and no entry is dead.
STEP_COUNTER_REGISTRY: Dict[str, str] = {
    "moe_rows": "expert layers (models/moe.py): the (position, expert) "
                "pairs that reached each expert held here, int32 (layers, "
                "batch shards, held experts); the grouped kernels' time "
                "follows them",
    "moe_moved": "expert layers that hold a share of the experts: the rows "
                 "each layer moved into expert order and back, int32 "
                 "(layers, batch shards): moe.window_rows for each window "
                 "of the held run that the step's own count made it walk "
                 "(every pair a layer sorts when the run is that long)",
    "loss_main": "a decoder with a multi-token-prediction module "
                 "(models/hybrid.py): the next-token cross-entropy, float32 "
                 "scalar, one of the two terms of the step's loss",
    "loss_mtp": "the same decoder: the module's cross-entropy of the token "
                "two ahead, mean over the positions that have one, float32 "
                "scalar; the step's loss adds mtp_weight times it",
    "mhc_sinkhorn_err": "a residual of several streams (models/streams.py): "
                        "the largest distance from 1 of a row or column sum "
                        "of a sub-layer's normalised stream map over the "
                        "step's positions, float32 (sub-layers,), a "
                        "prediction module's last: whether the turns reach "
                        "the manifold at the logits training drives them to",
    "loss_ut": "a looped stack (models/looped.py): the mean cross-entropy "
               "of each pass's head, float32 (ut_steps,); the step's loss "
               "weighs them by the exit distribution",
    "ut_exit_mass": "the same stack: the mean over the step's positions of "
                    "the probability of leaving at each pass, float32 "
                    "(ut_steps,), summing to one; an entry near 1 means the "
                    "gate has collapsed onto one pass and the other heads "
                    "train on nothing",
}


def step_counter(name: str) -> str:
    """``name``, held to :data:`STEP_COUNTER_REGISTRY`: the key under which
    a loss hands a counter out of the compiled step."""
    if name not in STEP_COUNTER_REGISTRY:
        raise KeyError(f"step counter {name!r} is not declared in "
                       "tracing.STEP_COUNTER_REGISTRY")
    return name


def _new_id64() -> str:
    return f"{_ID_BASE ^ (next(_id_counter) * _GOLDEN & _MASK64):016x}"


def _new_trace_id() -> str:
    return _new_id64() + f"{_ID_BASE:016x}"


def is_tracing_enabled() -> bool:
    """(ref: tracing_helper.py:92)."""
    return _enabled


def enable_tracing(exporter: Optional[Callable[[dict], None]] = None) -> None:
    global _enabled, _exporter
    _enabled = True
    _exporter = exporter


def disable_tracing() -> None:
    global _enabled, _exporter
    _enabled = False
    _exporter = None


def exported_spans() -> List[dict]:
    """Spans captured by the default in-memory exporter."""
    # deque.append is atomic, so the hot path exports lock-free; snapshots
    # just retry the rare "mutated during iteration" race.
    for _ in range(100):
        try:
            return list(_buffer)
        except RuntimeError:
            continue
    return list(_buffer)


def clear_spans() -> None:
    with _buffer_lock:
        _buffer.clear()


#: Passive span tap (flight recorder): sees every span the exporter sees,
#: including ones that outlive their tracing session — the recorder is a
#: black box, not a tracing consumer.  One global load + None check on the
#: hot path when no tap is installed.
_tap: Optional[Callable[[dict], None]] = None


def set_span_tap(fn: Optional[Callable[[dict], None]]) -> None:
    """Install (or clear with None) the passive span tap.  The tap must be
    cheap and must never raise — it runs inline on every span export."""
    global _tap
    _tap = fn


def _export(span: dict) -> None:
    if _tap is not None:
        _tap(span)
    if not _enabled:
        return  # span outlived its tracing session (e.g. a parked long-poll)
    if _exporter is not None:
        _exporter(span)
    else:
        _buffer.append(span)


def current_context() -> Optional[dict]:
    """{"trace_id", "span_id"} of the active span, for propagation."""
    span = _current_span.get()
    if span is None:
        return None
    return {"trace_id": span["trace_id"], "span_id": span["span_id"]}


def active_span() -> Optional[dict]:
    """The active span dict itself (or None) — zero-allocation alternative
    to current_context() for in-process consumers (histogram exemplars,
    batch-span parents).  Treat it as read-only; its trace_id/span_id stay
    valid after the span closes, but cross-process propagation must use
    current_context() (the span dict carries arbitrary attribute objects)."""
    return _current_span.get()


class _NullSpan:
    """Context manager returned when tracing is off — zero per-use cost."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, et, ev, tb):
        return False


_NULL_SPAN = _NullSpan()


#: ``jax.profiler.TraceAnnotation`` once jax is in the process (probed,
#: never imported: tracing must not pull jax into a process without it).
_annotation: Optional[Callable[[str], Any]] = None


def _find_annotation() -> Optional[Callable[[str], Any]]:
    global _annotation
    _annotation = getattr(sys.modules.get("jax.profiler"),
                          "TraceAnnotation", None)
    return _annotation


def annotate(name: str):
    """``span()``'s sibling for the profiler's clock alone: a
    ``jax.profiler.TraceAnnotation`` (jax's own no-op while no profile is
    open; a shared no-op before jax is in the process) and nothing for the
    exporter.  For per-step and per-tick sites whose host-clock story the
    step profiler already tells after the fact (``train.step`` and its
    children) or that would only flood the span buffer: ``train.dispatch``,
    ``train.report``, ``train.data_wait``, the pump, drain and watchdog
    threads."""
    ann = _annotation or _find_annotation()
    return _NULL_SPAN if ann is None else ann(name)


class _ProfilerSpan:
    """``span()`` with tracing off and jax loaded: the profiler annotation
    under the disabled span's contract (``with ... as s`` gives None)."""

    __slots__ = ("_ann",)

    def __init__(self, ann):
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        return None

    def __exit__(self, et, ev, tb):
        self._ann.__exit__(et, ev, tb)
        return False


class _SpanCtx:
    """Class-based span context manager: ~2x cheaper to enter/exit than a
    generator @contextmanager, which matters at several spans per request."""

    __slots__ = ("_s", "_token", "_ann")

    def __init__(self, s: dict, ann=None):
        self._s = s
        self._ann = ann

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self._token = _current_span.set(self._s)
        return self._s

    def __exit__(self, et, ev, tb):
        s = self._s
        if et is not None:
            s["status"] = f"ERROR: {et.__name__}"
        s["end"] = _now()
        _current_span.reset(self._token)
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        _export(s)
        return False


_now = time.time


def span(name: str, parent: Optional[dict] = None,
         attributes: Optional[Dict[str, Any]] = None):
    """Open a span; nests under the active span unless `parent` is given.

    The span takes ownership of `attributes` — callers must not mutate the
    dict afterwards (hot path: no defensive copy).  With tracing off the
    span is still :func:`annotate`'s profiler annotation."""
    ann = _annotation or _find_annotation()
    if not _enabled:
        return _NULL_SPAN if ann is None else _ProfilerSpan(ann(name))
    if parent is None:
        # The active span dict itself carries trace_id/span_id — no need to
        # build the {"trace_id", "span_id"} projection on the hot path.
        parent = _current_span.get()
    if parent is not None:
        trace_id = parent.get("trace_id") or _new_trace_id()
        parent_id = parent.get("span_id")
    else:
        trace_id = _new_trace_id()
        parent_id = None
    s = {
        "name": name,
        "trace_id": trace_id,
        "span_id": _new_id64(),
        "parent_id": parent_id,
        "start": _now(),
        "end": None,
        "attributes": attributes if attributes is not None else {},
        "status": "OK",
    }
    return _SpanCtx(s, None if ann is None else ann(name))


def record_span(name: str, start: float, end: float, *,
                trace_id: Optional[str] = None,
                parent: Optional[dict] = None,
                attributes: Optional[Dict[str, Any]] = None,
                status: str = "OK") -> Optional[dict]:
    """Export a retroactively-timed span (e.g. queue wait measured after the
    fact from an enqueue timestamp). Returns the span dict, or None when
    tracing is off.

    Takes ownership of `attributes` (no defensive copy); passing one shared
    dict for a whole batch of spans is fine as long as nobody mutates it."""
    if not _enabled:
        return None
    if parent is None:
        parent = _current_span.get()
    if parent is not None:
        tid = trace_id or parent.get("trace_id") or _new_trace_id()
        parent_id = parent.get("span_id")
    else:
        tid = trace_id or _new_trace_id()
        parent_id = None
    s = {
        "name": name,
        "trace_id": tid,
        "span_id": _new_id64(),
        "parent_id": parent_id,
        "start": start,
        "end": end,
        "attributes": attributes if attributes is not None else {},
        "status": status,
    }
    _export(s)
    return s


def record_span_batch(name: str, intervals, *,
                      attributes: Optional[Dict[str, Any]] = None) -> None:
    """Export one retroactive span per (start, end, parent_ctx) interval in
    a single tight loop — the serve batching layer attributes queue-wait
    and execute spans to every request of a micro-batch this way, keeping
    per-item call overhead off the replica event loop.  Intervals with a
    None parent are skipped (request wasn't traced); all spans share the
    `attributes` dict (callers must not mutate it afterwards)."""
    if not _enabled:
        return
    attrs = attributes if attributes is not None else {}
    emit = _exporter if _exporter is not None else _buffer.append
    tap = _tap
    for start, end, parent in intervals:
        if parent is None:
            continue
        s = {
            "name": name,
            "trace_id": parent.get("trace_id") or _new_trace_id(),
            "span_id": _new_id64(),
            "parent_id": parent.get("span_id"),
            "start": start,
            "end": end,
            "attributes": attrs,
            "status": "OK",
        }
        if tap is not None:
            tap(s)
        emit(s)


def inject_task_spec(spec) -> None:
    """Called at submit time: stamp the submitter's context onto the spec."""
    if _enabled:
        spec.trace_ctx = current_context()


def task_execute_span(spec):
    """Execute-side span parented on the submit-side context in the spec
    (the reference wraps the worker's task execution the same way)."""
    if not _enabled:
        return _NULL_SPAN
    # task_id is a str subclass — store it directly, no str() copy.
    return span(f"task::{spec.name}",
                parent=getattr(spec, "trace_ctx", None),
                attributes={"task_id": spec.task_id,
                            "attempt": spec.attempt})
