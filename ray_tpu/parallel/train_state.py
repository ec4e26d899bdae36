"""Sharded train-state assembly: params + optimizer state on a mesh.

The ZeRO/FSDP equivalent of the reference's Train stack (ref: train/torch/
train_loop_utils.py prepare_model DDP/FSDP wrap) with no wrapper at all:
parameters are placed with their logical shardings, optimizer state is
*computed from them under jit* so XLA propagates the same shardings onto the
Adam moments (optimizer sharding = ZeRO), and the train step is jitted with
donated state.  Gradient synchronization is derived by the partitioner, with
one exception since PR 32: under an `fsdp` axis the weight gradients of the
Llama layer's seven projections are reduced by a hand-written ring of chunk
products and ``ppermute``s (``ops/grad_ring.py``), because the partitioner's
reduce-scatter of them runs alone on the TPU and the ring's sends run behind
matmuls.  Every other collective of the step is the partitioner's.

The jitted step is a :class:`TrainStep`: the same ``jax.jit`` dispatch, which
also names its host side (``train.dispatch`` on the profiler's clock, the
``dispatch`` counter of the step profiler's row), labels what it compiles in
the device-telemetry compile registry (``train_step``), and can say which
phase and model part every instruction of its compiled program belongs to
(:meth:`TrainStep.anatomy`), from the ``jax.named_scope`` names the models
put into the step (``tracing.SCOPE_REGISTRY``).
"""

from __future__ import annotations

import collections
import contextlib
import functools
import re
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import grad_ring, remat
from ray_tpu.parallel.mesh import pytree_sharding
from ray_tpu.util import device_telemetry, tracing


def create_sharded_state(
    init_fn: Callable[[Any], Any],
    logical: Any,
    mesh,
    key,
    optimizer=None,
    rules: Optional[Dict] = None,
) -> Tuple[Any, Any]:
    """Initialize params directly into their sharded layout (no host round
    trip: init runs under jit with out_shardings so each device materializes
    only its shard) and build the optimizer state beside them: every
    sub-tree of it shaped like the parameters (Adam's moments) is born under
    the parameters' shardings, every other leaf (the counts) replicated.
    optax makes the moments from zeros, which carry no sharding for the
    compiler to propagate; left to it they come out whole on every chip."""
    shardings = pytree_sharding(logical, mesh, rules)
    device_telemetry.listen_for_compiles()
    with jax.set_mesh(mesh):
        with tracing.span("train.init_params"), \
                device_telemetry.compile_label("init_params"):
            params = jax.jit(init_fn, out_shardings=shardings)(key)
        opt_state = None
        if optimizer is not None:
            with tracing.span("train.init_opt_state"), \
                    device_telemetry.compile_label("init_opt_state"):
                # On one device there is nothing to say, and the executable
                # stays the one it was.
                init = jax.jit(optimizer.init) if mesh.size == 1 else \
                    jax.jit(optimizer.init, out_shardings=_state_shardings(
                        optimizer, params, shardings, mesh))
                opt_state = init(params)
    return params, opt_state


def _state_shardings(optimizer, params, shardings, mesh):
    """Shardings for ``optimizer.init(params)``: ``shardings`` (the
    parameters') for every sub-tree with their structure and shapes, a
    replicated one for every other leaf."""
    like = jax.tree.structure(params)
    shapes = [p.shape for p in jax.tree.leaves(params)]

    def mirrors(node) -> bool:
        return jax.tree.structure(node) == like and \
            [leaf.shape for leaf in jax.tree.leaves(node)] == shapes

    replicated = jax.sharding.NamedSharding(mesh,
                                            jax.sharding.PartitionSpec())
    return jax.tree.map(
        lambda node: shardings if mirrors(node) else replicated,
        jax.eval_shape(optimizer.init, params), is_leaf=mirrors)


def make_optimizer(learning_rate=3e-4, weight_decay=0.1, b1=0.9, b2=0.95,
                   grad_clip=1.0):
    """AdamW behind a global-norm clip, the decoders' optimizer.  The first
    moment is stored in bf16: the momentum is noise-tolerant (unlike nu,
    which stays fp32) and halving its HBM read+write is worth ~+0.8 MFU on
    v5e (r5 sweep on GPT-2 124M: 47.5 -> 48.2; 13-step loss 9.562 vs
    9.565)."""
    import optax

    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(learning_rate, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=jnp.bfloat16),
    )


def make_train_step(loss_fn, optimizer):
    """Pure (params, opt_state, tokens, targets) -> (params, opt_state, loss)
    for a decoder's ``loss_fn(params, tokens, targets)``.

    Under jit with sharded inputs this is the whole distributed step: XLA
    derives the gradient psum/reduce-scatter from the shardings (the DDP
    allreduce of the reference's _TorchBackend lives inside the compiled
    program here).  The one gradient sync written by hand sits below this
    function, in the model: the Llama layer's weight gradients under `fsdp`
    (``ops/grad_ring.py``).  The update runs
    under the ``optimizer`` scope, which :func:`classify_op_name` reads as
    the step's ``update`` phase.
    """
    import optax

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        with jax.named_scope("optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


_thread = threading.local()  # .notes: the dicts of the calls traced in here


def note_first_call(**facts) -> None:
    """From inside a traced step: ``facts`` about the model being traced,
    for the ``train.first_call`` span and first-call record of whichever
    :class:`TrainStep` call is tracing on this thread (``models/llama.py``:
    the experts held, the block length, how many positions the layers and
    the loss see; ``ops/attention.py``: the splash calls that cover a layer's
    mask, their blocks and grid steps).  Outside such a call, nothing."""
    for notes in getattr(_thread, "notes", ()):
        notes.update(facts)


@contextlib.contextmanager
def _noting():
    notes: Dict[str, Any] = {}
    stack = _thread.__dict__.setdefault("notes", [])
    stack.append(notes)
    try:
        yield notes
    finally:
        stack.pop()


def jit_train_step(step_fn, donate_state: bool = True, mesh=None):
    """jit with donated (params, opt_state) so updates reuse their buffers —
    the HBM discipline that makes big models fit.  Returns a
    :class:`TrainStep`.

    Pass ``mesh`` whenever the state lives on more than one device: the
    Pallas attention kernel (splash, what attn_impl="auto" means on TPU) and
    the context-parallel paths (attn_impl="ring"/"ulysses") shard_map over
    the AMBIENT mesh, which the step installs around trace/execute via
    jax.set_mesh.  Without it jax refuses to lower the kernel for several
    devices.
    """
    return TrainStep(step_fn, donate_state=donate_state, mesh=mesh)


class TrainStep:
    """A jitted train step that names its own work.

    Calling it is ``jax.jit``'s dispatch (not the AOT path: the step's
    outputs come back under other sharding specs than its inputs, which an
    executable keyed on shardings would take for a new program).  Around
    the dispatch: the ``train.dispatch`` annotation, the ``dispatch``
    counter of the calling worker's step-profiler row, and a compile label,
    so that an executable jax builds or loads in here is recorded under
    ``train_step`` with a trigger classified from the call's signature.
    The signature is taken only when a compile event fired, before the
    arguments are donated; the first one is kept for :meth:`anatomy`.
    A call that compiled is a ``train.first_call`` span and a first-call
    record of the registry; both say what the model's layers keep for the
    backward there (``remat_kept``, ``remat_kept_bytes``, ``remat_room_bytes``:
    ``ops/remat.py`` decides it while the step is traced) and how many weight
    gradients were traced as rings over `fsdp` (``grad_ring_products``,
    ``grad_ring_axis``: ``ops/grad_ring.py``; 0 and 0 on one chip), and what
    the traced code said of itself through :func:`note_first_call`
    (``models/llama.py``: ``experts_held``, ``experts_total``,
    ``block_length``, ``attn_positions``, ``loss_positions``;
    ``ops/attention.py``, where the splash kernel runs, how its calls cover a
    layer's mask, a head: ``attn_calls``, ``attn_blocks``,
    ``attn_blocks_cut``, ``attn_grid_steps_fwd``, ``attn_grid_steps_bwd``).

    A step that was traced in this call, keeps more than the plain policy
    would and is refused for memory (``RESOURCE_EXHAUSTED``, at compile or
    at its first execution) is rebuilt once under the plain policy, with a
    warning, the ``ray_tpu_train_remat_fallback_total`` counter and
    ``remat_fallback`` on the span.  A step of several processes is rebuilt
    only on the compiler's refusal, which all of them get alike.  A later
    call that fails, fails.
    """

    label = "train_step"

    def __init__(self, step_fn, donate_state: bool = True, mesh=None):
        self._donation = (0, 1) if donate_state else ()
        self._step_fn = step_fn
        self._jitted = jax.jit(step_fn, donate_argnums=self._donation)
        #: the ambient mesh around trace, lower and execute
        self._in_mesh = (contextlib.nullcontext if mesh is None
                         else lambda: jax.set_mesh(mesh))
        #: (args, kwargs) of the first call that compiled, as
        #: ShapeDtypeStructs with shardings
        self._abstract: Optional[Tuple[Any, Any]] = None
        self._anatomy: Optional[Dict[str, Tuple[Optional[str],
                                                Optional[str]]]] = None
        device_telemetry.listen_for_compiles()
        device_telemetry.register_program(self.label, self)

    def __call__(self, *args, **kwargs):
        t0 = time.perf_counter()
        label = device_telemetry.compile_label(
            self.label, lambda: self._sign(args, kwargs))
        fell_back = False
        with tracing.annotate("train.dispatch"), label, self._in_mesh(), \
                remat.recording() as decided, \
                grad_ring.recording() as rings, _noting() as notes:
            try:
                out = self._jitted(*args, **kwargs)
            except RuntimeError as e:  # XLA's; re-raised unless remat's
                if not self._refused_for_remat(e, decided, args, kwargs):
                    raise
                remat.fall_back(decided[-1], str(e).splitlines()[0][:200])
                fell_back = True
                # a new function object: jax keeps the refused program's
                # trace under the old one and would hand it back
                self._jitted = jax.jit(functools.partial(self._step_fn),
                                       donate_argnums=self._donation)
                out = self._jitted(*args, **kwargs)
        seconds = time.perf_counter() - t0
        profiler = sys.modules.get("ray_tpu.train.profiler")
        if profiler is not None:
            profiler.count("dispatch", seconds)
        if label.compiles:
            end = time.time()
            attributes = dict(decided[-1].attributes() if decided else {},
                              remat_fallback=fell_back, **rings.attributes(),
                              **notes)
            device_telemetry.record_first_call(self.label, seconds, ts=end,
                                               **attributes)
            tracing.record_span(
                "train.first_call", end - seconds, end,
                attributes={"label": self.label,
                            "compile_s": label.compile_s, **attributes})
        return out

    def _refused_for_remat(self, error, decided, args, kwargs) -> bool:
        """Whether ``error`` is the memory's refusal of a program that this
        call traced with more kept than the plain policy keeps (``decided``:
        what the rule said while it traced), and the (donated) arguments are
        still whole for a second try.  A step of several processes is rebuilt
        only if the compiler refused it, which every process sees alike (the
        compile is asked once more, alone, to tell): a process that ran out
        of memory on its own while its peers launched the program cannot
        leave them, and the error stands."""
        if not ("RESOURCE_EXHAUSTED" in str(error)
                and decided and decided[-1].kept
                and not any(leaf.is_deleted()
                            for leaf in jax.tree.leaves((args, kwargs))
                            if isinstance(leaf, jax.Array))):
            return False
        if decided[-1].processes == 1:
            return True
        try:
            self._jitted.lower(*args, **kwargs).compile()
        except RuntimeError as again:
            return "RESOURCE_EXHAUSTED" in str(again)
        return False

    def _sign(self, args, kwargs):
        """(shapes, shardings, donation) of a call, for the compile
        registry; runs inside the compile event, so the arguments are
        still whole."""
        abstract = jax.tree.map(_abstract_leaf, (args, kwargs))
        if self._abstract is None:
            self._abstract = abstract
        leaves, treedef = jax.tree.flatten(abstract)
        shapes = tuple((leaf.shape, leaf.dtype) if hasattr(leaf, "shape")
                       else type(leaf).__name__ for leaf in leaves)
        shardings = tuple(getattr(leaf, "sharding", None) for leaf in leaves)
        return (shapes, treedef), shardings, self._donation

    def anatomy(self) -> Dict[str, Tuple[Optional[str], Optional[str]]]:
        """``{instruction name: (phase, part)}`` of the compiled step, for
        the signature of the first call that compiled.

        On demand and cached: lowers and compiles that signature again —
        jax hands back the first call's executable where it still holds it,
        else it is a persistent-cache load — and reads the compiled
        module's text with :func:`parse_anatomy`.  The instruction names
        are the ones a device trace's ``XLA Ops`` events carry.  The scope
        names are those of the tree that *compiled* the executable: a
        persistent cache keyed without HLO metadata (jax's default) can
        hand back another tree's, which is why
        ``configure_compile_cache()`` puts the metadata into the key."""
        if self._anatomy is None:
            if self._abstract is None:
                raise RuntimeError(
                    "TrainStep.anatomy() needs a call that compiled first")
            args, kwargs = self._abstract
            with device_telemetry.compile_label(self.label + ".anatomy"), \
                    self._in_mesh():
                text = self._jitted.lower(*args, **kwargs).compile() \
                    .as_text()
            self._anatomy = parse_anatomy(text)
        return self._anatomy


def _abstract_leaf(x):
    if not hasattr(x, "shape") or not hasattr(x, "dtype"):
        return x  # a python scalar: jit traces it, lower() takes it as is
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=getattr(x, "sharding", None))


# ------------------------------------------------------------------ anatomy
# Read off the compiled v5e step of both model families (compile only,
# ``benchmarks/tools/compile_only.py --hlo``; a sample is kept in
# tests/data/v5e_step_op_names.json).  jax spells the transforms into the
# path: ``jit(step)/jvp(lm_head)/...`` and, inside the layer scan,
# ``jit(step)/jvp()/while/body/closed_call/attn/...`` are the forward;
# ``jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/...`` the
# backward; ``.../checkpoint/rematted_computation/attn/...`` remat's second
# forward inside it; ``jit(step)/optimizer/mul`` the update.  A
# ``jax.named_scope`` lands inside the transform's brackets outside the scan
# and as a path element of its own inside it.  (``.../attn_kernel/transpose``
# ends in the *primitive* transpose: only ``transpose(jvp(`` is the
# transform.)

PHASES = ("forward", "backward", "recompute", "update")
_PATH_TOKEN = re.compile(r"[^/()]+")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? ([a-z\-]+)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REFERENCE = re.compile(r"%([\w.\-]+)")


@dataclass
class _Instruction:
    name: str
    opcode: str
    op_name: str = ""
    calls: Optional[str] = None  # the computation a fusion fuses
    refers_to: List[str] = field(default_factory=list)  # %names in its text


def classify_op_name(op_name: str
                     ) -> Tuple[Optional[str], Optional[str]]:
    """(phase, part) of one ``op_name``: the phase from jax's transform
    path, the part the innermost registered scope on the path; ``None``
    where the string has neither."""
    tokens = _PATH_TOKEN.findall(op_name)
    part = next((t for t in reversed(tokens)
                 if t in tracing.SCOPE_REGISTRY), None)
    if "optimizer" in tokens:
        phase = "update"
    elif "rematted_computation" in tokens:
        phase = "recompute"
    elif "transpose(jvp(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = None
    return phase, part


def parse_anatomy(hlo_text: str
                  ) -> Dict[str, Tuple[Optional[str], Optional[str]]]:
    """Every instruction of a compiled module's text ->
    ``classify_op_name`` of its own ``op_name``.  A fusion that lacks a
    phase or a part there (the compiler gives one the metadata of its root,
    often a slice update or a layout change around the matmul that is the
    work) takes the commonest among the instructions it fuses, nested
    fusions included.  A fusion that spans two parts counts to one.  What
    the compiler emits with no metadata at all (async copies and slices
    into fast memory, layout copies, converts) is data movement for
    whoever reads it: it takes the commonest (phase, part) of the
    instructions that use its result, through a few hops
    (``copy-start`` -> ``copy-done`` -> the kernel)."""
    computations: Dict[str, List[_Instruction]] = {}
    current = None
    for line in hlo_text.splitlines():
        header = _COMPUTATION.match(line)
        if header:
            current = computations.setdefault(header.group(1), [])
            continue
        found = _INSTRUCTION.match(line)
        if found and current is not None:
            current.append(_Instruction(found.group(1), found.group(2)))
        elif line.rstrip() == "}":
            current = None
        if current:  # an instruction's text may run over several lines
            last = current[-1]
            op_name, calls = _OP_NAME.search(line), _CALLS.search(line)
            if op_name and not last.op_name:
                last.op_name = op_name.group(1)
            if calls and last.calls is None:
                last.calls = calls.group(1)
            last.refers_to += _REFERENCE.findall(line)

    def fused_votes(name, seen):
        votes = collections.Counter()
        if name in seen:
            return votes
        seen.add(name)
        for i in computations.get(name, ()):
            if i.opcode == "fusion" and i.calls:
                votes.update(fused_votes(i.calls, seen))
            elif i.op_name:
                votes[classify_op_name(i.op_name)] += 1
        return votes

    out = {}
    for rows in computations.values():
        for i in rows:
            phase, part = classify_op_name(i.op_name)
            if i.opcode == "fusion" and i.calls and (phase is None
                                                     or part is None):
                votes = fused_votes(i.calls, set())
                if phase is None:
                    phase = _commonest(votes, 0)
                if part is None:
                    part = _commonest(votes, 1)
            out[i.name] = (phase, part)
    nameless = (None, None)
    for rows in computations.values():
        users: Dict[str, List[str]] = {}
        for i in rows:
            for operand in i.refers_to:
                if operand != i.name:
                    users.setdefault(operand, []).append(i.name)
        for _ in range(4):  # hops
            found = {}
            for i in rows:
                if out[i.name] == nameless and i.opcode != "parameter":
                    votes = collections.Counter(
                        out[u] for u in users.get(i.name, ())
                        if out[u] != nameless)
                    if votes:
                        found[i.name] = votes.most_common(1)[0][0]
            if not found:
                break
            out.update(found)
    return out


def _commonest(votes, index: int) -> Optional[str]:
    tally = collections.Counter()
    for key, n in votes.items():
        if key[index] is not None:
            tally[key[index]] += n
    return tally.most_common(1)[0][0] if tally else None
