"""What the scanned layer's ``jax.checkpoint`` keeps for the backward, chosen
when the step is traced from what the chip has room for.

A decoder's layer scan (``models/llama.py``) runs under ``jax.checkpoint``:
the layer's input is kept, the splash kernel's output and log-sum-exp are kept
(``ops.attention.SPLASH_RESIDUALS``), what an expert layer's router decided is
kept (:data:`ROUTING`: a few MB a layer, which a full-precision product, a
``top_k`` and three sorts would make again), and the rest of the layer is run
a second time in the backward.  Where the chip has memory to spare that second
run is 10-14 % of the step paid for bytes nobody uses.  The models name the
arrays worth keeping (``jax.ad_checkpoint.checkpoint_name``) and say, from
shapes, what each takes a layer and what forward work keeping it spares
(:class:`Rung`); :func:`choose` climbs the rungs in the order of spared work
a byte and keeps each for as many of the layers that name it as fit.  A
scanned stack (``models/llama.py``) is one layer to the rule, all or nothing,
and its two rungs cost the same a byte, so their order is :data:`LADDER`'s; a
stack that runs unrolled (``models/hybrid.py``) wraps each layer in a
``jax.checkpoint`` of its own and asks the decision for each layer's policy
(:meth:`Decision.policy`).  There is no option: the answer follows from sizes
the code can observe.

**What it reads, and when.**  :func:`device_memory` asks the process's local
devices for ``memory_stats()`` while the loss is being *traced*, and takes the
fullest chip's ``bytes_limit`` and ``bytes_in_use``.  At that moment the
train state (and, in a benchmark's second lowering of the same step, the same
state) is resident, so the difference is what the step's own temporaries may
take.  Everything else is shapes: the candidates' bytes a chip and a bound on
what the program needs without them (:func:`own_temporaries`).  A backend
without memory statistics (the CPU; a compile for a described topology)
answers ``None`` and the policy is the plain one, no rung kept.

**One program on every host.**  A step over a mesh of several processes is
one SPMD program, and each process traces it for itself: were each to decide
from its own chips, a host that holds more (an evaluation program, a
checkpoint's staging) would build another step than its peers, with other
collectives, and the job would hang.  So where the ambient mesh is larger
than the process's own devices, the processes hand each other what their
chips report (:func:`every_process`, through ``jax.distributed``'s key-value
store: host side, nothing is launched on a device while it traces) and all
decide from the fullest chip of the job.  Every process has to trace such a
step, as it has to run it; one that waits for its peers longer than the
collectives' timeout fails with an error that says so.  With no ambient mesh
a multi-process job keeps the plain policy, because nothing at trace time
says whose program it is.

**Why an estimate and not the compiler's verdict.**  Building the richest
program and stepping down when the compiler refuses it would need no bound,
but the verdict is the wrong one: the compiler holds a program's buffers
against the whole chip, not against what other programs and arrays have left
of it, and a program it accepts can still be refused when it runs, which a
job of several processes does not survive (below).  And it costs a compile a
rung in exactly the jobs that fill their chips: 34 s for the two-layer
Mistral step against 20 s of whole set-up (PERF.md, PR 31).

**Stability.**  The same function may be traced twice in a process (the train
step, then a tool that lowers it again for its text, when the first
program's batches and losses are resident too); both must get the same
program, and a rule that keeps layer by layer has margins of tens of MB, not
hundreds.  So :func:`decide` remembers what it answered under the question
it hashes for the peers (the rungs, the bound, the mesh's shape) and answers
a second trace of the same step in the same process from that memory,
whatever ``bytes_in_use`` reads by then; every process of a job remembers
alike, so a second trace asks no peer.  :func:`fall_back` pins the plain
policy for the rest of the process once a richer program was refused, over
any remembered answer; the caller (``TrainStep``) does that across processes
only for a refusal by the compiler, which every process gets alike.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import logging
import math
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu.collective.dcn_group import kv_client, multiprocess_world
from ray_tpu.ops.attention import SPLASH_RESIDUALS
from ray_tpu.util import first_call, metrics

logger = logging.getLogger(__name__)

#: q, k and v as they enter ``causal_attention``: after QK-norm and RoPE, in
#: the compute dtype, K and V at their own head count.
QKV = "attn_qkv"
#: The MLP's two products ``h @ w_gate`` and ``h @ w_up`` in the compute dtype
#: (with experts: the two grouped matmuls' outputs).
GATE_UP = "mlp_gate_up"
#: The order of the scanned stack's two rungs.  Both families cost the same
#: FLOPs per byte kept (``d_model`` a byte); q, k and v go first because they
#: spare RoPE's passes besides and are a fifth of the bytes.
LADDER = (QKV, GATE_UP)
#: A Mamba-2 layer's ``in_proj`` output, ``[z | xBC | dt]`` as the
#: convolution and the gate read it (``models/mamba2.py``).
SSM_IN = "ssm_in_proj"
#: The projections a short convolution reads: q, k and v of kinds ``K`` and
#: ``G`` before their taps, ``[B | C | u]`` of kind ``C``.
CONV_IN = "conv_in_proj"
#: The inverse of a gated-delta-net chunk's unit-triangular system
#: (``ops/gdn.py``): ``T``, and on the kernels' path the float32 ``X`` it is
#: a scaled copy of, which the backward reads too.
INVERSE = "scan_inverse"
#: A latent-attention layer's two normed latents and its rotary key
#: (``models/mla.py``).
LATENTS = "mla_latents"
#: The stream maps of a sub-layer under hyper-connections
#: (``models/streams.py``): the read's and the write's weights and the
#: normalised stream map, float32, n^2 + 2n numbers a position.  No rung of
#: :data:`LADDER`, which the one-stream models share: a model with streams
#: offers it in front of the ladder (a hundredth of q, k and v's bytes, and
#: it spares the norm over every stream, a product and the Sinkhorn turns).
MAPS = "mhc_maps"
#: What an expert layer's router decided (``models/moe.py``): its logits, the
#: chosen experts and their weights, the pairs' sorted order with its inverse
#: and group sizes, the weights in that order: (N, E), (N, k) and (N x k,)
#: arrays of four bytes.  No rung of the ladder: always kept, as the splash
#: residuals are, so a step routes once and its backward reads that routing.
ROUTING = "moe_routing"

#: Share of the chip's ``bytes_limit`` the rule leaves alone: a tenth, as a
#: policy.  It is not there for the estimate's error: :func:`own_temporaries`
#: came out over the compiler's own figure in every program it was held
#: against, and what the runtime reserves is the compiler's figure to 0.1 GiB
#: (PERF.md, PR 31).  It is for what the rule cannot see: the step is not the
#: chip's only tenant (prefetched batches, an evaluation or a check between
#: steps, a checkpoint's staging), and an allocator needs slack.
RESERVE_SHARE = 0.10

REMAT_FALLBACKS = metrics.Counter(
    "ray_tpu_train_remat_fallback_total",
    "Train steps whose program, built to keep more of the layer for the "
    "backward, was refused for memory and rebuilt under the plain remat "
    "policy (ops/remat.py).")


#: FLOPs a v5e's matrix unit does while its HBM moves one byte (197 TFLOP/s
#: over 819 GB/s): what makes a memory-bound pass's bytes comparable with a
#: product's FLOPs where rungs are ordered.  It orders; it sizes nothing.
FLOPS_A_BYTE = 240


def spared(flops: float = 0.0, moved: float = 0.0) -> float:
    """The forward work that keeping an array spares, in FLOPs: the
    products' own, and for a pass bound by memory the bytes it moves at
    :data:`FLOPS_A_BYTE`."""
    return flops + FLOPS_A_BYTE * moved


@dataclasses.dataclass(frozen=True)
class Rung:
    """What some layers name under one name: a chip's ``nbytes`` of it a
    layer, the forward work keeping one layer's spares (:func:`spared`),
    how many ``layers`` name it and whose they are (``group``: a kind's
    letter in ``models/hybrid.py``; a scanned stack is one layer of no
    group, its bytes all its layers')."""
    name: str
    nbytes: int
    spares: float = 0.0
    layers: int = 1
    group: str = ""

    @property
    def a_byte(self) -> float:
        return self.spares / max(self.nbytes, 1)


@dataclasses.dataclass(frozen=True)
class Decision:
    """One answer of the rule.  ``kept``: (group, name, the layers of the
    group that keep it, the layers that name it) for every rung of which a
    layer is kept, in the order they were taken (the splash residuals and
    :data:`ROUTING` are always kept and not listed); ``kept_bytes``: their
    bytes a chip; ``room_bytes``: what the rule saw free for them, after the
    program's own temporaries, the routing and the reserve (``None``: the
    device reports no memory); ``processes``: how many processes took it
    together (1: this process's own program); ``routing_bytes``: what the
    layers' routing takes, a chip, all layers (0: no layer routes)."""
    kept: Tuple[Tuple[str, str, int, int], ...] = ()
    kept_bytes: int = 0
    room_bytes: Optional[int] = None
    processes: int = 1
    routing_bytes: int = 0

    @property
    def names(self) -> Tuple[str, ...]:
        """The names of which any layer keeps some, each once."""
        return tuple(dict.fromkeys(name for _, name, _, _ in self.kept))

    def layers(self) -> List[Tuple[str, int, int]]:
        """(name, layers that keep it, layers that name it), a name once:
        its groups' counts summed over the groups the rule reached."""
        counts: Dict[str, List[int]] = {}
        for _, name, kept, of in self.kept:
            row = counts.setdefault(name, [0, 0])
            row[0] += kept
            row[1] += of
        return [(name, *row) for name, row in counts.items()]

    def attributes(self) -> Dict[str, object]:
        """What :func:`decide` notes of it (``util/first_call.py``)."""
        return {"remat_kept": [list(row) for row in self.layers()],
                "remat_kept_bytes": self.kept_bytes,
                "remat_room_bytes": self.room_bytes,
                "remat_routing_bytes": self.routing_bytes}

    def policy(self, *layers: Tuple[str, int]):
        """The checkpoint policy of one layer: ``save_only_these_names(splash
        residuals, the routing, *what this layer keeps)``.  ``layers``: the
        (group, index within the group) it is, more than one where a layer
        bears two groups' names (a kind's branch under the stream maps); a
        rung kept for k layers is kept by the group's first k.  None given:
        a scanned stack, every layer."""
        layers = layers or (("", 0),)
        return _policy(tuple(dict.fromkeys(
            name for group, name, kept, _ in self.kept
            if any(group == g and index < kept for g, index in layers))))


@functools.lru_cache(maxsize=None)
def _policy(names: Tuple[str, ...]):
    """One policy object a set of names: layers that keep alike trace
    alike, and jax shares what it traced of them."""
    return jax.checkpoint_policies.save_only_these_names(
        SPLASH_RESIDUALS, ROUTING, *names)


def _rungs(candidates: Sequence) -> List[Rung]:
    return [c if isinstance(c, Rung) else Rung(*c) for c in candidates]


def choose(limit: int, in_use: int, candidates: Sequence, temporaries: int
           ) -> Decision:
    """The rule, pure.  ``limit``, ``in_use``: the fullest chip's bytes;
    ``candidates``: :class:`Rung`s, or (name, bytes a chip over all layers)
    for a stack that keeps all layers or none; ``temporaries``: the bound on
    what the program needs without them.  The rungs are climbed in the order
    of spared work a byte (equal ones in the order given, which is the
    scanned stack's :data:`LADDER`); each is kept for as many of its layers
    as fit beside every layer kept before, the temporaries and the reserve;
    the first that is not kept whole ends the climb."""
    room = limit - in_use - temporaries - int(RESERVE_SHARE * limit)
    rungs = sorted(_rungs(candidates), key=lambda r: -r.a_byte)
    kept, kept_bytes = [], 0
    for rung in rungs:
        layers = min(rung.layers, max(room - kept_bytes, 0)
                     // max(rung.nbytes, 1))
        if layers:
            kept.append((rung.group, rung.name, layers, rung.layers))
            kept_bytes += layers * rung.nbytes
        if layers < rung.layers:
            break
    return Decision(tuple(kept), kept_bytes, room)


def fullest(reports: Sequence[Optional[Tuple[int, int]]]
            ) -> Optional[Tuple[int, int]]:
    """Of several chips' (``bytes_limit``, ``bytes_in_use``) the one the
    rule has to hold for all: the smallest limit with the most in use;
    ``None`` if any of them reports nothing."""
    if not reports or any(r is None for r in reports):
        return None
    return min(r[0] for r in reports), max(r[1] for r in reports)


def device_memory() -> Optional[Tuple[int, int]]:
    """(``bytes_limit``, ``bytes_in_use``) of the fullest local device, or
    ``None`` where a device reports no memory statistics."""
    rows = [d.memory_stats() for d in jax.local_devices()]
    if not all(r and r.get("bytes_limit") for r in rows):
        return None
    return fullest([(int(r["bytes_limit"]), int(r.get("bytes_in_use", 0)))
                    for r in rows])


def axis_shards(mesh, *names: str) -> int:
    """How many ways the (abstract) ambient mesh cuts an array along the
    given mesh axes; 1 for no mesh and for axes it does not have."""
    if mesh.empty:
        return 1
    return math.prod(mesh.shape.get(a, 1) for a in names)


def rows_under_mesh(tokens: int, seq_len: int):
    """(the ambient mesh, the rows of ``seq_len`` positions a step holds
    under it where a chip has ``tokens``): what an op's ``path`` asks of a
    kind that sizes its layer for the rule."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh, tokens * axis_shards(mesh, "data", "fsdp", "seq") // seq_len


def own_temporaries(*, block_bytes: int, other_bytes: int, layer_bytes: int,
                    sharded: bool, tokens: int, d_model: int, n_layer: int,
                    attn_width: int, n_head: int, mlp_width: int, vocab: int,
                    itemsize: int, logits_itemsize: int,
                    passes: int = 1) -> int:
    """A bound, from shapes, on the temporaries of the step as it is without
    any rung of the ladder; every size a chip's.  ``block_bytes`` and
    ``other_bytes`` are the chip's share of the stacked layers' parameters
    and of the rest (embedding, head) as float32, ``layer_bytes`` one whole
    layer's as float32.  The larger of two moments of the step:

    - inside the backward scan: the layers' gradients, which accumulate in
      float32 stacks; the layers' and the rest's weights in the compute
      dtype (the compiler hoists the casts of whole stacks; the head's
      gradient waits there in that dtype); what the forward scan stacked, a
      layer: its input, the kernel's output and its log-sum-exp; one layer's
      working set, six arrays of (tokens, mlp_width) and six of (tokens,
      attn_width) in the compute dtype (the products, their activation and
      the cotangents, as the v5e's buffer assignment holds them); and, where
      the parameters are cut over chips, two layers' gathered weights in
      flight;
    - around the head: the logits and their cotangent, the same casts and
      stacks, and the rest's gradients in float32.

    ``passes``: how often the step runs the stack (a looped model's
    ``ut_steps``).  Every pass stacks its own layers' inputs and kernel
    outputs, so what the scans stack is ``passes`` times a pass's, and the
    backward scan holds :func:`_looped`'s besides.

    Held against the v5e compiler's buffer assignment for the benchmark's
    cells it reads 4.88 GiB for 4.56 and 4.50 (Mistral-7B at two layers),
    6.19 for 6.00 (twelve layers under ``fsdp=4``) and 3.72 for 2.39 (OLMoE
    at one layer, whose scan of one has no stacks) (PERF.md, PR 31).
    """
    cast = itemsize / 4
    stacked = passes * n_layer * tokens * (
        d_model * itemsize + attn_width * itemsize + n_head * 4)
    casts = int((block_bytes + other_bytes) * cast)
    working = 6 * tokens * (mlp_width + attn_width) * itemsize
    gathered = int(2 * layer_bytes * cast) if sharded else 0
    in_the_scan = block_bytes + casts + stacked + working + gathered
    at_the_head = other_bytes + casts + stacked \
        + 2 * tokens * vocab * logits_itemsize
    if passes > 1:
        in_the_scan += _looped(block_bytes, stacked // passes, passes,
                               tokens * d_model, itemsize)
    return max(in_the_scan, at_the_head)


def _looped(block_bytes: int, a_pass: int, passes: int, state: int,
            itemsize: int) -> int:
    """What a stack that is run ``passes`` times over (``models/looped.py``:
    an outer scan over the passes around the layers' scan) holds inside the
    backward scan beyond :func:`own_temporaries`' one pass: a second set of
    the layers' float32 gradient stacks (the passes' running sum beside the
    stacks the pass at hand fills); ``a_pass``, one pass's share of what the
    forward stacked, sliced out of the passes' stack for the layers' scan;
    and of each pass's normed ``state`` (tokens x d_model) the state and its
    cotangent in the compute dtype and the final norm's float32 input and
    cotangent.  The head's term is untouched: the head runs a pass at a
    time and holds one pass's logits and their cotangent.  Held against the
    v5e's buffer assignment for Ouro-2.6B at eight layers, four passes and
    8192 tokens it reads 8.18 GiB for 7.97 (PERF.md, PR 65)."""
    return block_bytes + a_pass + passes * state * (2 * itemsize + 2 * 4)


_lock = threading.Lock()
_plain_only = False  # guarded_by: _lock
#: how often each question was put to the peers (:func:`every_process`)
_asked: Dict[str, int] = {}  # guarded_by: _lock
#: what :func:`decide` answered each question from a device's report
_decided: Dict[str, "Decision"] = {}  # guarded_by: _lock


def every_process(report: Optional[Tuple[int, int]], question: str
                  ) -> List[Optional[Tuple[int, int]]]:
    """This process's ``report`` and every peer's, in process order, through
    ``jax.distributed``'s key-value store.  ``question`` names what is being
    decided (the same on every process that traces the same step); the n-th
    time a process asks it, it meets the peers' n-th.  A peer that does not
    ask within ``GLOBAL_CONFIG.collective_timeout_s`` fails the trace."""
    with _lock:
        nth = _asked[question] = _asked.get(question, 0) + 1
    client, me = kv_client(), jax.process_index()
    prefix = f"ray_tpu/remat/{question}/{nth}"
    client.key_value_set(f"{prefix}/{me}",
                         "" if report is None else "%d,%d" % report)
    timeout_ms = int(GLOBAL_CONFIG.collective_timeout_s * 1000)
    reports = []
    for peer in range(multiprocess_world()):
        try:
            said = client.blocking_key_value_get(f"{prefix}/{peer}",
                                                 timeout_ms)
        except jax.errors.JaxRuntimeError as e:  # DEADLINE_EXCEEDED
            raise RuntimeError(
                f"remat: process {peer} did not trace this step within "
                f"{timeout_ms / 1000:.0f} s of process {me}.  A step over a "
                "mesh of several processes is one program: every process "
                "has to trace it, and agree with the others on what its "
                "layers keep for the backward.") from e
        reports.append(tuple(map(int, said.split(","))) if said else None)
    return reports


def tracing_processes(mesh) -> int:
    """How many processes trace a step under ``mesh`` as one program: this
    one alone unless the job has several and the mesh is larger than the
    process's own devices (or there is none to say whose program it is)."""
    world = multiprocess_world()
    if world <= 1 or (not mesh.empty
                      and mesh.size <= jax.local_device_count()):
        return 1
    return world


def _job_memory(mesh, question: str
                ) -> Tuple[Optional[Tuple[int, int]], int]:
    """(the fullest chip of every process that will run the step being
    traced under ``mesh``, how many processes that is)."""
    memory, processes = device_memory(), tracing_processes(mesh)
    if processes == 1:
        return memory, 1
    if mesh.empty:  # whose program this is, nothing here can tell
        return None, processes
    return fullest(every_process(memory, question)), processes


def decide(candidates: Sequence, temporaries: int) -> Decision:
    """:func:`choose` for layers whose named intermediates are
    ``candidates`` (:func:`choose`'s, and, where a layer routes, (``ROUTING``,
    its bytes a chip over all layers), which is kept whatever the answer and
    counts among what the step needs), in a step that needs ``temporaries``
    without them, from what the devices report (those of every process
    under a mesh that spans several).  A question answered once from a
    device's report is answered from memory after that (the module's
    Stability paragraph); :func:`fall_back` overrides both.  Notes the
    decision for the first-call record."""
    rungs = _rungs(candidates)
    routing = sum(r.nbytes * r.layers for r in rungs if r.name == ROUTING)
    candidates = tuple(r for r in rungs if r.name != ROUTING)
    temporaries += routing
    mesh = jax.sharding.get_abstract_mesh()
    question = hashlib.sha1(repr(
        (candidates, temporaries, tuple(mesh.shape.items()))
    ).encode()).hexdigest()[:16]
    with _lock:
        plain_only, decision = _plain_only, _decided.get(question)
    memory = None
    if plain_only:
        decision = Decision(routing_bytes=routing)
    elif decision is None:
        memory, processes = _job_memory(mesh, question)
        decision = dataclasses.replace(
            Decision() if memory is None
            else choose(*memory, candidates, temporaries),
            processes=processes, routing_bytes=routing)
        if memory is not None:
            # two traces of one question at once: the first answer stands
            # for both.  The lock is not held while the peers are asked
            # (``every_process`` takes it, and a peer may take minutes).
            with _lock:
                decision = _decided.setdefault(  # analysis: ignore[atomicity] setdefault: the first answer wins; the lock cannot span the peers' exchange
                    question, decision)
    first_call.note(**decision.attributes())
    logger.info("remat: keeping %s for the backward (%d bytes a chip; room "
                "%s; device memory %s; %d process(es)) beside %d bytes of "
                "routing", decision.layers() or "nothing more",
                decision.kept_bytes, decision.room_bytes,
                memory or "as first read", decision.processes, routing)
    return decision


def layer_policy(candidates: Sequence, temporaries: int):
    """The one checkpoint policy of a scanned stack's layers:
    :meth:`Decision.policy` of what :func:`decide` keeps."""
    return decide(candidates, temporaries).policy()


def fall_back(kept: Sequence, reason: str) -> None:
    """A program that kept ``kept`` (the record's ``remat_kept``) was refused
    for memory: from here on this process gets the plain policy, whatever it
    remembers, so a second trace of the same step builds what the first
    ended up running."""
    global _plain_only
    with _lock:
        _plain_only = True
    REMAT_FALLBACKS.inc()
    logger.warning(
        "remat: the step that kept %s for the backward was refused for "
        "memory (%s); rebuilding it under the plain policy, which this "
        "process keeps from here on",
        ", ".join("%s (%d of %d layers)" % tuple(row) for row in kept),
        reason)
