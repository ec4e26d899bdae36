"""What the scanned layer's ``jax.checkpoint`` keeps for the backward, chosen
when the step is traced from what the chip has room for.

A decoder's layer scan (``models/llama.py``) runs under ``jax.checkpoint``:
the layer's input is kept, the splash kernel's output and log-sum-exp are kept
(``ops.attention.SPLASH_RESIDUALS``), what an expert layer's router decided is
kept (:data:`ROUTING`: a few MB a layer, which a full-precision product, a
``top_k`` and three sorts would make again), and the rest of the layer is run
a second time in the backward.  Where the chip has memory to spare that second
run is 10-14 % of the step paid for bytes nobody uses.  The models name the
arrays worth keeping (``jax.ad_checkpoint.checkpoint_name``), in the order of
:data:`LADDER`; :func:`layer_policy` keeps as many rungs as fit and hands back
the checkpoint policy.  There is no option: the answer follows from sizes the
code can observe.

**What it reads, and when.**  :func:`device_memory` asks the process's local
devices for ``memory_stats()`` while the loss is being *traced*, and takes the
fullest chip's ``bytes_limit`` and ``bytes_in_use``.  At that moment the
train state (and, in a benchmark's second lowering of the same step, the same
state) is resident, so the difference is what the step's own temporaries may
take.  Everything else is shapes: the candidates' bytes a chip and a bound on
what the program needs without them (:func:`own_temporaries`).  A backend
without memory statistics (the CPU; a compile for a described topology)
answers ``None`` and the policy is the plain one, :data:`LADDER` unused.

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
step, then a tool that lowers it again for its text); both must get the same
program.  The rule's margins are hundreds of MB; a batch or a loss more or
less in ``bytes_in_use`` does not move it.  :func:`fall_back` pins the plain
policy for the rest of the process once a richer program was refused; the
caller (``TrainStep``) does that across processes only for a refusal by the
compiler, which every process gets alike.
"""

from __future__ import annotations

import dataclasses
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
#: The order in which names are kept.  Both families cost the same FLOPs per
#: byte kept (``d_model`` a byte); q, k and v go first because they spare
#: RoPE's passes besides and are a fifth of the bytes.
LADDER = (QKV, GATE_UP)
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


@dataclasses.dataclass(frozen=True)
class Decision:
    """One answer of the rule.  ``kept``: the rungs of :data:`LADDER` kept
    (the splash residuals and :data:`ROUTING` are always kept and not
    listed); ``kept_bytes``: their bytes a chip, all layers; ``room_bytes``:
    what the rule saw free for them, after the program's own temporaries,
    the routing and the reserve (``None``: the device reports no memory);
    ``processes``: how many processes took it together (1: this process's
    own program); ``routing_bytes``: what the layers' routing takes, a chip,
    all layers (0: no layer routes)."""
    kept: Tuple[str, ...] = ()
    kept_bytes: int = 0
    room_bytes: Optional[int] = None
    processes: int = 1
    routing_bytes: int = 0

    def attributes(self) -> Dict[str, object]:
        """What :func:`decide` notes of it (``util/first_call.py``)."""
        return {"remat_kept": list(self.kept),
                "remat_kept_bytes": self.kept_bytes,
                "remat_room_bytes": self.room_bytes,
                "remat_routing_bytes": self.routing_bytes}


def choose(limit: int, in_use: int, candidates: Sequence[Tuple[str, int]],
           temporaries: int) -> Decision:
    """The rule, pure.  ``limit``, ``in_use``: the fullest chip's bytes;
    ``candidates``: (name, bytes a chip over all layers) in ladder order;
    ``temporaries``: the bound on what the program needs without them.  A
    rung is kept only if it, every rung before it, the temporaries and the
    reserve stay inside the limit; the first that does not fit ends the
    climb."""
    room = limit - in_use - temporaries - int(RESERVE_SHARE * limit)
    kept, kept_bytes = [], 0
    for name, nbytes in candidates:
        if kept_bytes + nbytes > room:
            break
        kept.append(name)
        kept_bytes += nbytes
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


def own_temporaries(*, block_bytes: int, other_bytes: int, layer_bytes: int,
                    sharded: bool, tokens: int, d_model: int, n_layer: int,
                    attn_width: int, n_head: int, mlp_width: int, vocab: int,
                    itemsize: int, logits_itemsize: int) -> int:
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

    Held against the v5e compiler's buffer assignment for the benchmark's
    cells it reads 4.88 GiB for 4.56 and 4.50 (Mistral-7B at two layers),
    6.19 for 6.00 (twelve layers under ``fsdp=4``) and 3.72 for 2.39 (OLMoE
    at one layer, whose scan of one has no stacks) (PERF.md, PR 31).
    """
    cast = itemsize / 4
    stacked = n_layer * tokens * (d_model * itemsize + attn_width * itemsize
                                  + n_head * 4)
    casts = int((block_bytes + other_bytes) * cast)
    working = 6 * tokens * (mlp_width + attn_width) * itemsize
    gathered = int(2 * layer_bytes * cast) if sharded else 0
    in_the_scan = block_bytes + casts + stacked + working + gathered
    at_the_head = other_bytes + casts + stacked \
        + 2 * tokens * vocab * logits_itemsize
    return max(in_the_scan, at_the_head)


_lock = threading.Lock()
_plain_only = False  # guarded_by: _lock
#: how often each question was put to the peers (:func:`every_process`)
_asked: Dict[str, int] = {}  # guarded_by: _lock


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


def _job_memory(mesh, question_parts) -> Tuple[Optional[Tuple[int, int]], int]:
    """(the fullest chip of every process that will run the step being
    traced under ``mesh``, how many processes that is)."""
    memory, processes = device_memory(), tracing_processes(mesh)
    if processes == 1:
        return memory, 1
    if mesh.empty:  # whose program this is, nothing here can tell
        return None, processes
    question = hashlib.sha1(repr(question_parts).encode()).hexdigest()[:16]
    return fullest(every_process(memory, question)), processes


def decide(candidates: Sequence[Tuple[str, int]],
           temporaries: int) -> Decision:
    """:func:`choose` for a layer whose named intermediates would take
    ``candidates`` (name, bytes a chip over all layers: the ladder's rungs
    in its order and, where a layer routes, :data:`ROUTING`, which is kept
    whatever the answer and counts among what the step needs), in a step
    that needs ``temporaries`` without them, from what the devices report
    (those of every process under a mesh that spans several).  Notes the
    decision for the first-call record."""
    routing = dict(candidates).get(ROUTING, 0)
    candidates = [c for c in candidates if c[0] != ROUTING]
    temporaries += routing
    mesh = jax.sharding.get_abstract_mesh()
    with _lock:
        plain_only = _plain_only
    memory, processes = (None, 1) if plain_only else _job_memory(
        mesh, (tuple(candidates), temporaries, tuple(mesh.shape.items())))
    decision = dataclasses.replace(
        Decision() if memory is None
        else choose(*memory, candidates, temporaries),
        processes=processes, routing_bytes=routing)
    first_call.note(**decision.attributes())
    logger.info("remat: keeping %s for the backward (%d bytes a chip; room "
                "%s; device memory %s; %d process(es)) beside %d bytes of "
                "routing", decision.kept or "nothing more",
                decision.kept_bytes, decision.room_bytes, memory, processes,
                routing)
    return decision


def layer_policy(candidates: Sequence[Tuple[str, int]], temporaries: int):
    """The checkpoint policy of such a layer: ``save_only_these_names(splash
    residuals, the routing, *what decide keeps)``."""
    return jax.checkpoint_policies.save_only_these_names(
        SPLASH_RESIDUALS, ROUTING, *decide(candidates, temporaries).kept)


def fall_back(kept: Sequence[str], reason: str) -> None:
    """A program that kept ``kept`` was refused for memory: from here
    on this process gets the plain policy, so a second trace of the same
    step builds what the first ended up running."""
    global _plain_only
    with _lock:
        _plain_only = True
    REMAT_FALLBACKS.inc()
    logger.warning(
        "remat: the step that kept %s for the backward was refused for "
        "memory (%s); rebuilding it under the plain policy, which this "
        "process keeps from here on", ", ".join(kept), reason)
