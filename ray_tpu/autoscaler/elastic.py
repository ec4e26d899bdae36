"""Elastic-capacity signals + the simulated-preemption hook.

The training-side contract with the autoscaler (ROADMAP item 3): the
elastic Trainer needs exactly two things from the cluster layer —

* ``worker_capacity(bundle)`` — how many copies of a worker bundle the
  *live* cluster can host right now.  The trainer shrinks its world size
  to this after a preemption and grows back toward ``max_workers`` when
  the number recovers (checked every ``ElasticConfig.grow_check_period_s``).
  Capacity is computed against each node's TOTAL resources, not its
  instantaneous availability: between attempts the worker group's
  placement group is released, and a grow decision made against
  still-held resources would deadlock against the very group it is
  trying to replace.

* ``simulate_preemption(...)`` — the chaos hook that makes a TPU slice
  vanish the way real preemption does: every actor hosted on the victim
  node dies (``ActorDiedError`` surfaces to anyone awaiting their calls)
  and the node leaves the scheduler in the same stroke.  Real clusters
  get this for free from the cloud; tests, ``tests/chaos_utils.py`` and
  ``scripts/bench_elastic.py`` drive it through the ``preempt_node``
  fault point (ray_tpu._private.fault_injection).
"""

from __future__ import annotations

import logging
from typing import Dict, Optional

logger = logging.getLogger(__name__)

Resources = Dict[str, float]


def _bundle_fits(total: Resources, bundle: Resources) -> int:
    """How many copies of ``bundle`` fit in ``total`` (0 when any key is
    missing)."""
    copies = None
    for key, need in bundle.items():
        if need <= 0:
            continue
        have = total.get(key, 0.0)
        n = int(have / need + 1e-9)
        copies = n if copies is None else min(copies, n)
    return 0 if copies is None else copies


def worker_capacity(bundle: Resources,
                    exclude_nodes: Optional[set] = None) -> int:
    """Total copies of ``bundle`` the live cluster can host, summed over
    alive nodes (against node totals — see module docstring)."""
    from ray_tpu._private.runtime import get_runtime

    exclude = {str(n) for n in (exclude_nodes or ())}
    capacity = 0
    for node in get_runtime().scheduler.nodes():
        if not node.alive or str(node.id) in exclude:
            continue
        capacity += _bundle_fits(node.total, bundle)
    return capacity


def capacity_available(bundle: Resources, want: int) -> bool:
    """True when the live cluster can host ``want`` copies of ``bundle``
    — the trainer's grow-back signal."""
    return worker_capacity(bundle) >= want


def actors_on_node(node_id) -> list:
    """ActorIDs of live actors hosted on ``node_id`` (virtual-node model:
    in-process actors carry the scheduler node their lease landed on)."""
    from ray_tpu._private.runtime import get_runtime

    runtime = get_runtime()
    want = str(node_id)
    out = []
    for aid, state in list(runtime._actors.items()):
        if state.state != "ALIVE":
            continue
        hosted = state.remote_node or state.node_id
        if hosted is not None and str(hosted) == want:
            out.append(aid)
    return out


def pick_preemptible_node(exclude_head: bool = True) -> Optional[str]:
    """A live node a preemption could take (never the head by default);
    None when the cluster has no candidate."""
    from ray_tpu._private.runtime import get_runtime

    runtime = get_runtime()
    head = str(runtime.head_node_id)
    for node in runtime.scheduler.nodes():
        if node.alive and (not exclude_head or str(node.id) != head):
            return str(node.id)
    return None


def simulate_preemption(node_id: Optional[str] = None,
                        exclude_head: bool = True) -> Optional[str]:
    """Preempt one node: remove it from the scheduler, then kill every
    actor it hosted (no restart — a preempted slice does not come back as
    the same node).  Returns the preempted node id, or None when
    no candidate node exists (e.g. a single-head cluster with
    ``exclude_head``)."""
    from ray_tpu._private.ids import NodeID
    from ray_tpu._private.runtime import get_runtime

    runtime = get_runtime()
    if node_id is None:
        node_id = pick_preemptible_node(exclude_head=exclude_head)
        if node_id is None:
            return None
    victims = actors_on_node(node_id)
    # The node goes first: a controller that sees a dead worker and asks
    # worker_capacity() must not still count the node that took it (it
    # would "recover" at the old world size instead of shrinking; the
    # window was the 10-50 ms the kills take, against a 250 ms health poll).
    try:
        runtime.scheduler.remove_node(NodeID(str(node_id)))
    except Exception:
        pass
    for aid in victims:
        try:
            runtime.kill_actor(aid, no_restart=True)
        except Exception:  # already dying — the node removal still counts
            pass
    from ray_tpu.train import metrics as train_metrics

    train_metrics.PREEMPTIONS.inc()
    logger.warning("simulated preemption: node %s (%d actor(s) killed)",
                   node_id, len(victims))
    return str(node_id)
