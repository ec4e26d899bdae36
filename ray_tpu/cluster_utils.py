"""Multi-node cluster harness for tests
(ref: python/ray/cluster_utils.py — Cluster:135, add_node:202, remove_node:286).

Two modes:

* **virtual** (default): nodes are scheduler entries; scheduling semantics
  (spread, affinity, placement groups, spillback) are exercised for real
  while execution stays in this process — the single-box multi-node trick
  the reference's test suite is built on.
* **real=True**: each node is a separate OS process (`python -m ray_tpu
  worker --address=...`) that JOINS this process's head over the node
  manager and RECEIVES dispatched tasks/actors, with results riding the
  object plane — the reference's `Cluster(add_node)` spawning raylet
  processes (ref: node_manager.h:117).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Dict, Optional

import ray_tpu
from ray_tpu._private.ids import NodeID
from ray_tpu._private.runtime import get_runtime


def worker_node_cmd(address: str, num_cpus: float,
                    resources: Optional[Dict[str, float]] = None,
                    labels: Optional[Dict[str, str]] = None,
                    node_id: Optional[str] = None) -> list:
    """Command line for a worker-node process joining ``address`` (shared
    by the test harness and node providers, so a new worker flag cannot
    silently drift between them)."""
    import json

    cmd = [sys.executable, "-m", "ray_tpu", "worker",
           "--address", address,
           "--num-cpus", str(num_cpus),
           "--resources", json.dumps(resources or {})]
    if node_id:
        cmd += ["--node-id", str(node_id)]
    if labels:
        cmd += ["--labels"] + [f"{k}={v}" for k, v in labels.items()]
    return cmd


def worker_node_env() -> Dict[str, str]:
    """Environment for a spawned worker-node process on THIS host.

    Forces CPU jax (the driver owns this host's chips; a second process
    that asks for them fails), scrubs the driver host's TPU slice
    description (node processes simulate OTHER hosts), and guarantees this
    ray_tpu checkout is importable."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    for key in list(env):
        if key.startswith("TPU_") or key == "PJRT_LIBRARY_PATH":
            del env[key]
    # Node processes must import THIS ray_tpu even when the driver got it
    # via sys.path (dev checkout driven from a scratch cwd).
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(
        ray_tpu.__file__)))
    existing = env.get("PYTHONPATH", "")
    if pkg_root not in existing.split(os.pathsep):
        env["PYTHONPATH"] = (pkg_root + os.pathsep + existing).rstrip(
            os.pathsep)
    return env


class Cluster:
    def __init__(self, initialize_head: bool = False,
                 head_node_args: Optional[dict] = None,
                 real: bool = False):
        self.real = real
        self.head_node_id: Optional[NodeID] = None
        self._nodes: Dict[NodeID, dict] = {}
        self._procs: Dict[NodeID, subprocess.Popen] = {}
        self.node_address: str = ""
        if initialize_head:
            args = dict(head_node_args or {})
            runtime = ray_tpu.init(ignore_reinit_error=True, **args)
            self.head_node_id = runtime.head_node_id
            self._nodes[self.head_node_id] = args
        if real:
            self.node_address = get_runtime().start_node_server()

    def add_node(self, num_cpus: float = 1, num_tpus: float = 0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 wait: bool = True) -> NodeID:
        runtime = get_runtime()
        node_resources = {"CPU": float(num_cpus)}
        if num_tpus:
            node_resources["TPU"] = float(num_tpus)
        node_resources.update(resources or {})
        if not self.real:
            node_id = runtime.scheduler.add_node(node_resources, labels)
            self._nodes[node_id] = node_resources
            return node_id

        if not self.node_address:
            self.node_address = runtime.start_node_server()
        node_id = NodeID.from_random()
        cmd = worker_node_cmd(
            self.node_address, num_cpus,
            {k: v for k, v in node_resources.items() if k != "CPU"},
            labels, str(node_id))
        env = worker_node_env()
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)
        self._procs[node_id] = proc
        self._nodes[node_id] = node_resources
        if wait:
            self.wait_for_node(node_id)
        return node_id

    def wait_for_node(self, node_id: NodeID, timeout: float = 60.0) -> None:
        """Block until the node registered with the head's scheduler."""
        runtime = get_runtime()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            node = runtime.scheduler.get_node(node_id)
            if node is not None and node.alive:
                return
            proc = self._procs.get(node_id)
            if proc is not None and proc.poll() is not None:
                out, err = proc.communicate()
                raise RuntimeError(
                    f"worker node {node_id} exited rc={proc.returncode}:\n"
                    f"{out}\n{err}")
            time.sleep(0.05)
        raise TimeoutError(f"node {node_id} did not join within {timeout}s")

    def remove_node(self, node_id: NodeID, allow_graceful: bool = True) -> None:
        proc = self._procs.pop(node_id, None)
        if proc is not None:
            # Real node: kill the OS process; the head notices the dropped
            # connection and runs node-death recovery (the point of the
            # chaos tests).
            proc.kill()
            proc.wait(timeout=30)
        else:
            get_runtime().scheduler.remove_node(node_id)
        self._nodes.pop(node_id, None)

    def shutdown(self) -> None:
        for node_id in list(self._procs):
            self.remove_node(node_id)
        ray_tpu.shutdown()
        self._nodes.clear()
