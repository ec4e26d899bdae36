"""What the chip bring-up changed, checked on CPU: accelerator detection
without a preloaded jax, the placeable compile cache, the peaks table, no
silent attention fallback, process workers kept off the chip, the splash
kernel divided over the mesh, and chip_smoke.py's device check and loop."""

import dataclasses
import os
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ------------------------------------------------------ accelerator detection
def _fake_devices(platform, kind, n):
    return [types.SimpleNamespace(platform=platform, device_kind=kind, id=i)
            for i in range(n)]


@pytest.fixture
def no_jax_no_tpu_env(monkeypatch):
    """jax not imported, nothing said about platforms or a TPU slice."""
    monkeypatch.delitem(sys.modules, "jax")
    for key in list(os.environ):
        if key.startswith("TPU_") or key == "JAX_PLATFORMS":
            monkeypatch.delenv(key)


def test_detect_asks_jax_when_nothing_preloaded_it(no_jax_no_tpu_env,
                                                   monkeypatch):
    from ray_tpu._private import accelerators

    monkeypatch.setattr(accelerators, "_jax_devices",
                        lambda: _fake_devices("tpu", "TPU v5 lite", 4))
    resources, labels = accelerators.detect_accelerators()
    assert resources == {"TPU": 4.0}
    assert labels == {"accelerator-type": "tpu-v5-lite"}


def test_detect_registers_no_tpu_for_cpu_devices(no_jax_no_tpu_env,
                                                 monkeypatch):
    from ray_tpu._private import accelerators

    monkeypatch.setattr(accelerators, "_jax_devices",
                        lambda: _fake_devices("cpu", "cpu", 8))
    assert accelerators.detect_accelerators() == ({}, {})


def test_detect_counts_devices_not_slice_variables(no_jax_no_tpu_env,
                                                   monkeypatch):
    """A one-chip machine cut from a v5litepod-4 host exports the host's
    bounds; the count is what jax sees."""
    from ray_tpu._private import accelerators

    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    monkeypatch.setattr(accelerators, "_jax_devices",
                        lambda: _fake_devices("tpu", "TPU v5 lite", 1))
    resources, _ = accelerators.detect_accelerators()
    assert resources == {"TPU": 1.0, "TPU-v5litepod-4-head": 1.0}


def test_detect_does_not_swallow_a_backend_error(no_jax_no_tpu_env,
                                                 monkeypatch):
    from ray_tpu._private import accelerators

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(accelerators, "_jax_devices", boom)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        accelerators.detect_accelerators()


def test_detect_skips_jax_when_told_cpu(no_jax_no_tpu_env, monkeypatch):
    from ray_tpu._private import accelerators

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setattr(accelerators, "_jax_devices",
                        lambda: pytest.fail("asked jax for devices"))
    assert accelerators.detect_accelerators() == ({}, {})


# ------------------------------------------------------------------ peaks
def test_peaks_table_raises_on_unknown_device_kind():
    from ray_tpu._private.accelerators import device_peaks

    assert device_peaks("TPU v5 lite").flops == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("TPU v99")
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")


# ------------------------------------------------------------ compile cache
def _start_cache_probe(env_dir):
    """A fresh process that configures the cache twice and prints both."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    return subprocess.Popen(
        [sys.executable, "-c",
         "from ray_tpu.parallel.compile_cache import configure_compile_cache"
         " as c; print(c()); print(c())"],
        env=env, cwd=REPO, stdout=subprocess.PIPE, text=True)


def test_compile_cache_env_var_wins_and_default_is_fixed(tmp_path):
    from ray_tpu.parallel.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    placed = str(tmp_path / "placed")
    probes = [_start_cache_probe(d) for d in (placed, None, None)]
    outs = [p.communicate(timeout=120)[0].split() for p in probes]
    assert all(p.returncode == 0 for p in probes)
    # Set: jax's own reading of the variable stands.  Unset: the one
    # in-checkout path, the same across calls and across processes.
    assert outs == [[placed] * 2, [DEFAULT_CACHE_DIR] * 2,
                    [DEFAULT_CACHE_DIR] * 2]


# ------------------------------------------------------- attention dispatch
def test_auto_attention_does_not_swallow_a_kernel_error(monkeypatch):
    from ray_tpu.ops import attention

    def refused(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attention, "splash_attention", refused)
    q = jnp.zeros((1, 128, 2, 64), jnp.bfloat16)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        attention.causal_attention(q, q, q, "auto")
    with pytest.raises(ValueError, match="Unknown attn_impl"):
        attention.causal_attention(q, q, q, "pallas")


def test_splash_divides_over_the_ambient_mesh():
    """Under a data x tensor mesh the kernel runs in a shard_map over the
    batch and head axes and still matches the XLA path, forward and grads."""
    from ray_tpu.ops.attention import causal_attention, splash_attention
    from ray_tpu.parallel import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=2, tensor=2), jax.devices()[:4])
    B, S, H, hd = 4, 128, 4, 64
    q, k, v = (jax.random.normal(key, (B, S, H, hd), jnp.float32)
               for key in jax.random.split(jax.random.key(0), 3))
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(("data", "fsdp"), None, "tensor"))

    def with_grads(fn):
        def run(*args):
            out, vjp = jax.vjp(fn, *args)
            return (out, *vjp(out))
        return run

    want = jax.jit(with_grads(
        lambda q, k, v: causal_attention(q, k, v, "xla")))(q, k, v)
    split = jax.jit(with_grads(splash_attention))
    with jax.set_mesh(mesh):
        args = [jax.device_put(x, sharding) for x in (q, k, v)]
        assert "sdy.manual_computation" in split.lower(*args).as_text()
        got = split(*args)
    for g, w in zip(got, want):
        assert float(jnp.max(jnp.abs(g - w))) < 1e-4 * float(jnp.max(jnp.abs(w)))


def test_splash_lowers_for_the_tpu_only_inside_the_mesh_shard_map(
        monkeypatch):
    """What the four-chip run hit: jax refuses to lower a Mosaic call the
    SPMD partitioner would have to split.  Lowering for the TPU needs no
    TPU, so the refusal and the repair are both checked here."""
    from ray_tpu.ops.attention import splash_attention
    from ray_tpu.parallel import MeshSpec, batch_sharding, make_mesh

    mesh = make_mesh(MeshSpec(data=4), jax.devices()[:4])
    q = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16,
                             sharding=batch_sharding(mesh))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def lower():
        return jax.jit(splash_attention).trace(q, q, q).lower(
            lowering_platforms=("tpu",))

    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        lower()
    with jax.set_mesh(mesh):
        assert "tpu_custom_call" in lower().as_text()


def test_splash_remat_policy_keeps_the_named_residuals_alone():
    """``save_splash_residuals`` marks the two arrays the kernel names and
    nothing else: not a kernel call (the splash forward's own log-sum-exp
    output is 128 lanes wide; ring attention and the fused loss run kernels
    too), not another name, not a matmul."""
    from jax.ad_checkpoint import checkpoint_name
    from jax.experimental import pallas as pl

    from ray_tpu.ops.attention import SPLASH_RESIDUALS, save_splash_residuals

    def copy(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def body(x):
        for kernel in ("splash_mha_fwd_residuals", "ring_attention_step"):
            x = pl.pallas_call(copy, out_shape=x, name=kernel,
                               interpret=True)(x)
        x = checkpoint_name(x @ x, "attn_out")
        return checkpoint_name(x, SPLASH_RESIDUALS)

    eqns = jax.make_jaxpr(body)(jnp.ones((8, 8))).eqns
    saveable = [
        (eqn.primitive.name, eqn.params.get("name"))
        for eqn in eqns
        if save_splash_residuals(eqn.primitive,
                                 *(v.aval for v in eqn.invars), **eqn.params)]
    assert [eqn.primitive.name for eqn in eqns] == [
        "pallas_call", "pallas_call", "dot_general", "name", "name"]
    assert saveable == [("name", SPLASH_RESIDUALS)]


# ------------------------------------------------------------ process pool
def _worker_platform():
    return os.environ.get("JAX_PLATFORMS")


def test_pool_workers_stay_on_cpu_whatever_the_parent_exports(
        monkeypatch, ray_start_regular):
    import ray_tpu

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # what a TPU host exports
    task = ray_tpu.remote(isolation="process")(_worker_platform)
    assert ray_tpu.get(task.remote(), timeout=120) == "cpu"


# -------------------------------------------------------------- chip_smoke
def test_chip_smoke_refuses_to_run_without_a_chip():
    """In seconds, non-zero, naming what jax found, printing no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    out = subprocess.run([sys.executable, "chip_smoke.py"], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert time.time() - t0 < 30
    assert "needs a TPU" in out.stderr and "'cpu'" in out.stderr
    assert out.stdout == ""


def test_chip_smoke_train_phase_at_tiny_size():
    """The loop chip_smoke.py runs on the chip, on the virtual CPU devices:
    the same code with another config and without the device checks."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    import ray_tpu
    from ray_tpu.models import gpt2

    n = len(jax.local_devices())
    config = dataclasses.replace(
        gpt2.GPTConfig.tiny(), remat_policy="attn_outside", scan_layers=False)
    ray_tpu.init(num_cpus=4, num_tpus=n, ignore_reinit_error=True)
    try:
        summary, hlo = chip_smoke.train_phase(
            config, seqs_per_chip=2, peak_flops_per_chip=1e12,
            watch=chip_smoke.CompileWatch())
    finally:
        ray_tpu.shutdown()
    assert summary["n_chips"] == n and summary["global_batch"] == 2 * n
    assert summary["window_compiles"] == 0
    assert summary["losses"][-1] < summary["losses"][0] - chip_smoke.MIN_LOSS_FALL
    assert summary["all_reduce"] >= 1 and "all-reduce" in hlo
    # Off the chip the device checks are what fails, and they do.
    with pytest.raises(RuntimeError, match="memory_stats"):
        chip_smoke.check_train_on_chip(summary, config, 2)
