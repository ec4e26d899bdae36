"""``ops/remat.py``: what the scanned layer keeps for the backward is chosen at
trace time from the device's free bytes.  The CPU reports no memory, so every
test that wants a richer program hands the rule a device of its own."""

import contextlib
import dataclasses
import os
import re
import socket
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals  # not re-exported in 0.9
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.models import hybrid, layers, llama, moe
from ray_tpu.ops import remat
from ray_tpu.ops.attention import SPLASH_RESIDUALS, save_splash_residuals
from ray_tpu.parallel import MeshSpec, make_mesh
from ray_tpu.parallel.train_state import (create_sharded_state,
                                          jit_train_step)
from ray_tpu.util import device_telemetry, first_call
from tests import families

GiB = 2 ** 30
#: a v5e chip's ``bytes_limit``
V5E = int(15.75 * GiB)
ROOMY = (1 << 50, 0)


@pytest.fixture(autouse=True)
def fresh_rule(monkeypatch):
    """No fallback pinned and nothing remembered by a test before."""
    monkeypatch.setattr(remat, "_plain_only", False)
    monkeypatch.setattr(remat, "_decided", {})


def on_device(monkeypatch, memory):
    """A process on a device that reports ``memory``: nothing decided yet."""
    monkeypatch.setattr(remat, "device_memory", lambda: memory)
    monkeypatch.setattr(remat, "_decided", {})


def _policy(config, B=2):
    """What ``forward_hidden`` asks for a batch of B rows, from shapes."""
    shapes = jax.eval_shape(lambda: llama.init_params(config,
                                                      jax.random.key(0)))
    return llama._layer_policy(shapes, (B, config.seq_len, config.d_model),
                               config)


def _decide(config, B=2):
    """The rule's decision for that batch."""
    shapes = jax.eval_shape(lambda: llama.init_params(config,
                                                      jax.random.key(0)))
    return remat.decide(*llama._layer_sizes(
        shapes, (B, config.seq_len, config.d_model), config))


MISTRAL = dict(vocab_size=32768, n_head=32, n_kv_head=8, d_model=4096,
               d_ff=14336)
OLMOE = dict(vocab_size=50304, n_head=16, n_kv_head=16, d_model=2048,
             d_ff=1024, n_experts=64, experts_per_token=8, qk_norm=True)
SDAR = dict(vocab_size=18992, n_head=32, n_kv_head=4, d_model=2048,
            head_dim=128, d_ff=768, n_experts=128, experts_per_token=8,
            experts_held=range(16), qk_norm="head", block_length=4,
            mask_token_id=18991)
OURO = dict(vocab_size=49152, n_head=16, n_kv_head=16, d_model=2048,
            head_dim=128, d_ff=5632, ut_steps=4, sandwich_norm=True,
            exit_beta=0.1)
BOTH = (remat.QKV, remat.GATE_UP)

#: The benchmark's cells and two jobs that are none: the model, the batch,
#: the mesh, the resident bytes on a chip when the step is traced (the
#: compiled step's arguments, compile-only for the v5e, PERF.md PR 31), and
#: what the rule must say.
CELLS = {
    "mistral7b-s8192": (dict(MISTRAL, n_layer=2), (1, 8192), {}, 6.563, BOTH),
    "mistral7b-s1024": (dict(MISTRAL, n_layer=2), (8, 1024), {}, 6.563, BOTH),
    "olmoe-s4096": (dict(OLMOE, n_layer=1), (2, 4096), {}, 5.827, BOTH),
    # six layers of 16384 positions and their 131072 (position, expert) rows
    # of 2048: the compiler takes the plain step at 15.27 of 15.75 GiB and
    # refuses it with q, k and v kept (compile-only, PR 34)
    "sdar-ep8-s8192": (dict(SDAR, n_layer=6), (1, 16384), {}, 6.012, ()),
    # twelve layers over four chips: room for q/k/v, not for gate and up
    "mistral7b-fsdp4-s4096": (dict(MISTRAL, n_layer=12), (4, 4096),
                              {"fsdp": 4}, 6.720, (remat.QKV,)),
    # a third layer on one chip, a thirteenth over four: full, today's program
    "mistral7b-l3": (dict(MISTRAL, n_layer=3), (1, 8192), {}, 8.594, ()),
    "mistral7b-fsdp4-l13": (dict(MISTRAL, n_layer=13), (4, 4096),
                            {"fsdp": 4}, 7.230, ()),
    # eight layers run four times over: 32 layer applications' q, k and v
    # are 3.0 GiB and the rule sees 0.3 of room beside the two sets of
    # gradient stacks (on the chip: remat_room_bytes 308,806,296, PR 65)
    "ouro-l8-s4096": (dict(OURO, n_layer=8), (2, 4096), {}, 5.742, ()),
    # the same stack run once has room for everything
    "ouro-l8-one-pass": (dict(OURO, n_layer=8, ut_steps=1), (2, 4096), {},
                         5.742, BOTH),
}


@pytest.mark.parametrize("nudge_mb", [0, -64, 64])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_rule_decides_each_cell(cell, nudge_mb, monkeypatch):
    """Each Llama-path cell gets the decision of ISSUE 31's table, from the
    sizes ``llama._layer_policy`` computes, and a batch more or less in
    ``bytes_in_use`` does not move it."""
    sizes, (B, S), mesh_axes, resident, want = CELLS[cell]
    config = llama.LlamaConfig(seq_len=S, **sizes)
    on_device(monkeypatch,
              (V5E, int(resident * GiB) + nudge_mb * 2 ** 20))
    mesh = jax.set_mesh(make_mesh(MeshSpec(**mesh_axes), jax.devices()[:4])) \
        if mesh_axes else contextlib.nullcontext()
    with mesh:
        decision = _decide(config, B)
    assert decision.names == want
    # a scanned stack is one layer to the rule: all of it or none
    assert decision.layers() == [(name, 1, 1) for name in want]
    assert decision.room_bytes is not None and decision.processes == 1


#: The seven hybrid cells (``models/hybrid.py``): the bytes resident on the
#: chip when the step is traced (the compiled step's arguments, compile-only
#: for the v5e, PR 63; two rows stand 0.05-0.07 GiB off them, on the side
#: the chip's runs fell, where the arguments themselves lie within 64 MB of
#: a layer's edge: the rule keeps layer by
#: layer, so its margins are a layer's bytes, and what makes a second
#: lowering repeat the first is the decision's memory, not a margin) and
#: what the rule must keep there, as (kind, name,
#: layers kept, layers that name it) in the order it climbs: by spared work a
#: byte, each rung for as many of its kind's layers as fit.  The sizes are
#: the chip's: the scans' kernels, the convolution's pass and the splash
#: call's dq partials, which the backend decides.
M, A, E, K, L, D, W, C, G = "M*EKLDWCG"
HYBRID_CELLS = {
    "nemotron-ep16-s8192": (6.21, [(M, remat.SSM_IN, 4, 4),
                                   (A, remat.QKV, 1, 1),
                                   (E, remat.GATE_UP, 4, 4)]),
    # 0.8 GiB: the three inverses, q, k, v and one MLP's gate and up (0.34
    # GiB), as the chip's run kept (0.775 GiB; my chip run, PR 63); the
    # arguments, 8.65 GiB, lie 19 MB from that layer's edge
    "olmo-hybrid-s8192": (8.60, [(G, remat.INVERSE, 3, 3),
                                 (A, remat.QKV, 1, 1),
                                 (D, remat.GATE_UP, 1, 4)]),
    "lfm2-ep4-s8192": (6.63, [(A, remat.QKV, 2, 2), (D, remat.GATE_UP, 1, 1),
                              (C, remat.CONV_IN, 5, 5)]),
    "solar-open2-ep40-tp8": (7.83, [(A, remat.QKV, 1, 1),
                                    (E, remat.GATE_UP, 4, 4),
                                    (K, remat.CONV_IN, 3, 3)]),
    # the prediction module's layers among them; q, k and v of three of
    # seven, as the chip's run kept (1.335 GiB; my chip run, PR 63): the
    # arguments, 7.33 GiB, lie 40 MB from the fourth layer's edge
    "joyai-ep16-s8192": (7.40, [(L, remat.LATENTS, 7, 7),
                                (E, remat.GATE_UP, 6, 6),
                                (D, remat.GATE_UP, 1, 1),
                                (L, remat.QKV, 3, 7)]),
    "laguna-ep32-s8192": (7.55, [(W, remat.QKV, 3, 3), (A, remat.QKV, 2, 2),
                                 (E, remat.GATE_UP, 4, 4),
                                 (D, remat.GATE_UP, 1, 1)]),
    # the fullest: 1.1 GiB under the reserve, nothing kept
    "xing4-ep8-s4096": (8.51, []),
}


def _hybrid_cell(name, monkeypatch):
    """(the cell's model configuration, its rungs, its bound), sized as on
    the chip."""
    from tests.test_families import _cell_config

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    config, (rows, seq_len) = _cell_config(name)
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    return (config, *hybrid._layer_sizes(
        shapes, (rows, seq_len, config.d_model), config))


@pytest.mark.parametrize("nudge_mb", [0, -64, 64])
@pytest.mark.parametrize("cell", sorted(HYBRID_CELLS))
def test_the_rule_decides_each_hybrid_cell(cell, nudge_mb, monkeypatch):
    """Each hybrid cell gets the decision of ISSUE 63's table from the
    sizes ``hybrid._layer_sizes`` computes, layer by layer, and a batch
    more or less in ``bytes_in_use`` does not move it; a second trace of
    the step, 0.5 GiB fuller (the benchmark's lowering for the anatomy),
    gets the first's answer."""
    resident, want = HYBRID_CELLS[cell]
    config, rungs, bound = _hybrid_cell(cell, monkeypatch)
    in_use = int(resident * GiB) + nudge_mb * 2 ** 20
    on_device(monkeypatch, (V5E, in_use))
    decision = remat.decide(rungs, bound)
    assert list(decision.kept) == want
    assert decision.kept_bytes <= max(decision.room_bytes, 0)
    assert decision.routing_bytes == families.named(rungs).get(remat.ROUTING,
                                                             0)
    monkeypatch.setattr(remat, "device_memory",
                        lambda: (V5E, in_use + GiB // 2))
    assert remat.decide(rungs, bound) == decision
    # what a layer's checkpoint is told: the kind's first layers keep
    for group, index in hybrid._placed(config.sublayers):
        assert decision.policy((group, index)) is remat._policy(tuple(
            name for g, name, layers, _ in want
            if g == group and index < layers))



@pytest.mark.slow  # a cell's step compiles for the v5e in 40-140 s here
@pytest.mark.parametrize("cell", sorted(HYBRID_CELLS) + ["ouro-l8-s4096"])
def test_the_bound_lies_over_the_compilers(cell, monkeypatch, capsys,
                                           tmp_path):
    """``hybrid._layer_sizes``' bound against the compiler's own figure,
    ``memory_analysis()``'s temporaries of the cell's step compiled for a
    described v5e (``benchmarks/tools/compile_only.py``: no device memory
    there, so the plain program, which is what the bound is of): never
    under it, and within 0.5 GiB over it.  A kernel that takes arrays out
    of HBM, or a layer that puts some in, fails here.  The looped step's
    figure (``llama._layer_sizes``' bound) is the buffer assignment's total
    less the arguments: ``memory_analysis()`` counts what a scan stacks once
    more a loop around it, 4.3 GiB over the assignment there (PR 65)."""
    import glob
    import importlib.util
    looped = cell not in HYBRID_CELLS
    if looped:  # the tool's compile, with the assignment's report written
        compile_ = jax.stages.Lowered.compile
        monkeypatch.setattr(
            jax.stages.Lowered, "compile", lambda lowered: compile_(
                lowered, compiler_options={"xla_dump_to": str(tmp_path),
                                           "xla_dump_hlo_as_text": True}))

    from benchmarks.lib import correct, spec

    tool = importlib.util.spec_from_file_location(
        "compile_only", os.path.join(spec.BENCH_DIR, "tools",
                                     "compile_only.py"))
    compile_only = importlib.util.module_from_spec(tool)
    tool.loader.exec_module(compile_only)
    # the tool points these at the described chip and leaves them there
    for name in ("default_backend", "devices"):
        monkeypatch.setattr(jax, name, getattr(jax, name))
    cache = jax.config.jax_enable_compilation_cache
    # the step's row is the tool's first; its next line reads this
    monkeypatch.delattr(correct, "GRAD_SEQ")
    monkeypatch.setattr(sys, "argv", ["compile_only.py", cell])
    try:
        with pytest.raises(AttributeError, match="GRAD_SEQ"):
            compile_only.main()
        (row,) = [line for line in capsys.readouterr().out.splitlines()
                  if line.startswith(f"| {cell} step")]
        compiled = float(row.split("|")[5]) * GiB  # "temp GiB", to 0.01
        if looped:
            (report,) = glob.glob(str(
                tmp_path / "*jit_step*memory-usage-report.txt"))
            with open(report) as f:  # "Total bytes used: N (..GiB)"
                total = int(f.readline().split()[3])
            compiled = total - float(row.split("|")[3]) * GiB
            from tests.test_families import _cell_config

            config, (rows, seq_len) = _cell_config(cell)
            shapes = jax.eval_shape(lambda: llama.init_params(
                config, jax.random.key(0)))
            _, bound = llama._layer_sizes(
                shapes, (rows, seq_len, config.d_model), config)
        else:
            _, _, bound = _hybrid_cell(cell, monkeypatch)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    assert compiled - 0.005 * GiB <= bound <= compiled + 0.505 * GiB, (
        bound / GiB, compiled / GiB)


def _rung(name, nbytes, spares, layers=1, group="g"):
    return remat.Rung(name, nbytes, spares, layers, group)


@pytest.mark.parametrize("limit, in_use, candidates, temporaries, want", [
    # of 1000, 100 are the reserve; 500 in use leave 400 less the temporaries
    (1000, 500, [("a", 100), ("b", 100)], 301, []),
    # the first rung fits exactly, the second does not
    (1000, 500, [("a", 100), ("b", 100)], 300, [("a", 1, 1)]),
    (1000, 500, [("a", 100), ("b", 100)], 200, [("a", 1, 1), ("b", 1, 1)]),
    # rungs that spare the same a byte keep the order given (the scanned
    # stack's ladder): a second that would fit is not taken past a first
    # that does not
    (1000, 500, [("a", 300), ("b", 50)], 200, []),
    # a model with nothing named (GPT-2 XL's cell)
    (V5E, int(4.22 * GiB), [], int(9 * GiB), []),
    # greedy by spared work a byte: the small rung that spares 9 a byte
    # goes before the large one that spares 2, whatever the order given
    (1000, 500, [_rung("large", 150, 300.0), _rung("small", 30, 270.0)],
     200, [("small", 1, 1), ("large", 1, 1)]),
    (1000, 500, [_rung("large", 150, 300.0), _rung("small", 30, 270.0)],
     300, [("small", 1, 1)]),
    # layer by layer: three of five layers fit, and the climb ends there
    # though the next rung's layers are small enough
    (1000, 500, [_rung("wide", 60, 600.0, layers=5),
                 _rung("late", 10, 1.0, layers=2)], 200,
     [("wide", 3, 5)]),
    # a rung kept whole lets the next be kept for the layers that fit
    (1000, 500, [_rung("first", 20, 200.0, layers=2),
                 _rung("then", 50, 100.0, layers=4)], 250,
     [("first", 2, 2), ("then", 2, 4)]),
])
def test_choose(limit, in_use, candidates, temporaries, want):
    decision = remat.choose(limit, in_use, candidates, temporaries)
    assert decision.layers() == want
    assert decision.names == tuple(name for name, _, _ in want)
    sizes = {r.name: r.nbytes for r in remat._rungs(candidates)}
    assert decision.kept_bytes == sum(sizes[name] * kept
                                      for name, kept, _ in want)
    assert decision.room_bytes == limit - in_use - temporaries \
        - int(remat.RESERVE_SHARE * limit)


def test_a_rung_kept_for_some_layers_is_kept_by_the_groups_first():
    """Two kinds name one rung: each keeps it for its own first layers,
    the record sums them, and a layer that bears two groups' names (a
    kind's branch under the stream maps) gets both."""
    decision = remat.choose(1000, 0, [
        _rung("maps", 10, 1000.0, layers=4, group="hc"),
        _rung("qkv", 100, 500.0, layers=2, group="*"),
        _rung("qkv", 150, 450.0, layers=3, group="W")], 300)
    assert decision.kept == (("hc", "maps", 4, 4), ("*", "qkv", 2, 2),
                             ("W", "qkv", 2, 3))
    assert decision.layers() == [("maps", 4, 4), ("qkv", 4, 5)]
    assert decision.attributes()["remat_kept"] == [["maps", 4, 4],
                                                   ["qkv", 4, 5]]
    plain, qkv = remat._policy(()), remat._policy(("qkv",))
    assert [decision.policy(("W", i)) for i in range(3)] == [qkv, qkv, plain]
    assert decision.policy(("*", 1)) is qkv
    assert decision.policy(("E", 0)) is plain
    assert decision.policy(("W", 2), ("hc", 3)) is remat._policy(("maps",))
    assert decision.policy(("W", 0), ("hc", 9)) is qkv


def test_a_second_lowering_gets_the_first_answer(monkeypatch):
    """``decide`` answers a question it answered before from memory, however
    full the chip has become meanwhile (the traced benchmark run lowers the
    step again beside 0.3-0.5 GiB of batches and losses); another question
    is decided afresh; ``fall_back`` overrides what is remembered."""
    rungs = [_rung("wide", 200 << 20, 2.0, layers=8)]
    on_device(monkeypatch, (V5E, 10 * GiB))
    first = remat.decide(rungs, 3 * GiB)
    assert first.layers() == [("wide", 6, 8)]
    monkeypatch.setattr(remat, "device_memory",
                        lambda: (V5E, 10 * GiB + GiB // 2))
    with first_call.noting() as notes:
        assert remat.decide(rungs, 3 * GiB) == first
    assert notes["remat_kept"] == [["wide", 6, 8]]
    # the same layers in a step that needs more: asked anew, and fuller
    assert remat.decide(rungs, 3 * GiB + 1).layers() == [("wide", 3, 8)]
    remat.fall_back(notes["remat_kept"], "RESOURCE_EXHAUSTED: test")
    with first_call.noting() as notes:
        assert remat.decide(rungs, 3 * GiB).kept == ()
    assert notes["remat_kept"] == [] and notes["remat_kept_bytes"] == 0


def test_what_no_device_reported_is_not_remembered(monkeypatch):
    """A trace on a backend without memory statistics (a compile for a
    described topology before the run) pins nothing for the process."""
    rungs = [_rung("wide", 100, 2.0)]
    monkeypatch.setattr(remat, "device_memory", lambda: None)
    assert remat.decide(rungs, 0) == remat.Decision()
    monkeypatch.setattr(remat, "device_memory", lambda: ROOMY)
    assert remat.decide(rungs, 0).names == ("wide",)


def test_no_memory_statistics_is_the_plain_policy():
    """The CPU's devices report nothing; the reader says so and the rule
    keeps the splash residuals alone."""
    assert remat.device_memory() is None
    assert _decide(llama.LlamaConfig.tiny()) == remat.Decision()


# ------------------------------------------------- one program on every host
def test_fullest_is_the_smallest_limit_with_the_most_in_use():
    assert remat.fullest([(100, 10), (90, 30), (100, 20)]) == (90, 30)
    assert remat.fullest([(100, 10), None]) is None
    assert remat.fullest([]) is None


#: two processes of one job: rank 0 holds 2 GiB more than rank 1 (an
#: evaluation program, a checkpoint's staging), which alone would cost it
#: the second rung
RANKS = [(V5E, int(8.5 * GiB)), (V5E, int(6.563 * GiB))]


def _as_process(monkeypatch, rank, world=2, exchanged=None):
    """This process is ``rank`` of ``world``, each with 2 of the job's
    chips; its peers answer ``RANKS``."""
    monkeypatch.setattr(remat, "multiprocess_world", lambda: world)
    monkeypatch.setattr(jax, "local_device_count", lambda: 2)
    on_device(monkeypatch, RANKS[rank])

    def every_process(report, question):
        assert report == RANKS[rank]
        if exchanged is not None:
            exchanged.append(question)
        return RANKS

    monkeypatch.setattr(remat, "every_process", every_process)


def test_processes_with_different_memory_build_one_program(monkeypatch):
    """Under a mesh that spans processes each decides from the fullest chip
    of the job, whatever its own report: the same answer, asked under the
    same name, on both; alone they would have differed."""
    config = llama.LlamaConfig(seq_len=8192, **dict(MISTRAL, n_layer=2))
    mesh = make_mesh(MeshSpec(data=4), jax.devices()[:4])
    got, alone, asked = [], [], []
    for rank in range(2):
        with monkeypatch.context() as m:
            _as_process(m, rank, exchanged=asked)
            with jax.set_mesh(mesh):
                got.append(_decide(config, B=4))
        with monkeypatch.context() as m:  # the same chips, a job of its own
            on_device(m, RANKS[rank])
            alone.append(_decide(config, B=1))
    assert got[0] == got[1]
    assert got[0].names == (remat.QKV,) and got[0].processes == 2
    assert [d.names for d in alone] == [(remat.QKV,), BOTH]
    assert len(asked) == 2 and asked[0] == asked[1]


def test_a_program_of_the_process_s_own_chips_asks_nobody(monkeypatch):
    """In a multi-process job a mesh no larger than the process's devices is
    its own program (no peer traces it: asking would wait for ever); with no
    mesh at all nothing says whose program it is, and it stays plain."""
    config = llama.LlamaConfig(seq_len=8192, **dict(MISTRAL, n_layer=2))
    asked = []
    _as_process(monkeypatch, 1, exchanged=asked)
    with jax.set_mesh(make_mesh(MeshSpec(data=2), jax.devices()[:2])):
        own = _decide(config, B=2)
    assert own.names == BOTH and own.processes == 1
    assert _decide(config) == remat.Decision(processes=2)
    assert asked == []


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_real_processes_agree_and_train():
    """Two OS processes, one jax.distributed job, one Llama step over the
    global ``fsdp`` mesh; rank 0's chips report room for q/k/v alone, rank
    1's for everything.  Both keep what the fullest chip allows, through
    the real key-value store, and the step runs to the same loss on both."""
    port, procs = _free_port(), []
    child = os.path.join(os.path.dirname(__file__),
                         "_remat_multihost_child.py")
    for rank in range(2):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   COORD=f"127.0.0.1:{port}", NPROC="2", RANK=str(rank),
                   CHILD_DEVICES="2")
        env.pop("PYTHONPATH", None)
        procs.append(subprocess.Popen(
            [sys.executable, child], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True))
    rows = []
    for p in procs:
        stdout, stderr = p.communicate(timeout=570)
        assert p.returncode == 0, f"child failed:\n{stderr[-3000:]}"
        (line,) = [l for l in stdout.splitlines() if l.startswith("RESULT")]
        rows.append(line.split()[1:])
    rows.sort()
    # RESULT <rank> <kept> <processes> <alone> <loss>
    assert [r[0] for r in rows] == ["0", "1"]
    assert [r[1] for r in rows] == [remat.QKV, remat.QKV]
    assert [r[2] for r in rows] == ["2", "2"]
    assert [r[3] for r in rows] == [remat.QKV, ",".join(BOTH)]
    assert rows[0][4] == rows[1][4] and np.isfinite(float(rows[0][4]))


def _batch(config, B=2):
    tokens = jax.random.randint(jax.random.key(1), (B, config.seq_len + 1),
                                0, config.vocab_size)
    return tokens[:, :-1], tokens[:, 1:]


@pytest.mark.parametrize("preset", ["tiny", "tiny_moe"])
def test_keeping_more_changes_no_number(preset, monkeypatch):
    """Loss and every gradient leaf agree between the plain policy and the
    richest one to 1e-6 of the leaf's largest entry.  The kept arrays are
    the very values the backward would compute again, so nothing but the
    compiler's choice of fusions (the order of a float32 sum) may differ."""
    config = getattr(llama.LlamaConfig, preset)()
    params = llama.init_params(config, jax.random.key(0))
    batch = _batch(config)

    def run(kept):
        with first_call.noting() as notes:
            out = jax.jit(jax.value_and_grad(llama.loss_fn),
                          static_argnums=3)(params, *batch, config)
        assert notes["remat_kept"] == [[name, 1, 1] for name in kept]
        return out

    plain_loss, plain = run(())
    on_device(monkeypatch, ROOMY)
    loss, grads = run(BOTH)
    np.testing.assert_allclose(float(loss), float(plain_loss), rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(plain)):
        got, want = np.asarray(got), np.asarray(want)
        assert np.max(np.abs(got - want)) <= 1e-6 * np.max(np.abs(want))


def _layer_residuals(config, policy):
    """(dtype, shape) of what a checkpointed layer saves for its backward."""
    params = llama.init_params(config, jax.random.key(0))
    blk = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jnp.zeros((2, config.seq_len, config.d_model), config.dtype)
    layer = jax.checkpoint(lambda x, blk: llama._block(x, blk, config),
                           policy=policy)
    return sorted((str(aval.dtype), tuple(aval.shape))
                  for aval, _ in saved_residuals(layer, x, blk))


def _routing_residuals(config, B=2):
    """(dtype, shape) of what one layer's router decided (``moe.route``,
    ``moe.sort_pairs``, the weights in expert order), which a layer keeps
    under every policy: nothing for a model without experts."""
    if not config.n_experts:
        return []
    N, E, k = B * config.seq_len, config.n_experts, config.experts_per_token
    scores = [("float32", (N, k))] if config.norm_topk_prob else []
    return [("float32", (N, E)), ("int32", (N, k)), *scores,
            ("int32", (N * k,)), ("int32", (N, k)), ("int32", (E,)),
            ("float32", (N * k,))]


@pytest.mark.parametrize("preset", ["tiny", "tiny_moe"])
def test_the_layer_saves_the_named_arrays(preset, monkeypatch):
    """Under the richest policy the layer's residuals are today's plus q, k,
    v and the two MLP products; with no device memory they are what
    ``save_splash_residuals`` gives and, with experts, the routing (since
    PR 48; the dense layer's stay as they were)."""
    config = getattr(llama.LlamaConfig, preset)()
    today = sorted(_layer_residuals(config, save_splash_residuals)
                   + _routing_residuals(config))
    assert _layer_residuals(config, _policy(config)) == today
    on_device(monkeypatch, ROOMY)
    rich = _layer_residuals(config, _policy(config))
    B, S = 2, config.seq_len
    H, KV, hd = config.n_head, config.n_kv_head, config.head_dim
    rows = B * S * max(config.experts_per_token, 1)
    mlp_out = (rows, config.d_ff) if config.n_experts \
        else (B, S, config.d_ff)
    named = [("bfloat16", (B, S, H, hd)), ("bfloat16", (B, S, KV, hd)),
             ("bfloat16", (B, S, KV, hd)), ("bfloat16", mlp_out),
             ("bfloat16", mlp_out)]
    assert rich == sorted(today + named)


#: Every name a hybrid kind bears, with the rehearsal preset
#: (``tests/families.py``) whose pattern holds a kind that bears it.
NAMED = {remat.SSM_IN: "nemotron_h", remat.CONV_IN: "lfm2_moe",
         remat.INVERSE: "olmo_hybrid", remat.LATENTS: "joyai_llm_flash",
         remat.MAPS: "xing4_0", remat.QKV: "laguna",
         remat.GATE_UP: "solar_open2"}


@pytest.mark.parametrize("name", sorted(NAMED))
def test_keeping_a_hybrid_kinds_arrays_changes_no_number(name, monkeypatch):
    """``test_keeping_more_changes_no_number`` for every name the hybrid
    kinds bear, on the rehearsal preset that bears it, in float32 (in
    bfloat16 a router's choice may flip between two compilations of one
    program): loss and every gradient leaf under the plain policy and with
    every layer of every rung kept agree to 5e-6 of the leaf's largest
    entry (the order of float32 sums in other fusions), and the record says
    that the rung was kept for all its layers."""
    config = families.float32(NAMED[name])
    params = families.drawn(NAMED[name])
    tokens, targets = families.rows(config.vocab_size)

    def run():
        with first_call.noting() as notes:
            out = jax.jit(jax.value_and_grad(hybrid.loss_fn),
                          static_argnums=3)(params, tokens, targets, config)
        return out, {n: (kept, of) for n, kept, of in notes["remat_kept"]}

    (plain_loss, plain), kept = run()
    assert kept == {}
    on_device(monkeypatch, ROOMY)
    (loss, grads), kept = run()
    assert kept[name][0] == kept[name][1] > 0
    np.testing.assert_allclose(float(loss), float(plain_loss), rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(plain)):
        got, want = np.asarray(got), np.asarray(want)
        assert np.max(np.abs(got - want)) <= 5e-6 * np.max(np.abs(want))


def _hybrid_layer_residuals(config, kind, decision):
    """(dtype, shape) of what layer 0 of ``kind`` saves under its policy."""
    entry = hybrid.KINDS[kind]
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    blk = jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype),
                       shapes[entry.stack])
    x = jnp.zeros((2, config.seq_len, config.d_model), config.dtype)
    layer = jax.checkpoint(
        entry.module.layer(config, hybrid.logical_axes(config)[entry.stack],
                           0), policy=decision.policy((kind, 0)))
    return sorted((str(aval.dtype), tuple(aval.shape))
                  for aval, _ in saved_residuals(layer, x, blk))


@pytest.mark.parametrize("kind, preset, unread", [
    ("M", "nemotron_h", 0), ("K", "solar_open2", 0), ("C", "lfm2_moe", 0),
    ("G", "olmo_hybrid", 0), ("D", "olmo_hybrid", 0), ("*", "laguna", 0),
    ("W", "laguna", 0),
    # the rotary key of 8 lanes: with k kept the backward reads it no more
    ("L", "joyai_llm_flash", 2 * 128 * 8 * 2)])
def test_a_hybrid_layer_saves_the_named_arrays(kind, preset, unread):
    """``test_the_layer_saves_the_named_arrays`` for the kinds' own names:
    with every rung kept a layer's residuals hold, beside the plain
    policy's, arrays in the compute dtype that take what the kind's
    ``layer_bytes`` says its rungs take a layer (XLA's forms here: of the
    inverse ``T`` alone), less what the backward then reads no more."""
    import collections

    config = families.preset(preset, attn_impl="xla")
    shapes = jax.eval_shape(lambda: hybrid.init_params(config,
                                                       jax.random.key(0)))
    rungs = [r for r in hybrid._layer_sizes(
        shapes, (2, config.seq_len, config.d_model), config)[0]
        if r.name != remat.ROUTING]
    plain = collections.Counter(
        _hybrid_layer_residuals(config, kind, remat.Decision()))
    rich = collections.Counter(_hybrid_layer_residuals(
        config, kind, remat.choose(*ROOMY, rungs, 0)))
    more = list((rich - plain).elements())
    assert more and {dtype for dtype, _ in more} == {"bfloat16"}
    assert sum(2 * int(np.prod(shape)) for _, shape in more) == sum(
        r.nbytes for r in rungs if r.group == kind) - unread


_METADATA = re.compile(r", metadata=\{[^}]*\}")
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _without_locations(text):
    """A compiled module's text without where in the source each instruction
    came from (what ``scripts/hlo_strip.py`` drops, and ``op_name`` too)."""
    kept, in_table = [], False
    for line in _METADATA.sub("", text).splitlines():
        if line.strip() in _TABLES:
            in_table = True
        elif in_table:
            in_table = bool(line.strip())
        else:
            kept.append(line)
    return "\n".join(kept)


def _tiny_step_text(config):
    optimizer = llama.make_optimizer()
    params = llama.init_params(config, jax.random.key(0))
    step = jax.jit(llama.make_train_step(config, optimizer))
    text = step.lower(params, optimizer.init(params), *_batch(config)) \
        .compile().as_text()
    return _without_locations(text)


@pytest.mark.parametrize("preset", ["tiny", "tiny_moe"])
def test_without_device_memory_the_step_is_the_parents(preset, monkeypatch):
    """With no memory statistics the compiled tiny step is, instruction for
    instruction, the one of a tree without the rule: a layer in which no
    rung of the ladder is named, under ``save_splash_residuals`` and, since
    PR 48, the routing's name beside it (which a dense layer does not
    bear: its step is the one of the tree before the rule)."""
    config = getattr(llama.LlamaConfig, preset)()
    ours = _tiny_step_text(config)
    monkeypatch.setattr(
        llama, "_layer_policy",
        lambda *a: jax.checkpoint_policies.save_only_these_names(
            SPLASH_RESIDUALS, remat.ROUTING))
    for module in (layers, moe):  # where the layer's arrays are named
        monkeypatch.setattr(
            module, "checkpoint_name", lambda x, name: checkpoint_name(
                x, name) if name == remat.ROUTING else x)
    assert _tiny_step_text(config) == ours


# ---------------------------------------------------------------- TrainStep
def _step_and_state(config):
    optimizer = llama.make_optimizer()
    params = llama.init_params(config, jax.random.key(0))
    return (llama.make_train_step(config, optimizer), params,
            optimizer.init(params))


def test_first_call_says_what_was_kept(monkeypatch):
    device_telemetry.reset()
    on_device(monkeypatch, ROOMY)
    config = llama.LlamaConfig.tiny()
    step_fn, params, opt_state = _step_and_state(config)
    step = jit_train_step(step_fn)
    batch = _batch(config)
    params, opt_state, _ = step(params, opt_state, *batch)
    step(params, opt_state, *batch)
    (row,) = device_telemetry.first_calls("train_step")
    assert row["remat_kept"] == [[name, 1, 1] for name in BOTH]
    assert row["remat_kept_bytes"] > 0 and row["remat_room_bytes"] > 0
    assert row["remat_fallback"] is False


@pytest.mark.parametrize("memory", [None, ROOMY],
                         ids=["no-memory-statistics", "roomy"])
@pytest.mark.parametrize("preset", ["tiny", "tiny_moe"])
def test_first_call_says_what_the_routing_takes(preset, memory, monkeypatch):
    """``remat_routing_bytes``: every layer's routing for a model with
    experts, 0 for a dense one, whether or not the device reports memory
    (the routing is kept either way)."""
    device_telemetry.reset()
    on_device(monkeypatch, memory)
    config = getattr(llama.LlamaConfig, preset)()
    step_fn, params, opt_state = _step_and_state(config)
    jit_train_step(step_fn)(params, opt_state, *_batch(config))
    (row,) = device_telemetry.first_calls("train_step")
    N, E, k = 2 * config.seq_len, config.n_experts, config.experts_per_token
    assert row["remat_routing_bytes"] == (
        config.n_layer * 4 * (N * (E + 5 * k) + E) if E else 0)
    assert row["remat_kept"] == ([[name, 1, 1] for name in BOTH]
                                 if memory else [])


def test_a_refused_richer_step_falls_back_once(monkeypatch, caplog):
    """A step that kept more and is refused for memory is rebuilt under the
    plain policy, loudly; the process then stays with it, and a refusal of
    the plain program is the caller's to see."""
    device_telemetry.reset()
    on_device(monkeypatch, ROOMY)
    config = llama.LlamaConfig.tiny()
    inner, params, opt_state = _step_and_state(config)
    refusals = []

    def step_fn(*args):
        with first_call.noting() as notes:
            out = inner(*args)
        if notes["remat_kept"] or refusals == ["always"]:
            refusals.append(tuple(name for name, _, _
                                  in notes["remat_kept"]))
            raise RuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile "
                               "permanent error. Ran out of memory in hbm.")
        return out

    before = remat.REMAT_FALLBACKS.get()
    step = jit_train_step(step_fn)
    with caplog.at_level("WARNING", logger=remat.__name__):
        _, _, loss = step(params, opt_state, *_batch(config))
    assert np.isfinite(float(loss))
    assert refusals == [BOTH]
    assert remat.REMAT_FALLBACKS.get() == before + 1
    assert "refused for memory" in caplog.text
    (row,) = device_telemetry.first_calls("train_step")
    assert row["remat_fallback"] is True and row["remat_kept"] == []
    # a second trace in this process (a tool lowering the step again) gets
    # the program that ran
    assert _decide(config).names == ()
    # and a plain program that is refused fails
    refusals[:] = ["always"]
    inner, params, opt_state = _step_and_state(config)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        jit_train_step(step_fn)(params, opt_state, *_batch(config))
    assert remat.REMAT_FALLBACKS.get() == before + 1


class _Refused:
    """A jitted step whose call is refused for memory; ``by_compiler`` says
    whether compiling it alone is refused too."""

    def __init__(self, jitted, by_compiler):
        self.jitted, self.by_compiler = jitted, by_compiler

    def __call__(self, *args):
        self.jitted.lower(*args)  # the call traces: the rule decides
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    def lower(self, *args):
        return self

    def compile(self):
        if self.by_compiler:
            raise RuntimeError("RESOURCE_EXHAUSTED: XLA:TPU compile "
                               "permanent error.")


@pytest.mark.parametrize("by_compiler", [True, False])
def test_across_processes_only_the_compilers_refusal_falls_back(
        by_compiler, monkeypatch):
    """A step of several processes is rebuilt only when the compiler refused
    it, which every process sees alike; a process that ran out of memory on
    its own raises, since its peers have launched the program."""
    monkeypatch.setattr(remat, "_job_memory", lambda *a: (ROOMY, 2))
    monkeypatch.setattr(remat, "tracing_processes", lambda mesh: 2)
    config = llama.LlamaConfig.tiny()
    step_fn, params, opt_state = _step_and_state(config)
    step = jit_train_step(step_fn)
    step._jitted = _Refused(step._jitted, by_compiler)
    before = remat.REMAT_FALLBACKS.get()
    if by_compiler:
        device_telemetry.reset()
        _, _, loss = step(params, opt_state, *_batch(config))
        assert np.isfinite(float(loss))
        # the step was traced again (jax keeps the refused trace under the
        # function it came from), and the record says what runs
        (row,) = device_telemetry.first_calls("train_step")
        assert row["remat_fallback"] is True and row["remat_kept"] == []
        assert _decide(config).names == ()
    else:
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            step(params, opt_state, *_batch(config))
        assert _decide(config).names == BOTH
    assert remat.REMAT_FALLBACKS.get() == before + by_compiler


# ------------------------------------------------------ create_sharded_state
def test_moments_are_born_under_their_parameters_shardings():
    """ROADMAP A1 (1): under ``MeshSpec(fsdp=4)`` every leaf of the Adam
    moments lies as its parameter does, the counts replicated; optax makes
    them from zeros, which carry nothing for the compiler to propagate."""
    config = llama.LlamaConfig.tiny()
    mesh = make_mesh(MeshSpec(fsdp=4), jax.devices()[:4])
    params, opt_state = create_sharded_state(
        lambda key: llama.init_params(config, key),
        llama.logical_axes(config), mesh, jax.random.key(0),
        llama.make_optimizer())
    like = jax.tree.structure(params)
    mirrors = [node for node in jax.tree.leaves(
        opt_state, is_leaf=lambda n: jax.tree.structure(n) == like)
        if jax.tree.structure(node) == like]
    assert len(mirrors) == 2  # mu and nu
    for mirror in mirrors:
        for moment, param in zip(jax.tree.leaves(mirror),
                                 jax.tree.leaves(params)):
            assert moment.sharding.is_equivalent_to(param.sharding,
                                                    param.ndim)
    assert any(not p.sharding.is_fully_replicated
               for p in jax.tree.leaves(params))
    counts = [leaf for leaf in jax.tree.leaves(opt_state) if leaf.ndim == 0]
    assert counts and all(c.sharding.is_fully_replicated for c in counts)


def test_on_one_device_the_state_init_is_todays(monkeypatch):
    """On one device ``optimizer.init`` is jitted without ``out_shardings``,
    as it always was: the executable and its cache entry stay."""
    config = llama.LlamaConfig.tiny()
    seen = []
    real_jit = jax.jit

    def spy(fn, **kwargs):
        seen.append(kwargs)
        return real_jit(fn, **kwargs)

    monkeypatch.setattr(jax, "jit", spy)
    mesh = make_mesh(MeshSpec(), jax.devices()[:1])
    create_sharded_state(lambda key: llama.init_params(config, key),
                         llama.logical_axes(config), mesh, jax.random.key(0),
                         llama.make_optimizer())
    assert seen[-1] == {}
