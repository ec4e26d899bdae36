"""The splash calls' block geometry follows the row (``ops/attention.py``:
``splash_blocks``): what the rule picks at the shapes the benchmark's cells
run, what that does to the grid and the stored tiles of the block-diffusion
row, and that a call whose forward and backward blocks differ still agrees
with the einsum.  CPU only; the times behind the rule are PERF.md's (PR 42).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import SplashBlocks

#: cell -> the kernel's shape there (positions, query heads, kv heads,
#: head_dim (the q.k head's and the v head's where they differ), rows, block
#: length of a block-diffusion row) and the blocks the
#: sweep on the chip chose (q, kv, kv_compute forward, then the same of the
#: fused backward)
WIDE, NARROW = (1024, 1024, 512, 1024, 1024, 512), (512,) * 6
CELLS = {
    "sdar-ep8-s8192": ((16384, 32, 4, 128, 1, 4), WIDE),
    "mistral7b-s8192": ((8192, 32, 8, 128, 1, 0), WIDE),
    "nemotron-ep16-s8192": ((8192, 32, 2, 128, 2, 0), WIDE),
    "mistral7b-fsdp4-s4096": ((4096, 32, 8, 128, 1, 0), WIDE),
    "olmoe-s4096": ((4096, 16, 16, 128, 2, 0), WIDE),
    "mistral7b-s1024": ((1024, 32, 8, 128, 8, 0), NARROW),
    "gpt2xl-s1024": ((1024, 25, 25, 64, 16, 0), NARROW),
    "joyai-ep16-s8192": ((8192, 32, 32, (192, 128), 1, 0), WIDE),
}


def _head_dims(head_dim):
    """(the q.k head's, the v head's)."""
    return head_dim if isinstance(head_dim, tuple) else (head_dim, head_dim)


@pytest.mark.parametrize("cell", CELLS)
def test_the_rule_picks_what_the_sweep_chose(cell):
    (kv_len, _, _, head_dim, _, block_length), want = CELLS[cell]
    head_dim = _head_dims(head_dim)[0]  # the rule reads the q.k head's
    blocks = attention.splash_blocks(kv_len, head_dim)
    assert tuple(blocks) == want
    # what the first-call record will say of the call (no kernel runs)
    _, counts = attention._splash_kernel(kv_len, 1, head_dim, True,
                                         block_length)
    assert {k: v for k, v in counts.items() if "block_" in k} == {
        "attn_block_q": blocks.q, "attn_block_kv": blocks.kv,
        "attn_block_q_bwd": blocks.q_bwd, "attn_block_kv_bwd": blocks.kv_bwd}
    assert counts["attn_dq_partials"] == kv_len // blocks.kv_bwd
    grid = (kv_len // blocks.q_bwd) * (kv_len // blocks.kv_bwd)
    assert counts["attn_grid_steps_bwd"] == grid  # walked whole


@pytest.mark.parametrize("kv_len,head_dim,block", [
    (256, 128, 256), (512, 128, 512), (1536, 128, 512), (2048, 128, 1024),
    (2560, 128, 512), (3072, 128, 1024), (32768, 128, 1024),
    (2048, 256, 512), (2048, 96, 512)])
def test_the_rule_keeps_to_rows_it_can_cut(kv_len, head_dim, block):
    """Rows and heads no cell runs: a block is never longer than the row and
    always cuts it evenly; a row of under two blocks of 1024, and a head the
    sweep did not measure, keep 512."""
    b = attention.splash_blocks(kv_len, head_dim)
    assert b == SplashBlocks(block, block, min(block, 512),
                             block, block, min(block, 512))
    assert all(kv_len % size == 0 for size in b)


def test_the_cells_row_under_the_rules_blocks():
    """The cell's row (2S = 16384, block length 4) in the blocks the rule
    picks, 1024 x 1024: 80 blocks with work a head (320 in blocks of 512,
    where 512-blocks had 288), 24 of them cut; the forward walks 144 grid
    steps for 544 and the fused backward 256 for 1024, 176 of them without
    work for 736, and writes 16 dq partials for 32.  Cut blocks read one of
    three stored tiles, the three kinds of diagonal at the new shape; no
    mask is computed in the kernel."""
    S, Bk, H = 8192, 4, 2
    kernel, counts = attention._splash_kernel(2 * S, H, 128, True, Bk)
    assert counts == {"attn_calls": 1, "attn_blocks": 80,
                      "attn_blocks_cut": 24, "attn_grid_steps_fwd": 144,
                      "attn_grid_steps_bwd": 256, "attn_block_q": 1024,
                      "attn_block_kv": 1024, "attn_block_q_bwd": 1024,
                      "attn_block_kv_bwd": 1024, "attn_dq_partials": 16}
    assert kernel.kwargs["mask_function"] is None
    for info in (kernel.fwd_mask_info, kernel.dkv_mask_info):
        assert info.q_sequence is None  # no mask computed in the kernel
        block_mask = np.asarray(info.block_mask)
        assert (block_mask == 1).sum() == 24 and (block_mask == 2).sum() == 56
        # every cut block points at a tile the kernel holds
        assert info.partial_mask_blocks.shape == (3, 1024, 1024)
        assert np.asarray(info.mask_next)[block_mask == 1].max() < 3
    tiles = np.asarray(kernel.fwd_mask_info.partial_mask_blocks)
    # the backward's tiles are the forward's, kv-major
    assert np.array_equal(
        np.asarray(kernel.dkv_mask_info.partial_mask_blocks),
        tiles.swapaxes(-1, -2))
    at = np.arange(1024)
    mine, theirs = at[:, None] // Bk, at[None, :] // Bk
    assert {t.tobytes() for t in tiles} == {
        (mine == theirs).tobytes(), (theirs < mine).tobytes(),
        (theirs <= mine).tobytes()}


def test_a_longer_kv_block_in_the_backward_alone():
    """The geometry ISSUE 42 expected and the sweep did not choose, through
    explicit blocks: only the backward's kv block at 1024.  512 grid steps
    and 16 partials; its 48 cut blocks read one of 6 tiles, the three kinds
    of diagonal through either half of the kv block."""
    Bk = 4
    kernel, counts = attention._splash_kernel(
        16384, 2, 128, True, Bk, SplashBlocks(512, 512, 512, 512, 1024, 512))
    assert (counts["attn_grid_steps_fwd"], counts["attn_grid_steps_bwd"],
            counts["attn_dq_partials"]) == (544, 512, 16)
    dkv = kernel.dkv_mask_info
    block_mask = np.asarray(dkv.block_mask)
    assert (block_mask == 1).sum() == 48 and (block_mask == 2).sum() == 112
    # kv-major: 1024 keys by 512 queries
    q_at = np.arange(512)[None, :] // Bk
    want = set()
    for half in (0, 512):
        k_at = (np.arange(1024)[:, None] - half) // Bk
        want |= {(k_at == q_at).tobytes(), (k_at < q_at).tobytes(),
                 (k_at <= q_at).tobytes()}
    assert {t.tobytes() for t in
            np.asarray(dkv.partial_mask_blocks).astype(bool)} == want


@pytest.mark.parametrize("kind,blocks", [
    ("causal", SplashBlocks(128, 128, 128, 128, 256, 128)),
    ("block_diffusion", SplashBlocks(128, 128, 128, 128, 256, 128)),
    ("block_diffusion", SplashBlocks(128, 256, 128, 256, 512, 256)),
], ids=["causal", "block-diffusion", "block-diffusion-wider"])
def test_unequal_blocks_agree_with_the_einsum(monkeypatch, kind, blocks):
    """Interpret mode, a row of 512 positions: the fused backward in other
    blocks than the forward and compute sub-blocks shorter than their blocks
    (as the rule has them), GQA; output and gradients against the einsum
    path."""
    B, P, H, KV, hd, Bk = 1, 512, 4, 2, 32, 4
    ks = jax.random.split(jax.random.key(42), 4)
    q = jax.random.normal(ks[0], (B, P, H, hd))
    k, v = (jax.random.normal(key, (B, P, KV, hd)) for key in ks[1:3])
    do = jax.random.normal(ks[3], q.shape)
    seen, real = [], attention._splash_kernel

    def forced(*args, **more):
        kernel, counts = real(*args, blocks=blocks, **more)
        seen.append(counts)
        return kernel, counts

    monkeypatch.setattr(attention, "_splash_kernel", forced)

    def run(impl):
        def out(q, k, v):
            if kind == "causal":
                return attention.causal_attention(q, k, v, impl)
            return attention.block_diffusion_attention(q, k, v, Bk, impl)

        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(out(q, k, v) * do),
            argnums=(0, 1, 2)))(q, k, v)

    (a, ga), (b, gb) = run("xla"), run("splash")
    assert seen and seen[0]["attn_block_kv_bwd"] == blocks.kv_bwd \
        and seen[0]["attn_dq_partials"] == P // blocks.kv_bwd
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for x, y in zip(ga, gb):
        err = float(jnp.linalg.norm(y - x) / jnp.linalg.norm(x))
        assert err < 1e-5


# ------------------------------------- the rule's blocks fit the chip's VMEM
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_rules_blocks_compile_for_the_v5e(monkeypatch, one_chip):
    """Forward and fused backward at every cell's real shape, compiled for a
    described v5e (nothing runs): the compiler refuses a kernel whose blocks
    need more than the scoped VMEM, which interpret mode never sees (in the
    sweep a q block of 2048 was refused with most kv blocks).  One test, so
    that one worker of a parallel run loads the TPU's compiler."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for cell, ((S, H, KV, hd, B, Bk), _) in CELLS.items():
            hd, hd_v = _head_dims(hd)

            def call(q, k, v, do):
                kernel, _ = attention._splash_kernel(S, H, hd, True, Bk)
                _, pull = jax.vjp(jax.vmap(kernel), q, k, v)
                return pull(do)

            q = jax.ShapeDtypeStruct((B, H, S, hd), jnp.bfloat16,
                                     sharding=one_chip)
            k, v, do = (jax.ShapeDtypeStruct((B, heads, S, width),
                                             jnp.bfloat16, sharding=one_chip)
                        for heads, width in ((KV, hd), (KV, hd_v), (H, hd_v)))
            text = jax.jit(call).lower(q, k, v, do).compile().as_text()
            # one forward, one backward
            assert text.count("tpu_custom_call") == 2, cell
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_the_scan_kernels_compile_for_the_v5e(monkeypatch, one_chip):
    """The Mamba-2 scan's forward and backward kernels (``ops/ssd_kernel.py``)
    at the shape ``nemotron-ep16-s8192`` runs them (2 rows of 8192, 64 heads
    of 64 in 8 groups, a state of 128, chunks of 128, bf16), compiled for a
    described v5e: the compiler refuses a kernel whose blocks, scratch and
    temporaries need more than the 16 MB of scoped VMEM (PR 42 met that
    limit with splash blocks of 2048), or that slices off the tiles, and
    interpret mode sees neither.  What the kernels ask for themselves, the
    blocks in two buffers and the scratch, is counted here too.  In this
    file, beside the other compile: one worker loads the TPU's compiler."""
    import re

    from ray_tpu.ops import ssd
    from ray_tpu.parallel.train_state import classify_op_name

    b, S, H, P, G, N, Q = 2, 8192, 64, 64, 8, 128, 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = (shape((b, S, H, P), jnp.bfloat16), shape((b, S, H), jnp.float32),
            shape((H,), jnp.float32), shape((b, S, G, N), jnp.bfloat16),
            shape((b, S, G, N), jnp.bfloat16), shape((H,), jnp.float32))
    assert ssd.path(args[0].shape, args[3].shape, Q,
                    jax.sharding.get_abstract_mesh()) == "kernel"

    def scan(*inputs):
        with jax.named_scope("ssm_scan"):  # as models/mamba2.py:mixer
            return ssd.ssd(*inputs, Q)

    def both(*inputs):
        y, pull = jax.vjp(jax.checkpoint(scan), *inputs)
        return pull(y)

    try:
        compiled = jax.jit(both).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    # the forward, the checkpoint's second run of it that keeps the boundary
    # states, the backward: each under the caller's scope, in its own phase
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    filed = sorted(classify_op_name(
        re.search(r'op_name="([^"]*)"', line).group(1)) for line in calls)
    assert filed == [("backward", "ssm_scan"), ("forward", "ssm_scan"),
                     ("recompute", "ssm_scan")]
    # a (Q, J) or (J, Q) float32 block in VMEM: J = 8 pads to a whole tile
    J = H // G
    wide, group, small = Q * J * P * 2, Q * N * 2, Q * 128 * 4
    states = J * P // 128 * N * 128 * 4
    forward = 2 * (2 * wide + 2 * group + 4 * small + states) + states
    backward = 2 * (3 * wide + 4 * group + 7 * small + states
                    + 8 * J * P * 4) + states + 3 * small
    assert forward < backward < 4 * 2 ** 20  # of 16 MiB



def test_the_kda_kernels_compile_for_the_v5e(monkeypatch, one_chip):
    """The delta-rule scan's kernels (``ops/kda_kernel.py``) at the shape
    ``solar-open2-ep40-tp8`` runs them (one row of 8192, 8 heads of 128,
    chunks of 64, bf16), compiled for a described v5e under the layer's
    checkpoint: ``A`` and ``B`` and the scan with the outputs, each forward,
    forward again and backward, every call under the caller's ``kda_scan``
    scope in its own phase (``step.kda_scan_ms`` reads the scope), and what
    the kernels ask of VMEM themselves far inside the 16 MiB."""
    import re

    from ray_tpu.ops import kda, kda_kernel
    from ray_tpu.parallel.train_state import classify_op_name

    b, S, H, d, C = 1, 8192, 8, 128, 64
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    wide = shape((b, S, H, d), jnp.bfloat16)
    args = (wide, wide, wide, shape((b, S, H, d), jnp.float32),
            shape((b, S, H), jnp.float32))
    assert kda.path(wide.shape, C,
                    jax.sharding.get_abstract_mesh()) == "kernel"
    assert kda_kernel.grid(wide, C) == (1, 1, 128)

    def scan(*inputs):
        with jax.named_scope("kda_scan"):  # as models/kda.py:mixer
            return kda.kda(*inputs, C)

    def both(*inputs):
        o, pull = jax.vjp(jax.checkpoint(scan), *inputs)
        return pull(o)

    try:
        compiled = jax.jit(both).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    filed = sorted(classify_op_name(
        re.search(r'op_name="([^"]*)"', line).group(1)) for line in calls)
    assert filed == [(phase, "kda_scan") for phase in
                     ("backward",) * 2 + ("forward",) * 2 + ("recompute",) * 2]
    # the backward scan's blocks in two buffers, and its scratch
    heads = kda_kernel.heads_a_step(H)
    # (q, k, v, dO, dq, dk, dv; G, dG; T, B, dB; dT; a (C, C) block pads its
    # 64 lanes to a tile)
    wide, square, states = C * heads * d, heads * C * 128, heads * d * d * 4
    backward = 2 * (7 * wide * 2 + 2 * wide * 4 + 3 * square * 2
                    + square * 4 + states) + states
    assert backward < 8 * 2 ** 20  # of 16 MiB


@pytest.mark.parametrize("b,S,H,dk,dv,C", [
    (1, 8192, 30, 96, 192, 64),     # olmo-hybrid-s8192
    (1, 1024, 30, 96, 192, 128), (1, 512, 30, 96, 192, 16),
    (1, 1024, 16, 128, 256, 64), (1, 1024, 32, 128, 128, 64),
    (1, 1024, 6, 64, 128, 64)],
    ids=["the-cell", "chunks-of-128", "chunks-of-16", "128-under-256",
         "128s", "64-under-128"])
def test_the_gdn_kernels_compile_for_the_v5e(monkeypatch, one_chip, b, S, H,
                                             dk, dv, C):
    """The gated-delta-net scan's kernels (``ops/gdn_kernel.py``) at the
    shape ``olmo-hybrid-s8192`` runs them (one row of 8192, 30 heads with
    keys of 96 under values of 192, sliced off the lane tiles, chunks of 64,
    bf16) and at the other widths and chunks ``ops.gdn.path`` admits,
    compiled for a described v5e under the layer's checkpoint: ``A`` and the
    scan with the outputs, each forward, forward again and backward, every
    call under the caller's ``gdn_scan`` scope in its own phase
    (``step.gdn_scan_ms`` reads the scope), and what the kernels ask of VMEM
    themselves inside what they tell the compiler."""
    import re

    from ray_tpu.ops import gdn, gdn_kernel
    from ray_tpu.parallel.train_state import classify_op_name

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    keys, values = (shape((b, S, H, d), jnp.bfloat16) for d in (dk, dv))
    args = (keys, keys, values, shape((b, S, H), jnp.float32),
            shape((b, S, H), jnp.float32))
    assert gdn.path(keys.shape, values.shape, C,
                    jax.sharding.get_abstract_mesh()) == "kernel"
    heads = gdn_kernel.heads_a_step(H, dk, dv)
    assert gdn_kernel.grid(keys, values, C) == (b, H // heads, S // C)

    def scan(*inputs):
        with jax.named_scope("gdn_scan"):  # as models/gdn.py:mixer
            return gdn.gdn(*inputs, C)

    def both(*inputs):
        o, pull = jax.vjp(jax.checkpoint(scan), *inputs)
        return pull(o)

    try:
        compiled = jax.jit(both).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    filed = sorted(classify_op_name(
        re.search(r'op_name="([^"]*)"', line).group(1)) for line in calls)
    assert filed == [(phase, "gdn_scan") for phase in
                     ("backward",) * 2 + ("forward",) * 2 + ("recompute",) * 2]
    assert gdn_kernel.fits(H, dk, dv, C)


def test_the_grouped_products_compile_for_the_v5e(monkeypatch, one_chip):
    """The expert layers' grouped products (``ops/grouped_matmul.py``: the
    forward, dx and dW of up and of down) at the four held-share cells' full
    shapes (``tests/test_olmoe.py``'s ``CELL_PRODUCTS`` but OLMoE's, whose
    tile is the (512, 1024, 1024) every cell ran until PR 50), in bf16 with the tiles ``tile_for`` gives them, compiled for a
    described v5e: three Mosaic calls each, none refused for the 16 MiB of
    scoped VMEM a tile's buffers must fit (a tile is a function of the
    call's shapes since PR 50: 640 for Solar's 1280, 896 and 640 for
    Nemotron's 2688 and 1856).  In this file: one worker loads the TPU's
    compiler."""
    from ray_tpu.ops.grouped_matmul import grouped_matmul
    from tests.test_olmoe import CELL_PRODUCTS

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    try:
        for cell, (M, D, F, G, H, first) in CELL_PRODUCTS.items():
            for K, N in ((D, F), (F, D)) if H < G else ():
                def three(lhs, rhs, dout, sizes):
                    out, pull = jax.vjp(
                        lambda a, b: grouped_matmul(a, b, sizes, first),
                        lhs, rhs)
                    return out, pull(dout)

                text = jax.jit(three).lower(
                    shape((M, K)), shape((H, K, N)), shape((M, N)),
                    shape((G,), jnp.int32)).compile().as_text()
                assert text.count("tpu_custom_call") == 3, (cell, K, N)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_the_rotary_kernel_compiles_for_the_v5e(monkeypatch, one_chip):
    """The rotary pass's kernel (``ops/rope_kernel.py``) over q and k at the
    shapes the cells hand it (Laguna's window layers at 72 + 8 heads and its
    full layers' 64 of 128 lanes under a YaRN table, Mistral's rows of 8192
    and of 1024, the block-diffusion row's two copies; heads of 256 lanes,
    which no cell has), forward and backward under a ``jax.checkpoint``,
    compiled for a described v5e: two Mosaic calls each, q and k in one (the
    forward and the backward: the pass saves nothing, so alone it is not run
    a second time), none refused for the scoped VMEM the rule's blocks must
    fit, every one under the caller's scope, and no
    product with a permutation beside them.  In this file: one worker loads
    the TPU's compiler."""
    from ray_tpu.models import layers
    from ray_tpu.parallel.train_state import classify_op_name

    yarn = layers.Yarn(factor=32.0, original=4096)
    shapes = {
        "laguna-window": (1, 8192, (72, 8), 128, {}),
        "laguna-full": (1, 8192, (48, 8), 128, dict(
            rotary=64, first=True, scale=yarn.scale,
            inv_freq=yarn.inv_freq(64, 5e5))),
        "mistral7b-s8192": (1, 8192, (32, 8), 128, {}),
        "mistral7b-s1024": (8, 1024, (32, 8), 128, {}),
        "sdar-ep8-s8192": (1, 16384, (32, 4), 128, dict(copies=2)),
        "heads-of-256": (1, 4096, (16, 4), 256, dict(rotary=128)),
    }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for name, (B, S, heads, hd, how) in shapes.items():
            def turn(*xs):
                with jax.named_scope("attn"):  # as layers.attention
                    return layers.rope(xs, 5e5, hd=hd, **how)

            def both(*xs):
                out, pull = jax.vjp(jax.checkpoint(turn), *xs)
                return pull(out)

            xs = [jax.ShapeDtypeStruct((B, S, H * hd), jnp.bfloat16,
                                       sharding=one_chip) for H in heads]
            text = jax.jit(both).lower(*xs).compile().as_text()
            calls = [line for line in text.splitlines()
                     if "tpu_custom_call" in line]
            assert len(calls) == 2, name
            assert all(classify_op_name(
                line.split('op_name="')[1].split('"')[0])[1] == "attn"
                for line in calls), name
            assert "convolution" not in text, name
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def test_the_conv_kernels_compile_for_the_v5e(monkeypatch, one_chip):
    """The short causal convolution's pass (``ops/conv_kernel.py``) through
    the four kinds' call sites at the shapes the cells hand them (the
    state-space mixer's span inside its projection's output, the delta
    rules' q or k in float32 and v rounded at 1024, 2880 and 5760 channels,
    the gate over ``[B | C | u]``), forward and backward under a
    ``jax.checkpoint``, compiled for a described v5e: a forward and a
    backward call an array (alone the checkpoint's second forward is the
    first's twin and the compiler runs it once), none refused for the scoped
    VMEM the rule's blocks must fit, every one under the caller's scope.  In this file: one worker loads the TPU's compiler."""
    from ray_tpu.models import mamba2, shortconv
    from ray_tpu.ops import conv_kernel
    from ray_tpu.parallel.train_state import classify_op_name

    f32 = jnp.float32

    def plain(out_dtype):
        def run(x, w):
            with jax.named_scope("gdn_conv"):
                return mamba2.short_conv(x, w, out_dtype)
        return run

    def spans(x, w, b):
        with jax.named_scope("ssm_conv"):
            assert conv_kernel.engaged(x.shape, 4, 4096, (4096, 1024, 1024))
            return conv_kernel.conv(x, w, b, act=True, out_dtype=jnp.bfloat16,
                                    offset=4096, widths=(4096, 1024, 1024))

    def gate(x, w):
        with jax.named_scope("shortconv_gate"):
            assert conv_kernel.engaged(x.shape, 3, gated=True)
            return conv_kernel.gated(x, w)

    #: name -> (the call site, its scope, the arrays' shapes, Mosaic calls)
    shapes = {
        "nemotron xBC": (spans, "ssm_conv", [
            ((2, 8192, 10304), jnp.bfloat16), ((4, 6144), f32),
            ((6144,), f32)], 6),
        "solar q": (plain(f32), "gdn_conv", [
            ((1, 8192, 1024), jnp.bfloat16), ((4, 1024), f32)], 2),
        "olmo q": (plain(f32), "gdn_conv", [
            ((1, 8192, 2880), jnp.bfloat16), ((4, 2880), f32)], 2),
        "olmo v": (plain(jnp.bfloat16), "gdn_conv", [
            ((1, 8192, 5760), jnp.bfloat16), ((4, 5760), f32)], 2),
        "lfm2 gate": (gate, "shortconv_gate", [
            ((2, 8192, 6144), jnp.bfloat16), ((3, 2048), f32)], 2),
    }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for name, (site, scope, arrays, want) in shapes.items():
            def both(*xs, site=site):
                out, pull = jax.vjp(jax.checkpoint(site), *xs)
                return pull(out)

            xs = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                  for shape, dtype in arrays]
            text = jax.jit(both).lower(*xs).compile().as_text()
            calls = [line for line in text.splitlines()
                     if "tpu_custom_call" in line]
            assert len(calls) == want, (name, len(calls))
            assert all(classify_op_name(
                line.split('op_name="')[1].split('"')[0])[1] == scope
                for line in calls), name
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


@pytest.mark.parametrize("rows", [256, 128])
def test_the_streams_kernels_compile_for_the_v5e(monkeypatch, one_chip, rows):
    """A sub-layer's hyper-connections (``ops/streams_kernel.py``) through
    ``streams.layer`` at ``xing4-ep8-s4096``'s shape, four streams of (2,
    4096, 3584) in bf16, forward and backward under a ``jax.checkpoint``,
    compiled for a described v5e at each row block of the rule: five Mosaic
    calls (the maps with the read forward and in the checkpoint's second
    forward, the write, the two backwards), none refused for the VMEM its
    call states, every one under ``mhc_mix``.  In this file: one worker loads
    the TPU's compiler."""
    import types

    from ray_tpu.models import streams
    from ray_tpu.ops import streams_kernel
    from ray_tpu.parallel.train_state import classify_op_name

    config = types.SimpleNamespace(
        streams=4, d_model=3584, hc_sinkhorn_iters=20, hc_eps=1e-6,
        hc_clamp=(-10.0, 10.0), rms_eps=1e-6)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(streams_kernel, "BLOCKS", (rows,))
    layer = jax.checkpoint(streams.layer(
        config, lambda u, blk: (jnp.tanh(u) * blk, None)))

    def both(X, blk, hc):
        (new, _), pull = jax.vjp(lambda *a: layer(*a), X, blk, hc)
        return pull((new, {k: jnp.ones_like(v) for k, v in
                           jax.eval_shape(layer, X, blk, hc)[1].items()}))

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    X = tuple(abstract((2, 4096, 3584), jnp.bfloat16) for _ in range(4))
    hc = {"phi": abstract((4 * 3584, 24), jnp.float32),
          "alpha": abstract((3,), jnp.float32),
          "base": abstract((24,), jnp.float32)}
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        text = jax.jit(both).lower(
            X, abstract((3584,), jnp.bfloat16), hc).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 5, len(calls)
    assert all(classify_op_name(
        line.split('op_name="')[1].split('"')[0])[1] == "mhc_mix"
        for line in calls)
