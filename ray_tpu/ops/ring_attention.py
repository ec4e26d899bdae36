"""Context parallelism: ring attention + Ulysses all-to-all attention.

The reference has NO native sequence/context parallelism (SURVEY §2.3/§5 —
delegated to DeepSpeed/HF over Ray-provided process groups).  Here it is
native and TPU-shaped:

- **Ring attention** (Liu et al. 2023): K/V chunks rotate around the `seq`
  mesh axis via `lax.ppermute` (riding the ICI ring) while each device
  accumulates its queries' attention with a streaming log-sum-exp — memory
  per device is O(S/world), and the rotation overlaps with the block matmuls.
- **Ulysses** (Jacobs et al. 2023): `lax.all_to_all` reshards
  (seq-sharded, all heads) -> (full seq, head-sharded), runs ordinary
  causal attention per head shard (flash-compatible), and reshards back.
  Cheaper than the ring when heads % world == 0 and S fits per-device.

Both are pure jnp/lax bodies meant for `shard_map`, so they are reverse-mode
differentiable (scan + ppermute transpose) and compile to one XLA program.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30  # strictly-finite mask value: -inf breaks the streaming max

# Pallas splash kernels need KV blocks that are multiples of the 128-lane
# register tile; the fused ring path activates only when the per-device
# sequence shard admits such a block.
_LANE = 128


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _ring_block(seq_len: int) -> Optional[int]:
    """Largest multiple-of-128 divisor of seq_len, capped at the v5e-tuned
    512 (ops/attention.py) — None when no legal splash block exists."""
    for b in (512, 384, 256, 128):
        if b <= seq_len and seq_len % b == 0:
            return b
    return None


_FUSED_PROBE: Optional[bool] = None


def _fused_available() -> bool:
    """The fused backward reaches into jax's splash internals (the public
    custom-VJP can't merge per-block lse across ring steps); probe the
    private surface so a jax upgrade degrades impl='auto' to the einsum
    body instead of breaking every gradient at trace time.

    hasattr checks aren't enough — a surface can survive by name while its
    shape changes (BlockSizes growing a required ctor arg, kwargs keys
    renamed, bwd params reshuffled).  So this CONSTRUCTS a tiny kernel via
    the same ``_block_kernel`` path the real fwd/bwd use and touches every
    attribute/key/parameter ``_fused_ring_bwd`` reads.  Probed once per
    process; failure downgrades impl='auto' with a one-time loud warning.
    """
    global _FUSED_PROBE
    if _FUSED_PROBE is None:
        _FUSED_PROBE = _probe_fused_surfaces()
    return _FUSED_PROBE


def _bwd_dkv_leading_params(sk) -> list:
    """Names of _splash_attention_bwd_dkv's positional-or-keyword params
    (everything before the keyword-only marker), in order."""
    import inspect

    out = []
    for name, p in inspect.signature(
            sk._splash_attention_bwd_dkv).parameters.items():
        if p.kind is not inspect.Parameter.POSITIONAL_OR_KEYWORD:
            break
        out.append(name)
    return out


def _probe_fused_surfaces() -> bool:
    import inspect
    import warnings

    try:
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_kernel as sk,
        )
        # Construction exercises the 9-kwarg BlockSizes ctor and
        # _make_splash_attention's full signature (head_shards,
        # save_residuals, interpret, ...) exactly as the ring body does.
        kern = _block_kernel(128, 1, 128, "diag", True)
        # Surfaces read by _fused_ring_bwd:
        if kern.dkv_mask_info is None:
            raise AttributeError("kernel lost its dkv mask_info (was "
                                 "use_fused_bwd_kernel dropped?)")
        bs = kern.kwargs["block_sizes"]
        _ = (bs.q_layout, bs.k_layout, bs.v_layout)
        _ = kern.kwargs["mask_function"]  # key must exist (value may be None)
        _ = sk.DEFAULT_MASK_VALUE
        # The bwd helper is called entirely with keyword args: every name we
        # pass must still be a parameter (or a **kwargs catch-all), and the
        # tensor args we bind by name must still be leading params.
        params = inspect.signature(sk._splash_attention_bwd_dkv).parameters
        needed = {"bq", "bkv", "bkv_compute", "is_mqa", "mask_info",
                  "mask_value", "attn_logits_soft_cap",
                  "use_fused_bwd_kernel", "q_layout", "k_layout", "v_layout",
                  "mask_function", "interpret"}
        has_var_kw = any(p.kind is inspect.Parameter.VAR_KEYWORD
                         for p in params.values())
        missing = needed - set(params)
        if missing and not has_var_kw:
            raise TypeError(
                f"_splash_attention_bwd_dkv lost parameters: {sorted(missing)}")
        lead = _bwd_dkv_leading_params(sk)
        tensor_args = {"q", "k", "v", "logsumexp", "do", "di"}
        if not tensor_args <= set(lead):
            raise TypeError(
                "_splash_attention_bwd_dkv renamed leading params: "
                f"{sorted(tensor_args - set(lead))} missing from {lead}")
        return True
    except Exception as e:  # noqa: BLE001 — ANY probe failure means einsum
        warnings.warn(
            "ray_tpu.ops.ring_attention: the fused splash ring-attention "
            f"path is unavailable ({type(e).__name__}: {e}); impl='auto' "
            "falls back to the einsum body, which materializes per-block "
            "(B,H,S,S) scores — expect higher HBM traffic. Pin a jax "
            "version with the splash_attention private surfaces, or pass "
            "impl='einsum' to silence this.",
            RuntimeWarning, stacklevel=2)
        return False


def _block_kernel(seq_len: int, n_heads: int, block: int, kind: str,
                  interp: bool):
    """One ring-step splash kernel over a (seq_len x seq_len) chunk pair.

    kind="diag" masks causally within the chunk (the rotation step where the
    K/V chunk is the device's own); kind="full" is the unmasked block (chunks
    strictly earlier in the global order, and every step when non-causal).
    save_residuals=True so each step yields (out, lse) for the streaming
    merge.  NOT cached across traces (see ops/attention.py:_splash_kernel).
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    mask_cls = sm.CausalMask if kind == "diag" else sm.FullMask
    mask = sm.MultiHeadMask([mask_cls((seq_len, seq_len))
                             for _ in range(n_heads)])
    bs = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=None, block_kv_dq=None, use_fused_bwd_kernel=True,
    )
    return sk._make_splash_attention(
        mask, block_sizes=bs, is_mqa=False, save_residuals=True,
        head_shards=1, q_seq_shards=1, interpret=interp)


def _mark_varying(ref, *arrs):
    """shard_map vma plumbing: scan carries must enter with the same
    device-varying type their ppermute-mixing bodies produce."""
    if hasattr(lax, "pcast"):
        mesh_axes = tuple(jax.typeof(ref).vma) if hasattr(jax, "typeof") else ()
        if mesh_axes:
            return tuple(lax.pcast(x, mesh_axes, to="varying") for x in arrs)
    return arrs


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _fused_ring_core(q, k, v, axis_name: str, causal: bool, block: int):
    """Ring attention whose per-rotation block is the splash flash kernel.

    q/k/v: (B, H, S_local, D), q pre-scaled.  Forward merges per-block
    normalized outputs with their logsumexp; backward re-rotates K/V and runs
    the fused splash dq/dkv kernel per block with the GLOBAL (merged) lse and
    di — the standard flash decomposition, so block backward passes sum to
    the exact dense gradient.
    """
    out, _ = _fused_ring_fwd(q, k, v, axis_name, causal, block)
    return out


def _fused_ring_fwd(q, k, v, axis_name: str, causal: bool, block: int):
    world = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    interp = _interpret()
    diag_kern = _block_kernel(S, H, block, "diag", interp)
    full_kern = _block_kernel(S, H, block, "full", interp)
    perm = [(i, (i + 1) % world) for i in range(world)]

    def run(kern):
        def f(k_cur, v_cur):
            o_b, (lse_b,) = jax.vmap(kern)(q, k_cur, v_cur)
            return o_b.astype(jnp.float32), lse_b
        return f

    def skip(k_cur, v_cur):
        return (jnp.zeros((B, H, S, D), jnp.float32),
                jnp.full((B, H, S), _NEG, jnp.float32))

    o0 = jnp.zeros((B, H, S, D), jnp.float32)
    lse0 = jnp.full((B, H, S), _NEG, jnp.float32)
    o0, lse0 = _mark_varying(q, o0, lse0)

    def step(carry, s):
        k_cur, v_cur, o_acc, lse_acc = carry
        if causal:
            src = (idx - s) % world
            case = jnp.where(src > idx, 0, jnp.where(src == idx, 1, 2))
            o_b, lse_b = lax.switch(
                case, [skip, run(diag_kern), run(full_kern)], k_cur, v_cur)
        else:
            o_b, lse_b = run(full_kern)(k_cur, v_cur)
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        o_new = (o_acc * jnp.exp(lse_acc - lse_new)[..., None]
                 + o_b * jnp.exp(lse_b - lse_new)[..., None])
        return (lax.ppermute(k_cur, axis_name, perm),
                lax.ppermute(v_cur, axis_name, perm), o_new, lse_new), None

    (_, _, o, lse), _ = lax.scan(step, (k, v, o0, lse0), jnp.arange(world))
    return o.astype(q.dtype), (q, k, v, o, lse)


def _fused_ring_bwd(axis_name: str, causal: bool, block: int, res, do):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    q, k, v, o, lse = res
    world = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    interp = _interpret()
    diag_kern = _block_kernel(S, H, block, "diag", interp)
    full_kern = _block_kernel(S, H, block, "full", interp)
    bs = diag_kern.kwargs["block_sizes"]
    perm = [(i, (i + 1) % world) for i in range(world)]

    do = do.astype(q.dtype)
    di = jnp.sum(o * do.astype(jnp.float32), axis=-1)  # (B, H, S) global

    # The leading (positional-or-keyword) params drift across jax versions
    # (segment_ids grew neighbours): bind q/k/v/logsumexp/do/di BY NAME and
    # default every other leading param to None.  _probe_fused_surfaces
    # guarantees the names exist before impl='auto' ever routes here.
    lead = _bwd_dkv_leading_params(sk)

    def run(kern):
        def per_ex(q1, k1, v1, lse1, do1, di1):
            vals = dict.fromkeys(lead)
            vals.update(q=q1, k=k1, v=v1, logsumexp=lse1, do=do1, di=di1)
            return sk._splash_attention_bwd_dkv(
                **vals,
                bq=block, bkv=block, bkv_compute=block, is_mqa=False,
                mask_info=kern.dkv_mask_info,
                mask_value=sk.DEFAULT_MASK_VALUE,
                attn_logits_soft_cap=None, use_fused_bwd_kernel=True,
                q_layout=bs.q_layout, k_layout=bs.k_layout,
                v_layout=bs.v_layout,
                mask_function=kern.kwargs["mask_function"], interpret=interp)

        def f(k_cur, v_cur):
            dq_c, dk_c, dv_c = jax.vmap(per_ex)(q, k_cur, v_cur, lse, do, di)
            return (dq_c.astype(jnp.float32), dk_c.astype(jnp.float32),
                    dv_c.astype(jnp.float32))
        return f

    def skip(k_cur, v_cur):
        z = jnp.zeros((B, H, S, D), jnp.float32)
        return z, z, z

    zq = jnp.zeros((B, H, S, D), jnp.float32)
    zq, zk, zv = _mark_varying(q, zq, zq, zq)

    def step(carry, s):
        k_cur, v_cur, dk_cur, dv_cur, dq = carry
        if causal:
            src = (idx - s) % world
            case = jnp.where(src > idx, 0, jnp.where(src == idx, 1, 2))
            dq_c, dk_c, dv_c = lax.switch(
                case, [skip, run(diag_kern), run(full_kern)], k_cur, v_cur)
        else:
            dq_c, dk_c, dv_c = run(full_kern)(k_cur, v_cur)
        # dk/dv ride the ring WITH their chunk: after `world` rotations the
        # accumulated gradients land back on the chunk's home device.
        return (lax.ppermute(k_cur, axis_name, perm),
                lax.ppermute(v_cur, axis_name, perm),
                lax.ppermute(dk_cur + dk_c, axis_name, perm),
                lax.ppermute(dv_cur + dv_c, axis_name, perm),
                dq + dq_c), None

    (_, _, dk, dv, dq), _ = lax.scan(
        step, (k, v, zk, zv, zq), jnp.arange(world))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_fused_ring_core.defvjp(_fused_ring_fwd, _fused_ring_bwd)


def fused_ring_attention_local(q, k, v, *, axis_name: str = "seq",
                               causal: bool = True,
                               sm_scale: Optional[float] = None,
                               block: Optional[int] = None):
    """Pallas-fused ring attention body for shard_map: (B, S_local, H, D).

    Per rotation step the local block runs the splash flash kernel (scores
    never leave VMEM); fully-masked steps (K/V chunk strictly after the
    queries, causal) skip compute entirely — half the ring for free.
    """
    B, S, H, D = q.shape
    if block is None:
        block = _ring_block(S)
    if block is None:
        raise ValueError(
            f"fused ring needs S_local ({S}) divisible by a 128-multiple "
            "block; use impl='einsum'")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    qt = (q * scale).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fused_ring_core(qt, kt, vt, axis_name, causal, block)
    return out.transpose(0, 2, 1, 3)


# ---------------------------------------------------------------- ring local
def ring_attention_local(q, k, v, *, axis_name: str = "seq",
                         causal: bool = True,
                         sm_scale: Optional[float] = None,
                         impl: str = "auto"):
    """Body for shard_map: q/k/v are (B, S_local, H, D) sequence shards.

    impl="fused" runs the splash flash kernel per rotation block (VERDICT r4
    #2: the einsum block materialized (B,H,S,S) scores — exactly the HBM
    traffic flash exists to kill); "einsum" is the streaming-LSE reference
    body below; "auto" picks fused whenever the shard admits a legal splash
    block (S_local % 128 == 0).

    Streaming-softmax accumulation over `world` rotation steps; the k/v
    chunk held at step s originated on rank (idx - s) mod world, which
    fixes the global positions for causal masking.
    """
    if impl == "auto":
        impl = "fused" if (_ring_block(q.shape[1]) is not None
                           and _fused_available()) else "einsum"
    if impl == "fused":
        return fused_ring_attention_local(q, k, v, axis_name=axis_name,
                                          causal=causal, sm_scale=sm_scale)
    world = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    qpos = idx * S + jnp.arange(S)

    m0 = jnp.full((B, H, S), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    o0 = jnp.zeros((B, S, H, D), jnp.float32)
    # Mark the carry init as device-varying: the scan body's outputs vary
    # over the mesh (they mix in ppermuted k/v), and shard_map's vma check
    # requires carry-in types to match carry-out.
    if hasattr(lax, "pcast"):
        mesh_axes = tuple(jax.typeof(q).vma) if hasattr(jax, "typeof") else ()
        if mesh_axes:
            m0, l0, o0 = (lax.pcast(x, mesh_axes, to="varying")
                          for x in (m0, l0, o0))
    perm = [(i, (i + 1) % world) for i in range(world)]

    def step(carry, s):
        k_cur, v_cur, m, l, o = carry
        src_chunk = (idx - s) % world
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur,
                            preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = src_chunk * S + jnp.arange(S)
            mask = kpos[None, :] <= qpos[:, None]  # (Sq, Sk)
            scores = jnp.where(mask, scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        if causal:
            # exp(_NEG - _NEG) == 1 on fully-masked rows: zero them by hand.
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_cur.dtype), v_cur,
                        preferred_element_type=jnp.float32)
        o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, m_new, l_new, o_new), None

    (_, _, m, l, o), _ = lax.scan(step, (k, v, m0, l0, o0),
                                  jnp.arange(world))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# ------------------------------------------------------------- ulysses local
def ulysses_attention_local(q, k, v, *, axis_name: str = "seq",
                            causal: bool = True,
                            sm_scale: Optional[float] = None,
                            attn_fn=None):
    """Body for shard_map: all_to_all (B, S/w, H, D) -> (B, S, H/w, D),
    full-sequence attention per head shard, then the inverse reshard."""
    world = lax.axis_size(axis_name)
    H = q.shape[2]
    if H % world != 0:
        raise ValueError(f"Ulysses needs heads ({H}) % seq axis ({world}) == 0")
    if world > 1:
        q, k, v = (lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True) for x in (q, k, v))
    if attn_fn is None:
        attn_fn = partial(_xla_attention, causal=causal, sm_scale=sm_scale)
    out = attn_fn(q, k, v)
    if world > 1:
        out = lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                             tiled=True)
    return out


def _xla_attention(q, k, v, causal: bool = True,
                   sm_scale: Optional[float] = None):
    """Plain einsum-softmax-einsum causal attention (fp32 softmax)."""
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        S, K = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((S, K), bool))
        scores = jnp.where(mask, scores, _NEG)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ------------------------------------------------------------ shard_map APIs
def _specs(axis_name: str, batch_axes):
    P = jax.sharding.PartitionSpec
    return P(batch_axes, axis_name, "tensor", None)


def ring_attention(q, k, v, *, mesh=None, axis_name: str = "seq",
                   causal: bool = True, sm_scale: Optional[float] = None,
                   batch_axes=("data", "fsdp"), impl: str = "auto"):
    """Context-parallel causal attention over seq-sharded (B, S, H, D).

    With mesh=None the ambient mesh (jax.set_mesh / enclosing shard_map)
    is used, so model code stays mesh-agnostic.
    """
    spec = _specs(axis_name, batch_axes)
    fn = partial(ring_attention_local, axis_name=axis_name, causal=causal,
                 sm_scale=sm_scale, impl=impl)
    # check_vma off: the splash pallas_call inside the fused body does not
    # declare vma on its output avals, which the vma checker rejects.
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def ulysses_attention(q, k, v, *, mesh=None, axis_name: str = "seq",
                      causal: bool = True, sm_scale: Optional[float] = None,
                      attn_fn=None, batch_axes=("data", "fsdp")):
    """Ulysses sequence parallelism over seq-sharded (B, S, H, D)."""
    spec = _specs(axis_name, batch_axes)
    fn = partial(ulysses_attention_local, axis_name=axis_name, causal=causal,
                 sm_scale=sm_scale, attn_fn=attn_fn)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)
