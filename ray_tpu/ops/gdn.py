"""The gated delta rule with one decay a head, computed in chunks.

Per head (keys of dk channels, values of dv, a state ``S`` of dk x dv), with
``g_t <= 0`` one number a head a position and ``beta_t`` in (0, 2):

    S_t = (I - beta_t k_t k_t^T) e^{g_t} S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                   (S_0 = 0 a row)

the gated-delta-net layer of the Qwen3-Next family (``models/gdn.py``).
``ops/kda.py`` is the same rule with a decay a *channel*; its docstring has
the derivation, which holds here with the decay a scalar: every position
reads the decayed state and writes the difference,

    u_t = beta_t (v_t - (e^{g_t} S_{t-1})^T k_t),   S_t = e^{g_t} S_{t-1}
                                                          + k_t u_t^T,

so inside a chunk of C positions, with ``G_t`` the cumulative sum of ``g``
inside the chunk and ``S_0`` the state that enters it,

    A = tril(K K^T, -1) * Gamma,   B = tril(Q K^T) * Gamma,
    Gamma_ts = e^{G_t - G_s}
    T = (I + diag(beta) A)^-1 diag(beta)        ops.kda._unit_lower_inverse
    U = T V - (T Kbar) S_0,        Kbar_t = k_t e^{G_t}
    o = Qbar S_0 + B U,            Qbar_t = q_t e^{G_t}
    S_C = e^{G_C} S_0 + (K e^{G_C - G})^T U

Because the decay is one number a position it leaves the q.k and k.k
contractions: ``A`` and ``B`` are one product a chunk times one (C x C)
array of decays, where a per-channel decay needs sub-chunks and explicit
decays.  Keys and values differ in width: ``K K^T`` and ``Q K^T`` contract
over dk, ``T V`` and ``B U`` are dv wide, the state is dk x dv.

What does not read ``S_0`` is computed for every chunk at once; a
``lax.scan`` over the S / C chunks carries ``S`` with two products a step;
the outputs are two products over every chunk at once again.

**Numbers.**  Every decay is ``exp`` of a non-positive difference of the one
cumulative sum, masked *before* the ``exp`` (the pairs s > t, whose
difference is positive, never reach it), so a chunk whose ``g`` adds up to
less than -88 underflows to zero where it should and nothing overflows.
``g``, ``G``, the decays, ``T`` and the states are float32; the products
multiply in q's dtype (bfloat16 in a training step) and accumulate in
float32.

**Two implementations of the one algorithm**, chosen by what the call can
observe (:func:`path`), with no argument, configuration field or environment
variable to pick one:

- ``kernel``: ``ops/gdn_kernel.py``, Pallas kernels over a grid of (row,
  heads, chunk) that keep ``Gamma``, ``B``, every scaled copy of q and k,
  ``U`` and the carried state in VMEM (``A`` and ``T`` cross HBM, 16 KB each
  a head a chunk of 64: the triangular system stays XLA's) and read q, k and
  v in the projections' own layout, a head's columns sliced at its lane
  offset, so that no heads-major copy is made.  Taken where the kernels were
  compiled for the v5e: keys and values of whole sublane tiles of 8 lanes
  (the published 96 under 192; 128 under 128 and 128 under 256, the
  Qwen3-Next family's; 64 under 128), at least :data:`NARROWEST` wide (under
  that a head is a sliver of a lane tile and the XLA form's batched products
  are no worse: ``tiny-olmo-hybrid.json``'s heads of 12 under 24), chunks of
  whole sublane tiles of q's dtype, 16 to 128 positions, and a step's blocks
  inside :data:`gdn_kernel.VMEM_MOST`; and where a Mosaic call may sit: no
  mesh, a mesh of one device, or a mesh whose `data` / `fsdp` axes divide
  the rows and whose `tensor` axis divides the heads, under which the
  kernels run inside a ``shard_map`` over those axes
  (``ops/placement.py``).  Its backward is written out (one
  ``custom_vjp``); the residuals are the inputs, ``A``, the inverse, ``T``
  and each chunk's incoming state.
- ``xla``: :func:`gdn_xla`, the einsum form above, for every other shape and
  placement and as the kernels' oracle in the tests.  Its backward is
  autodiff through this form: each product's transpose is a product of the
  same shape and the scan over the chunk states transposes into the reverse
  scan over their cotangents.  XLA writes ``Gamma``, ``A`` and ``B`` (three
  (C x C) float32 arrays a head a chunk), ``T`` and every scaled copy of q
  and k to HBM and turns q, k, v heads-major around the products: 108 ms a
  step in ``olmo-hybrid-s8192`` at 11.5 % of what the chip allows, which is
  what the kernels are for (PERF.md, PRs 58 and 59).

Under the layer's ``jax.checkpoint`` nothing of a chunk outlives the layer's
pass on either path.  The first-call record says which ran
(``gdn_scan_kernel``) and over what grid (``gdn_scan_grid``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import gdn_kernel, remat
from ray_tpu.ops.kda import _unit_lower_inverse
from ray_tpu.ops.placement import place, rows_and_heads
from ray_tpu.util import first_call

#: the narrowest keys and values the kernels take
NARROWEST = 64


def gdn(q, k, v, g, beta, chunk: int):
    """q, k: (b, S, H, dk); v: (b, S, H, dv); g: (b, S, H) float32,
    non-positive, the log of the decay a head; beta: (b, S, H) float32.  S a
    multiple of ``chunk``, ``chunk`` a power of two.  -> o (b, S, H, dv) in
    q's dtype; the state before a row's first position is zero."""
    S = q.shape[1]
    if S % chunk or chunk & (chunk - 1):
        raise ValueError(f"gdn: {S} positions in chunks of {chunk} (a power "
                         "of two that divides them)")
    if path(q.shape, v.shape, chunk,
            jax.sharding.get_abstract_mesh()) == "xla":
        first_call.note(gdn_scan_kernel=False, gdn_scan_grid=None)
        return gdn_xla(q, k, v, g, beta, chunk)

    def local(q, k, v, g, beta):
        first_call.note(gdn_scan_kernel=True,
                        gdn_scan_grid=list(gdn_kernel.grid(q, v, chunk)))
        return gdn_kernel.scan(q, k, v, g, beta, chunk)

    return place(local, (q, k, v, g, beta), ("rh",) * 5, "rh")


def path(q_shape, v_shape, chunk: int, mesh) -> str:
    """-> ``"kernel"`` or ``"xla"``: which implementation a call of these
    shapes takes under ``mesh`` (the module's docstring has the rule; the
    mesh's half of it is ``ops.placement.rows_and_heads``)."""
    b, _, H, dk = q_shape
    dv = v_shape[-1]
    cut = rows_and_heads(mesh, b, H)
    if cut is None or dk % 8 or dv % 8 or min(dk, dv) < NARROWEST \
            or chunk % 16 or chunk > 128:
        return "xla"  # the sizes; or positions, or nothing it knows, are cut
    heads = H // (mesh.shape[cut[1]] if cut[1] else 1)  # a chip's own
    return "kernel" if gdn_kernel.fits(heads, dk, dv, chunk) else "xla"


def gdn_xla(q, k, v, g, beta, chunk: int):
    """:func:`gdn` as einsums and one ``lax.scan`` over the chunk states."""
    b, S, H, dk = q.shape
    dv = v.shape[-1]
    C = chunk
    n, dt, f32 = S // C, q.dtype, jnp.float32
    # heads in front of the positions: every product is over (b, n, H)
    qc, kc = (jnp.moveaxis(a.reshape(b, n, C, H, dk), 3, 2) for a in (q, k))
    vc = jnp.moveaxis(v.reshape(b, n, C, H, dv), 3, 2)    # (b, n, H, C, dv)
    gc, bc = (jnp.moveaxis(a.astype(f32).reshape(b, n, C, H), 3, 2)
              for a in (g, beta))                         # (b, n, H, C)
    # the one cumulative sum every decay is a difference of, as a product
    # with a triangle of ones at full float32 precision (ops/ssd.py)
    t, s = np.arange(C)[:, None], np.arange(C)[None, :]
    G = jnp.einsum("ts,bnhs->bnht", (s <= t).astype(np.float32), gc,
                   precision=lax.Precision.HIGHEST)
    gamma = jnp.exp(jnp.where(s <= t, G[..., :, None] - G[..., None, :],
                              -jnp.inf))                  # (b, n, H, C, C)

    # --- A and B: one product each, then the decays
    A = jnp.einsum("bnhtd,bnhsd->bnhts", kc, kc,
                   preferred_element_type=f32) * gamma
    B = jnp.einsum("bnhtd,bnhsd->bnhts", qc, kc,
                   preferred_element_type=f32) * gamma

    # --- the triangular system a chunk and a head
    T = _unit_lower_inverse(jnp.where(s < t, A, 0.0) * bc[..., None]) \
        * bc[..., None, :]
    T = checkpoint_name(T.astype(dt), remat.INVERSE)
    decayed = jnp.exp(G)[..., None]                       # e^{G_t}
    q32, k32 = qc.astype(f32), kc.astype(f32)
    k_bar, q_bar = (k32 * decayed).astype(dt), (q32 * decayed).astype(dt)
    TV = jnp.einsum("bnhts,bnhse->bnhte", T, vc, preferred_element_type=f32)
    TK = jnp.einsum("bnhts,bnhsd->bnhtd", T, k_bar,
                    preferred_element_type=f32).astype(dt)
    last = G[..., -1:]                                    # G_C
    k_end = (k32 * jnp.exp(last - G)[..., None]).astype(dt)
    through = jnp.exp(last[..., 0])                       # (b, n, H)

    # --- the states that enter the chunks: the sequential part
    def carry(state, chunk_parts):
        tv, tk, k_end, through = chunk_parts
        u = tv - jnp.einsum("bhtd,bhde->bhte", tk, state.astype(dt),
                            preferred_element_type=f32)
        new = state * through[..., None, None] + jnp.einsum(
            "bhtd,bhte->bhde", k_end, u.astype(dt),
            preferred_element_type=f32)
        return new, (state, u)

    _, (entering, U) = lax.scan(
        carry, jnp.zeros((b, H, dk, dv), f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (TV, TK, k_end, through)))
    entering = jnp.moveaxis(entering, 0, 1).astype(dt)    # (b, n, H, dk, dv)
    U = jnp.moveaxis(U, 0, 1).astype(dt)                  # (b, n, H, C, dv)

    # --- the outputs
    o = jnp.einsum("bnhtd,bnhde->bnhte", q_bar, entering,
                   preferred_element_type=f32) \
        + jnp.einsum("bnhts,bnhse->bnhte", B.astype(dt), U,
                     preferred_element_type=f32)
    return jnp.moveaxis(o.astype(dt), 2, 3).reshape(b, S, H, dv)
