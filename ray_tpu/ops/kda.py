"""The gated delta rule with a per-channel decay (KDA), computed in chunks.

Per head (keys and values of d channels, a state ``S`` of d x d), with
``g_t <= 0`` a channel and ``beta_t`` in (0, 2):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                                   (S_0 = 0 a row)

Unlike ``ops/ssd.py``'s recurrence the state is no decayed sum: every
position first *reads* the decayed state and writes the difference,

    u_t = beta_t (v_t - (Diag(exp g_t) S_{t-1})^T k_t),
    S_t = Diag(exp g_t) S_{t-1} + k_t u_t^T,

so inside a chunk of C positions the ``u`` depend on each other.  With ``G_t``
the cumulative sum of ``g`` inside the chunk (float32, decreasing) and
``S_0`` the state that enters it:

    u_t = beta_t (v_t - (k_t e^{G_t})^T S_0 - sum_{s<t} A_ts u_s)
    A_ts = sum_c k_tc k_sc e^{G_tc - G_sc}

a unit lower-triangular system a chunk and a head:

    U = T V - (T Kbar) S_0,    T = (I + diag(beta) tril(A, -1))^-1 diag(beta)
    o_t = (q_t e^{G_t})^T S_0 + sum_{s<=t} B_ts u_s,
    B_ts = sum_c q_tc k_sc e^{G_tc - G_sc}
    S_C = Diag(e^{G_C}) S_0 + sum_s (k_s e^{G_C - G_s}) u_s^T

What does not read ``S_0`` (``A``, ``B``, ``T``, ``T V``, ``T Kbar``) is
computed for every chunk at once; a ``lax.scan`` over the S / C chunks
carries ``S`` with two products a step (``(T Kbar) S_0`` and the chunk's
state), the only sequential part; the outputs are two products over every
chunk at once again.

**Numbers.**  Every decay is ``exp`` of a *non-positive difference of the one
cumulative sum*, as ``ops/ssd.py`` has it; the decay sits inside the q.k and
k.k contractions, a channel each, where the factorised form ``e^{G_t}
e^{-G_s}`` overflows as soon as a chunk's ``g`` adds up to -88.  So ``A`` and
``B`` are built from sub-chunks of :data:`SUB` positions: between two
sub-chunks as the product of ``k_t e^{G_t - G_r}`` and ``k_s e^{G_r - G_s}``
with r the first position of t's sub-chunk (s < r <= t: both exponents are
non-positive), inside a sub-chunk as the explicit sum over the channels of
``k_tc k_sc e^{G_tc - G_sc}``.  ``g``, ``G``, the decays, ``T`` and the
states are float32; the products multiply in q's dtype (bfloat16 in a
training step) and accumulate in float32.  ``T`` is made by forward
substitution inside each :data:`SUB` x :data:`SUB` diagonal block, row by
row, and the blocks are joined two by two (``[[X, 0], [-Y M X, Y]]``,
:func:`_unit_lower_inverse`): a product of ``(I + N^{2^i})`` would be all
matmuls and loses every digit where the keys of a chunk resemble each other
and decay little, because the powers of ``N`` grow where the inverse does
not; the same joins from single positions up (``D - D M D`` over the whole
chunk, six times) are as exact and moved 7 GB more a step through HBM (the
step 279 ms against 266: PERF.md, PR 43).

**Two implementations of the one algorithm**, chosen by what the call can
observe (:func:`path`), with no argument, configuration field or environment
variable to pick one:

- ``kernel``: ``ops/kda_kernel.py``, Pallas kernels over a grid of (row,
  heads, chunk) that keep every decay, every scaled copy of q and k, the
  products behind ``A`` and ``B``, ``U`` and the carried state in VMEM (``A``
  and ``B`` there by halving the chunk log2(C) times instead of writing a
  sub-chunk's decays out; the triangular system stays XLA's, 16 KB a chunk a
  head).  Taken where the sizes lie on the chip's tiles (heads of whole lane
  tiles, 128 channels or a multiple; chunks of whole sublane tiles of q's
  dtype, 16 to 128 positions, compiled for the v5e at each: at 256 the
  backward's blocks outgrow its 16 MB of scoped VMEM; the published KDA sizes
  lie inside, ``tiny-solar-open2``'s heads of 16 do not) and the call sits where a
  Mosaic call may sit: no mesh, a mesh of one device, or a mesh whose `data`
  / `fsdp` axes divide the rows and whose `tensor` axis divides the heads,
  under which the kernels run inside a ``shard_map`` over those axes, every
  chip scanning its own rows and heads.  Its backward is written out
  (``custom_vjp``s); the residuals are the inputs, ``A``, ``B``, the inverse
  and each chunk's incoming state.
- ``xla``: :func:`kda_xla`, the einsum form above, for every other shape and
  placement and as the kernels' oracle in the tests.  Its backward is
  autodiff through this form: each product's transpose is a product of the
  same shape and the scan over the chunk states transposes into the reverse
  scan over their cotangents.  XLA writes the explicit decays, the scaled
  copies and the chunk matrices to HBM, 51 GB a step in
  ``solar-open2-ep40-tp8``, which is what the kernels are for (PERF.md, PRs
  43 and 45).

Under the layer's ``jax.checkpoint`` nothing of a chunk outlives the layer's
pass on either path.  The first-call record says which ran
(``kda_scan_kernel``) and over what grid (``kda_scan_grid``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.ops.placement import place, rows_and_heads
from ray_tpu.util import first_call

#: positions of a sub-chunk: the explicit sum over channels is SUB x SUB x d
#: a sub-chunk, so it grows with SUB, the forward substitution has SUB - 1
#: dependent steps, and there are chunk / SUB scaled copies of the keys
SUB = 16


def _unit_lower_inverse(N):
    """N: (..., C, C) float32, strictly lower triangular, C a power of two.
    -> ``(I + N)^-1``: forward substitution inside each diagonal block of
    :data:`SUB` positions (row i of the inverse is ``e_i - N[i, :i] X[:i]``,
    SUB - 1 dependent steps over every block at once), then the blocks
    joined two by two: where ``X`` and ``Y`` invert two neighbouring blocks
    and ``M`` is what ``N`` holds below the first and left of the second,
    ``[[X, 0], [-Y M X, Y]]`` inverts the pair."""
    C = N.shape[-1]
    hi = lax.Precision.HIGHEST
    sub = min(SUB, C)
    n = C // sub
    blocks = N.reshape(*N.shape[:-2], n, sub, n, sub)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = np.eye(sub, dtype=np.float32)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (sub,))]
    for i in range(1, sub):
        done = jnp.stack(rows, axis=-2)                  # (..., n, i, sub)
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", diag[..., i, :i], done, precision=hi))
    inverse = jnp.stack(rows, axis=-2)                   # (..., n, sub, sub)
    inverse = [inverse[..., i, :, :] for i in range(n)]
    width = sub
    while len(inverse) > 1:
        joined = []
        for i in range(0, len(inverse), 2):
            X, Y = inverse[i], inverse[i + 1]
            at = i * width
            M = N[..., at + width:at + 2 * width, at:at + width]
            below = -jnp.einsum("...ij,...jk,...kl->...il", Y, M, X,
                                precision=hi)
            joined.append(jnp.concatenate([
                jnp.concatenate([X, jnp.zeros_like(X)], axis=-1),
                jnp.concatenate([below, Y], axis=-1)], axis=-2))
        inverse, width = joined, 2 * width
    return inverse[0]


def kda(q, k, v, g, beta, chunk: int):
    """q, k, v: (b, S, H, d); g: (b, S, H, d) float32, non-positive, the log
    of the decay a channel; beta: (b, S, H) float32.  S a multiple of
    ``chunk``, ``chunk`` a power of two.  -> o (b, S, H, d) in q's dtype; the
    state before a row's first position is zero."""
    S = q.shape[1]
    if S % chunk or chunk & (chunk - 1):
        raise ValueError(f"kda: {S} positions in chunks of {chunk} (a power "
                         "of two that divides them)")
    if path(q.shape, chunk, jax.sharding.get_abstract_mesh()) == "xla":
        first_call.note(kda_scan_kernel=False, kda_scan_grid=None)
        return kda_xla(q, k, v, g, beta, chunk)
    from ray_tpu.ops import kda_kernel  # it imports this module

    def local(q, k, v, g, beta):
        first_call.note(kda_scan_kernel=True,
                        kda_scan_grid=list(kda_kernel.grid(q, chunk)))
        return kda_kernel.scan(q, k, v, g, beta, chunk)

    return place(local, (q, k, v, g, beta), ("rh",) * 5, "rh")


def path(q_shape, chunk: int, mesh) -> str:
    """-> ``"kernel"`` or ``"xla"``: which implementation a call of these
    shapes takes under ``mesh`` (the module's docstring has the rule; the
    mesh's half of it is ``ops.placement.rows_and_heads``)."""
    b, _, H, d = q_shape
    if d % 128 == 0 and chunk % 16 == 0 and chunk <= 128 \
            and rows_and_heads(mesh, b, H) is not None:
        return "kernel"
    return "xla"  # the sizes; or positions, or nothing it knows, are cut


def kda_xla(q, k, v, g, beta, chunk: int):
    """:func:`kda` as einsums and one ``lax.scan`` over the chunk states."""
    b, S, H, d = q.shape
    C = chunk
    sub = min(SUB, C)
    n, m, dt, f32 = S // C, C // sub, q.dtype, jnp.float32
    hi = lax.Precision.HIGHEST
    # heads in front of the positions: every product is over (b, n, H)
    qc, kc, vc = (jnp.moveaxis(a.reshape(b, n, C, H, d), 3, 2)
                  for a in (q, k, v))                     # (b, n, H, C, d)
    gc = jnp.moveaxis(g.astype(f32).reshape(b, n, C, H, d), 3, 2)
    bc = jnp.moveaxis(beta.astype(f32).reshape(b, n, C, H), 3, 2)
    # the one cumulative sum every decay is a difference of, as a product
    # with a triangle of ones at full float32 precision (ops/ssd.py)
    upto = np.tril(np.ones((C, C), np.float32))           # [t, s]: s <= t
    G = jnp.einsum("ts,bnhsd->bnhtd", upto, gc, precision=hi)
    q32, k32 = qc.astype(f32), kc.astype(f32)

    # --- A and B, (b, n, H, C, C): between sub-chunks as a product ...
    Gs = G.reshape(b, n, H, m, sub, d)
    first = Gs[..., 0, :]                  # G_r, r a sub-chunk's first
    own = jnp.exp(Gs - first[..., None, :]).reshape(G.shape)  # e^{G_t - G_r}
    k_own, q_own = (k32 * own).astype(dt), (q32 * own).astype(dt)
    # e^{G_r - G_s} for every s before r, a copy for each later sub-chunk
    since = jnp.exp(jnp.minimum(
        first[..., None, :] - G[:, :, :, None], 0.0))     # (b, n, H, m, C, d)
    k_since = (k32[:, :, :, None] * since).astype(dt)
    between = [jnp.einsum(
        "bnhitd,bnhisd->bnhits", a.reshape(b, n, H, m, sub, d), k_since,
        preferred_element_type=f32).reshape(b, n, H, C, C)
        for a in (k_own, q_own)]
    t, s = np.arange(C)[:, None], np.arange(C)[None, :]
    earlier_sub = s // sub < t // sub
    # ... and inside a sub-chunk as the explicit sum over the channels
    ks = k32.reshape(b, n, H, m, sub, d)
    tt, ss = np.arange(sub)[:, None], np.arange(sub)[None, :]
    decay = jnp.exp(jnp.where(
        (ss <= tt)[:, :, None],
        Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf))
    inside = [jnp.sum(a.reshape(b, n, H, m, sub, 1, d) * ks[..., None, :, :]
                      * decay, axis=-1) for a in (k32, q32)]

    def whole(between, inside):
        """(C, C) from the sub-chunks' off-diagonal product and the (m, sub,
        sub) diagonal blocks."""
        blocks = jnp.where(earlier_sub, between, 0.0).reshape(
            b, n, H, m, sub, m, sub)
        same = np.eye(m, dtype=bool)[:, None, :, None]
        return jnp.where(same, inside[..., :, :, None, :], blocks).reshape(
            b, n, H, C, C)

    A, B = whole(between[0], inside[0]), whole(between[1], inside[1])

    # --- the triangular system a chunk and a head
    T = _unit_lower_inverse(jnp.where(s < t, A, 0.0) * bc[..., None]) \
        * bc[..., None, :]
    T = T.astype(dt)
    decayed = jnp.exp(G)                                  # e^{G_t}
    k_bar, q_bar = (k32 * decayed).astype(dt), (q32 * decayed).astype(dt)
    TV = jnp.einsum("bnhts,bnhsd->bnhtd", T, vc, preferred_element_type=f32)
    TK = jnp.einsum("bnhts,bnhsd->bnhtd", T, k_bar,
                    preferred_element_type=f32).astype(dt)
    last = G[..., -1:, :]                                 # G_C
    k_end = (k32 * jnp.exp(last - G)).astype(dt)          # k_s e^{G_C - G_s}
    through = jnp.exp(last[..., 0, :])                    # (b, n, H, d)

    # --- the states that enter the chunks: the sequential part
    def carry(state, chunk_parts):
        tv, tk, k_end, through = chunk_parts
        u = tv - jnp.einsum("bhtd,bhde->bhte", tk, state.astype(dt),
                            preferred_element_type=f32)
        new = state * through[..., None] + jnp.einsum(
            "bhtd,bhte->bhde", k_end, u.astype(dt),
            preferred_element_type=f32)
        return new, (state, u)

    _, (entering, U) = lax.scan(
        carry, jnp.zeros((b, H, d, d), f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (TV, TK, k_end, through)))
    entering = jnp.moveaxis(entering, 0, 1).astype(dt)    # (b, n, H, d, d)
    U = jnp.moveaxis(U, 0, 1).astype(dt)                  # (b, n, H, C, d)

    # --- the outputs
    o = jnp.einsum("bnhtd,bnhde->bnhte", q_bar, entering,
                   preferred_element_type=f32) \
        + jnp.einsum("bnhts,bnhse->bnhte", B.astype(dt), U,
                     preferred_element_type=f32)
    return jnp.moveaxis(o.astype(dt), 2, 3).reshape(b, S, H, d)
