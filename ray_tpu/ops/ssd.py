"""The state-space recurrence of a Mamba-2 layer, computed in chunks.

Per head (H heads of P channels, a state of N, the heads in G groups that
share ``B`` and ``C``), with ``delta_t > 0`` and ``A < 0`` a head:

    a_t = exp(delta_t A)
    h_t = a_t h_{t-1} + delta_t x_t B_t^T        (P x N;  h_0 = 0 a row)
    y_t = h_t C_t + D x_t

Position by position that is S dependent steps of rank-one updates, which
no matrix unit can use.  The sum it stands for is

    y_t = sum_{s <= t} exp(sum_{s < r <= t} delta_r A) (C_t . B_s) delta_s x_s

and a row cut into chunks of Q positions splits it in two.  Inside a chunk
it is a masked product ``(L o (C B^T)) (delta x)`` with ``L_ts`` the decay
from s to t; across chunks each chunk hands on one state,
``sum_s exp(sum_{s < r <= end} delta_r A) delta_s x_s B_s^T``, the states are
carried through a scan over the S / Q chunks (the only sequential part: S / Q
steps over (H, P, N) numbers), and position t adds ``C_t`` times the state
that came into its chunk, decayed up to t.  Four families of products a
chunk, all of them matmuls: ``C B^T`` a group, ``(L o C B^T) (delta x)``, the
chunk's state and ``C`` times the incoming state a head.

**Numbers.**  Every decay is ``exp`` of a *difference of one cumulative sum*
of ``delta A`` over the chunk, taken in float32 and never positive: a product
of ratios ``exp(c_t) / exp(c_s)`` overflows as soon as a chunk's ``delta A``
adds up to -88, which a head with ``A = -16`` reaches in six positions of
``delta = 1``.  ``delta``, the cumulative sums, the decays and the chunk
states are float32; the four products multiply in ``x``'s dtype (bfloat16 in
a training step) and accumulate in float32.

**Two implementations of the one algorithm**, chosen by what the call can
observe (:func:`path`), with no argument, configuration field or environment
variable to pick one:

- ``kernel``: ``ops/ssd_kernel.py``, a forward and a backward Pallas kernel
  over a grid of (row, group of heads, chunk) that keep a chunk's decays,
  scores and the carried state in VMEM.  Taken where the sizes lie on the
  chip's tiles (chunk and state multiples of 128, heads of 128 / 2^n
  channels that fill lane tiles side by side within their group: the
  published Mamba-2 sizes do, ``tiny-nemotron-h.json``'s do not) and the call
  sits where a Mosaic call may sit: no mesh, a mesh of one device, or a mesh
  whose `data` / `fsdp` axes divide the rows and whose `tensor` axis divides
  the groups, under which the kernels run inside a ``shard_map`` over those
  axes (the partitioner cannot cut a Mosaic call), every chip scanning its
  own rows and groups.  Its backward is written out (a ``custom_vjp``); the
  residuals are the inputs and each chunk's incoming state.
- ``xla``: :func:`ssd_xla`, the einsum form below, for every other shape and
  placement (a `seq` axis over a row's positions among them), and the
  kernels' oracle in the tests.  Its backward is autodiff through the
  chunked form: each product's transpose is a product of the same shape, and
  the scan over the chunk states transposes into the reverse scan over their
  cotangents.  XLA writes its (chunk x chunk) decays of every head to HBM,
  which is what the kernels are for (PERF.md, PRs 40 and 44).

Under the layer's ``jax.checkpoint`` nothing of a chunk outlives the layer's
pass on either path.  The first-call record says which ran
(``ssm_scan_kernel``) and over what grid (``ssm_scan_grid``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.ops import ssd_kernel
from ray_tpu.ops.placement import place, rows_and_heads
from ray_tpu.util import first_call


def ssd(x, delta, A, B, C, D, chunk: int):
    """x: (b, S, H, P); delta: (b, S, H) float32, positive; A: (H,) float32,
    negative; B, C: (b, S, G, N) with H a multiple of G (head h reads group
    ``h // (H / G)``); D: (H,).  S a multiple of ``chunk``.  -> y (b, S, H,
    P) in x's dtype; the state before a row's first position is zero."""
    b, S, H, P = x.shape
    G = B.shape[2]
    if S % chunk or H % G:
        raise ValueError(f"ssd: {S} positions in chunks of {chunk}, {H} "
                         f"heads in {G} groups")
    if path(x.shape, B.shape, chunk,
            jax.sharding.get_abstract_mesh()) == "xla":
        first_call.note(ssm_scan_kernel=False, ssm_scan_grid=None)
        return ssd_xla(x, delta, A, B, C, D, chunk)

    def local(x, delta, A, B, C, D):
        first_call.note(ssm_scan_kernel=True,
                        ssm_scan_grid=list(ssd_kernel.grid(x, B, chunk)))
        return ssd_kernel.scan(x, delta, A, B, C, D, chunk, True)

    return place(local, (x, delta, A, B, C, D),
                 ("rh", "rh", "h", "rh", "rh", "h"), "rh")


def path(x_shape, B_shape, chunk: int, mesh) -> str:
    """-> ``"kernel"`` or ``"xla"``: which implementation a call of these
    shapes takes under ``mesh`` (the module's docstring has the rule; the
    mesh's half of it is ``ops.placement.rows_and_heads``)."""
    b, _, H, P = x_shape
    G, N = B_shape[2:]
    if ssd_kernel.tiles(chunk, H // G, P, N) \
            and rows_and_heads(mesh, b, G) is not None:
        return "kernel"
    return "xla"  # the sizes; or positions, or nothing it knows, are cut


def ssd_xla(x, delta, A, B, C, D, chunk: int):
    """:func:`ssd` as einsums and one ``lax.scan`` over the chunk states."""
    b, S, H, P = x.shape
    G, N = B.shape[2:]
    Q, J = chunk, H // G
    c, dt, f32 = S // Q, x.dtype, jnp.float32
    xc = x.reshape(b, c, Q, G, J, P)
    Bc, Cc = B.reshape(b, c, Q, G, N), C.reshape(b, c, Q, G, N)
    # positions last: the (Q, Q) decays then lie as the products read them
    delta = jnp.moveaxis(delta.astype(f32).reshape(b, c, Q, G, J), 2, -1)
    # the one cumulative sum every decay is a difference of, as a product
    # with a triangle of ones at full float32 precision: ``jnp.cumsum``
    # lowers to a ``reduce_window`` that took the v5e 1.6 ms for these 4 MB,
    # every time it ran (PERF.md, PR 40)
    upto = np.triu(np.ones((Q, Q), np.float32))          # [s, t]: s <= t
    cum = jnp.einsum("bcgjs,st->bcgjt",
                     delta * A.astype(f32).reshape(G, J, 1), upto,
                     precision=lax.Precision.HIGHEST)
    total = cum[..., -1]                                  # (b, c, G, J)

    # inside a chunk: (L o (C B^T)) (delta x)
    scores = jnp.einsum("bctgn,bcsgn->bcgts", Cc, Bc,
                        preferred_element_type=f32)
    t, s = jnp.arange(Q)[:, None], jnp.arange(Q)[None, :]
    between = cum[..., :, None] - cum[..., None, :]       # (b, c, G, J, t, s)
    decay = jnp.exp(jnp.where(s <= t, between, -jnp.inf))
    mixed = (decay * scores[:, :, :, None]).astype(dt)
    fed = (xc.astype(f32)
           * jnp.moveaxis(delta, -1, 2)[..., None]).astype(dt)  # delta x
    y = jnp.einsum("bcgjts,bcsgjp->bctgjp", mixed, fed,
                   preferred_element_type=f32)

    # each chunk's own state, then the states that come into each chunk
    to_end = jnp.exp(total[..., None] - cum)              # (b, c, G, J, Q)
    left = (xc.astype(f32)
            * jnp.moveaxis(delta * to_end, -1, 2)[..., None]).astype(dt)
    states = jnp.einsum("bcsgn,bcsgjp->bcgjpn", Bc, left,
                        preferred_element_type=f32)

    def carry(h, chunk_state):
        own, total = chunk_state
        return h * jnp.exp(total)[..., None, None] + own, h

    _, incoming = lax.scan(
        carry, jnp.zeros((b, G, J, P, N), f32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(total, 1, 0)))
    incoming = jnp.moveaxis(incoming, 0, 1).astype(dt)    # (b, c, G, J, P, N)
    y = y + jnp.einsum("bcqgn,bcgjpn->bcqgjp", Cc, incoming,
                       preferred_element_type=f32) \
        * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]

    y = y + xc.astype(f32) * D.astype(f32).reshape(G, J)[:, :, None]
    return y.astype(dt).reshape(b, S, H, P)
