"""What a step says of itself while it is traced, for its first-call record.

A call of a :class:`~ray_tpu.parallel.train_state.TrainStep` that built or
loaded its executable is a ``train.first_call`` span and a row of
``device_telemetry.first_calls()``.  The code that is traced in that call
knows things no reader of the compiled program can ask it for: what the
layers keep for the backward, which implementation a scan took, how a mask
was covered.  It says them with :func:`note` (:func:`count` for what adds
up over a trace, :func:`entry` for what it says a case at a time); whoever
wraps the trace in :func:`noting` reads them as one dict.  Nothing here
imports the package's layers, so ``ops/`` and ``models/`` import it at the
top of the file.  No metric reads the record.

**The keys**, by who notes them (the record also carries ``label``, ``ts``
and ``seconds``, the span ``compile_s``):

============================  ==============================================
``parallel/train_state.py``   ``remat_fallback``: the step was refused for
                              memory and rebuilt under the plain policy
``ops/remat.py``              ``remat_kept`` (the ladder's rungs kept),
                              ``remat_kept_bytes``, ``remat_room_bytes``
                              (None: the device reports no memory),
                              ``remat_routing_bytes`` (the expert layers'
                              routing, always kept; 0: no layer routes);
                              absent where no layer asked the rule (GPT-2)
``ops/grad_ring.py``          ``grad_ring_products`` (weight gradients
                              traced as rings; a scanned layer's once),
                              ``grad_ring_axis`` (`fsdp`'s size); 0 and 0
                              where none was
``ops/attention.py``          where the splash kernel runs, a head:
                              ``attn_calls``, ``attn_blocks`` (with work),
                              ``attn_blocks_cut`` (that apply a mask),
                              ``attn_grid_steps_fwd``,
                              ``attn_grid_steps_bwd``, ``attn_block_q``,
                              ``attn_block_kv``, ``attn_block_q_bwd``,
                              ``attn_block_kv_bwd``, ``attn_dq_partials``
                              (the row over the backward's kv block);
                              a call over a causal band by names of its
                              own: ``window_blocks`` (q, kv, the backward's
                              q, kv), ``window_blocks_run``,
                              ``window_blocks_cut``, ``window_grid_steps``
                              (forward, backward), ``window_pairs_visited``
                              and ``window_pairs_visited_bwd`` (the pairs
                              inside the blocks run, a head)
``ops/grouped_matmul.py``     ``gmm_tiles``: ``"m x k x n"`` of each distinct
                              grouped product traced -> the (rows,
                              contraction, columns) tile it walks
``models/moe.py``             where a layer holds a share of its experts,
                              ``moe_return``: ``"R x N x k x D"`` of each
                              distinct window's return to its tokens ->
                              (``"kernel"``, the token tile of
                              ``ops/window_return.py``) or (``"gather"``,
                              None)
``ops/ssd.py``                ``ssm_scan_kernel`` (the Pallas kernels, or
                              the XLA form), ``ssm_scan_grid`` (the
                              kernels' grid a chip, or None)
``ops/kda.py``                ``kda_scan_kernel``, ``kda_scan_grid``
``ops/gdn.py``                ``gdn_scan_kernel``, ``gdn_scan_grid``
``models/layers.py``          where a layer rotates (``rope``):
                              ``rope_kernel`` (the lane roll of
                              ``ops/rope_kernel.py``, or the product with a
                              permutation), ``rope_calls`` (the calls
                              traced: a layer's q and k are one, a scanned
                              layer's once)
``models/llama.py``           ``experts_held``, ``experts_total``,
                              ``block_length``, ``attn_positions``,
                              ``loss_positions``
``models/hybrid.py``          ``layer_kinds`` (the pattern run),
                              ``loss_positions``; with a prediction module
                              ``mtp_depth``, ``mtp_weight``; then each kind
                              of the pattern its own:
``models/attn.py`` (``*``)    ``attn_positions``, ``heads_held``,
                              ``heads_total``, ``attn_gate``; where only a
                              head's first lanes rotate
                              ``rope_rotary_lanes``, under YaRN
                              ``rope_yarn_factor``, with QK-norm
                              ``qk_norm``
``models/window.py`` (``W``)  ``attn_window`` (the band's keys),
                              ``window_heads``
``models/experts.py`` (``E``) ``experts_held``, ``experts_total``,
                              ``router_scoring``
``models/mamba2.py`` (``M``)  ``ssm_heads``, ``ssm_state``, ``ssm_chunk``,
                              ``ssm_chunks`` (S / chunk x rows)
``models/kda.py`` (``K``)     ``kda_heads``, ``kda_head_dim``,
                              ``kda_chunk``, ``kda_chunks``
``models/mla.py`` (``L``)     ``attn_positions``, ``mla_heads``,
                              ``mla_qk_head_dim``, ``mla_v_head_dim``,
                              ``mla_latents`` (the query's and the
                              key-value latent's width)
``models/dense.py`` (``D``)   ``dense_width``
``models/shortconv.py``       ``shortconv_taps``, ``shortconv_width``,
(``C``)                       ``shortconv_layers``
``models/gdn.py`` (``G``)     ``gdn_heads``, ``gdn_key_dim``,
                              ``gdn_value_dim``, ``gdn_chunk``,
                              ``gdn_chunks``
============================  ==============================================
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Iterator, List

_thread = threading.local()  # .open: the dicts of the blocks open in here


def _open() -> List[Dict[str, Any]]:
    return _thread.__dict__.setdefault("open", [])


def note(**facts: Any) -> None:
    """From inside a trace: ``facts`` for every :func:`noting` block open on
    this thread; a later note of a key replaces an earlier one.  Outside
    such a block, nothing."""
    for notes in _open():
        notes.update(facts)


def entry(name: str, key: str, value: Any) -> None:
    """``key -> value`` in the dict ``name`` (from empty) of every block open
    on this thread: what a trace says once for each distinct case of it."""
    for notes in _open():
        notes.setdefault(name, {})[key] = value


def count(name: str) -> None:
    """One more of ``name`` (from 0) in every block open on this thread."""
    for notes in _open():
        notes[name] = notes.get(name, 0) + 1


@contextlib.contextmanager
def noting(**start: Any) -> Iterator[Dict[str, Any]]:
    """What is noted on this thread while the block runs, as one dict that
    begins as ``start``; blocks nest, and a note reaches all of them."""
    notes = dict(start)
    _open().append(notes)
    try:
        yield notes
    finally:
        _open().pop()  # this one's dict is the last
