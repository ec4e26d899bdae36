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
top of the file.  One metric reads the record
(``kernels.splash_window_visited``: the band's ``window_pairs_visited*``).

**The keys** are :data:`KEYS`, each with the module that notes it and what
it says; the record also carries ``label``, ``ts`` and ``seconds``, the span
``compile_s``.  A new key is written where it is noted, in :data:`KEYS` and
in the test that is about it: nothing is checked while a step is traced (a
refusal there would be one more way for a step to stop), and
``tests/test_step_names.py`` holds every key a call site under
``ray_tpu/`` notes, and every key a kind's ``first_call_facts`` returns,
inside the table.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Iterator, List

#: (the module that notes them, what they say, the keys); a key that several
#: modules note stands under the first
_NOTED = (
    ("parallel/train_state.py", "the step was refused for memory and rebuilt "
     "under the plain policy", "remat_fallback"),
    ("ops/remat.py", "the rungs kept as [name, layers that keep it, layers "
     "that name it], their bytes, the room the rule "
     "saw (None: the device reports no memory), the expert layers' routing "
     "(always kept; 0: no layer routes); absent where no layer asked the "
     "rule (GPT-2)", "remat_kept remat_kept_bytes remat_room_bytes "
     "remat_routing_bytes"),
    ("ops/grad_ring.py", "weight gradients traced as rings (a scanned "
     "layer's once), `fsdp`'s size; 0 and 0 where none was",
     "grad_ring_products grad_ring_axis"),
    ("ops/attention.py", "where the splash kernel runs, a head: its calls, "
     "blocks with work, blocks that apply a mask, grid steps, the blocks "
     "picked for the row, the row over the backward's kv block",
     "attn_calls attn_blocks attn_blocks_cut attn_grid_steps_fwd "
     "attn_grid_steps_bwd attn_block_q attn_block_kv attn_block_q_bwd "
     "attn_block_kv_bwd attn_dq_partials"),
    ("ops/attention.py", "a call over a causal band, by names of its own: "
     "blocks (q, kv, the backward's q, kv), blocks run, blocks cut, grid "
     "steps (forward, backward), the pairs inside the blocks run, a head",
     "window_blocks window_blocks_run window_blocks_cut window_grid_steps "
     "window_pairs_visited window_pairs_visited_bwd"),
    ("ops/grouped_matmul.py", '"m x k x n" of each distinct grouped product '
     "traced -> the (rows, contraction, columns) tile it walks", "gmm_tiles"),
    ("models/moe.py", 'where a layer holds a share of its experts, "R x N x k '
     'x D" of each distinct window\'s return to its tokens -> ("kernel", the '
     'token tile of ops/window_return.py) or ("gather", None)', "moe_return"),
    ("ops/ssd.py", "the Pallas kernels or the XLA form, the kernels' grid a "
     "chip or None", "ssm_scan_kernel ssm_scan_grid"),
    ("ops/kda.py", "as ops/ssd.py's", "kda_scan_kernel kda_scan_grid"),
    ("ops/gdn.py", "as ops/ssd.py's", "gdn_scan_kernel gdn_scan_grid"),
    ("models/layers.py", "where a layer rotates: the lane roll of "
     "ops/rope_kernel.py or the product with a permutation, the calls traced "
     "(a layer's q and k are one, a scanned layer's once)",
     "rope_kernel rope_calls"),
    ("ops/conv_kernel.py", "where a kind runs its short causal convolution "
     "(M, K, G, C): the one Mosaic pass a direction or XLA's shifted "
     "multiply-adds, the convolutions traced (M's xBC and C's gate one a "
     "layer, K's and G's q, k and v one each)", "conv_kernel conv_calls"),
    ("models/llama.py", "the experts held of a layer's, a block-diffusion "
     "row's block (0: next-token), the positions attention and the loss "
     "run over, how often a step runs the stack (1: a plain decoder)",
     "experts_held experts_total block_length attn_positions "
     "loss_positions ut_steps"),
    ("models/hybrid.py", "the pattern run, one letter a layer; with a "
     "prediction module its depth and its loss's weight",
     "layer_kinds mtp_depth mtp_weight"),
    ("models/streams.py", "a residual of several streams: how many, the "
     "turns that normalise a stream map, the sub-layers under maps (whether "
     "their maps are kept for the backward: `mhc_maps` in remat_kept)",
     "streams hc_sinkhorn_iters mhc_sublayers"),
    ("ops/streams_kernel.py", "where a sub-layer's hyper-connections run: "
     "the four Mosaic passes over whole rows of the streams or XLA's "
     "expressions, the sub-layers traced (forward, a checkpoint's second "
     "forward and the backward are one trace each)",
     "streams_kernel mhc_calls"),
    ("models/attn.py", "(*) the query heads held of the model's, the output "
     "gate; where only a head's first lanes rotate, under YaRN, with QK-norm",
     "heads_held heads_total attn_gate rope_rotary_lanes rope_yarn_factor "
     "qk_norm"),
    ("models/window.py", "(W) the band's keys, its layers' query heads",
     "attn_window window_heads"),
    ("models/experts.py", "(E) sigmoid or softmax", "router_scoring"),
    ("models/mamba2.py", "(M) the last: S / chunk x rows",
     "ssm_heads ssm_state ssm_chunk ssm_chunks"),
    ("models/kda.py", "(K)", "kda_heads kda_head_dim kda_chunk kda_chunks"),
    ("models/mla.py", "(L) the last: the query's and the key-value latent's "
     "width", "mla_heads mla_qk_head_dim mla_v_head_dim mla_latents"),
    ("models/dense.py", "(D)", "dense_width"),
    ("models/shortconv.py", "(C)",
     "shortconv_taps shortconv_width shortconv_layers"),
    ("models/gdn.py", "(G)",
     "gdn_heads gdn_key_dim gdn_value_dim gdn_chunk gdn_chunks"),
)
#: every key of the record -> the module that notes it and what its keys say
KEYS: Dict[str, str] = {key: f"{module}: {says}"
                        for module, says, keys in _NOTED
                        for key in keys.split()}

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
