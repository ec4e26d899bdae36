"""What a family adapter (``benchmarks/models/<family>.py``) hands the
harness: the program's own model functions built from a configuration file,
the plain reference beside them, the sizes the cost functions need, and the
kinds of attention-kernel call its step makes."""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

from benchmarks.lib import cost

#: dtypes q, k and v can reach a kernel in; masks and their tables are integers
_FLOATS = ("bf16", "f16", "f32")


@dataclass(frozen=True)
class AttentionCall:
    """One kind of attention-kernel call: what the kernel is handed, the
    mask's area, and how a Mosaic call of the compiled step is told to be of
    this kind.  ``lib/cost.py:attention_call_cost`` charges a call by these
    and nothing else.

    A call is of this kind when the first three floating-point operands of
    rank 3 or more in its line of the compiled text (``hlo_report``'s
    ``operands``: the masks' tables are integers, the residuals come after)
    end in q's, k's and v's dimensions as stated here, and ``scope``, where
    the kind states one, is part of the call's ``op_name`` (a named scope of
    the program: for two kinds that differ in the mask alone).  Dimensions
    before those three are the rows of the call.  A family whose kernel takes
    its operands otherwise subclasses this in its adapter and says so in
    ``rows_of``.
    """

    name: str
    #: the heads the kernel is handed: q and o, and k and v (a kernel that
    #: reads k and v at their own head count is handed those)
    q_heads: int
    kv_heads: int
    qk_dim: int
    v_dim: int
    #: seq_len -> the (query, key) pairs the mask allows a head
    pairs: Callable[[int], float]
    #: the query and key lengths as multiples of the cell's S
    q_len: int = 1
    kv_len: int = 1
    scope: str = ""

    def shapes(self, seq_len: int) -> Tuple[Tuple[int, int, int], ...]:
        """What q, k and v end in, as the kernel is handed them."""
        q, kv = self.q_len * seq_len, self.kv_len * seq_len
        return ((self.q_heads, q, self.qk_dim),
                (self.kv_heads, kv, self.qk_dim),
                (self.kv_heads, kv, self.v_dim))

    def rows_of(self, seq_len: int, operands: Sequence,
                op_name: str) -> Optional[int]:
        """The rows (sequences) a call of this kind runs over, from its
        operands' shapes; None where the call is of another kind."""
        qkv = [tuple(dims) for dtype, dims in operands
               if dtype in _FLOATS and len(dims) >= 3][:3]
        if len(qkv) < 3 or self.scope not in op_name or any(
                got[-3:] != want
                for got, want in zip(qkv, self.shapes(seq_len))):
            return None
        return math.prod(qkv[0][:-3])


def causal(heads: int, kv_heads: int, head_dim: int) -> AttentionCall:
    """A causal call over a row of S at one head dimension."""
    return AttentionCall("causal", heads, kv_heads, head_dim, head_dim,
                         pairs=cost.causal_pairs)


def kind_of(calls: Sequence[AttentionCall], seq_len: int, instruction: str,
            operands: Sequence, op_name: str) -> Tuple[AttentionCall, int]:
    """(the kind that claims a Mosaic call, the call's rows).  A call that
    none of the family's kinds claims, or more than one, is an error that
    names the call: never a guess."""
    claimed: List[Tuple[AttentionCall, int]] = []
    for kind in calls:
        rows = kind.rows_of(seq_len, operands, op_name)
        if rows is not None:
            claimed.append((kind, rows))
    if len(claimed) != 1:
        stated = "; ".join(f"{k.name}: q, k, v end in {k.shapes(seq_len)}"
                           + (f" under scope {k.scope!r}" if k.scope else "")
                           for k in calls)
        raise ValueError(
            f"attention call {instruction} (op_name {op_name!r}, operands "
            f"{operands}) is claimed by "
            f"{[k.name for k, _ in claimed] or 'no kind'} of the family's "
            f"attention_calls ({stated}): the family adapter has to state "
            "one kind for each call its step makes")
    return claimed[0]


@dataclass
class Family:
    #: key -> parameter pytree (float32, on the device)
    init_fn: Callable
    logical_axes: Any
    make_optimizer: Callable[[], Any]
    #: optimizer -> (params, opt_state, tokens, targets) -> same + loss; the
    #: function is called ``step``, so its program is ``jit_step`` in a trace
    make_train_step: Callable
    #: the program's own loss: (params, tokens, targets) -> scalar
    loss_fn: Callable
    #: the plain reference: (params, tokens, targets, q_block) -> scalar
    reference_loss: Callable
    #: model FLOPs per trained token at this sequence length (lib/cost.py)
    flops_per_token: float
    #: the kinds of attention-kernel call the step makes (or may make: a
    #: kind that claims no call costs nothing)
    attention_calls: Tuple[AttentionCall, ...]
    #: ids are drawn from [0, vocab_size)
    vocab_size: int
    eod_id: int
