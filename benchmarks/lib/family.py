"""What a family adapter (``benchmarks/models/<family>.py``) hands the
harness: the program's own model functions built from a configuration file,
the plain reference beside them, and the sizes the cost functions need."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Tuple


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
    #: (heads, head_dim) of one attention call as the kernel sees it
    attention_heads: Tuple[int, int]
    #: ids are drawn from [0, vocab_size)
    vocab_size: int
    eod_id: int
