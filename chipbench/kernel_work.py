"""What ONE call of a kernel moves and computes, by the kernel's name in a
device trace: the numerators of a ``<kernel>_roofline`` share. Plain
arithmetic on the call's shapes, no JAX and nothing of the program, so
that it reads the same on a checkout that lacks the kernel.

No metric reads these yet. A roofline share needs the kernel's seconds
and its calls over the SAME interval: the profile gives seconds by op
name (``trace_reduce.reduce``: ``device_ops``) and no counts, the
counters (``engine_linear_state_bytes_total``, the loop's steps by kind)
cover the whole window and the drain. Scaling the window's counted work
to the profile's 3 s by ``ctx["device"]["tokens_in_window"]`` is off by
the live rows' drift between the two (10-20 % in a window), which a
share that may not pass 105 % cannot afford. ``PERF.md`` section 7 (5)
owes the matched interval to a ``benchmark`` PR (``run.py`` would read
``/metrics`` at the profile's two edges); ``linear_attn_op_share.serve``
reads the kernel's share of the busy time meanwhile.
"""

from __future__ import annotations


def linear_attn_recurrent_step_bytes(rows: int, value_heads: int,
                                     key_dim: int, value_dim: int) -> int:
    """HBM bytes of one call (``dynamo_tpu/ops/gated_delta_pallas.py``,
    one linear-attention layer's decode step over ``rows`` decode slots,
    dead ones included): every (row, head)'s float32 matrix read and
    written once, the packed ``qk`` operand, the three row operands
    (``v``, the decay and ``beta`` broadcast along the lanes) and the
    output."""
    matrices = rows * value_heads * key_dim * value_dim * 4
    small = rows * (2 * value_heads * key_dim + 4 * value_heads * value_dim) * 4
    return 2 * matrices + small


def linear_attn_recurrent_step_flops(rows: int, value_heads: int,
                                     key_dim: int, value_dim: int) -> int:
    """Float32 VPU operations of one call on the matrices: the decay (1),
    ``S^T k`` (2), the rank-one update (2) and ``S^T q`` (2) an element."""
    return 7 * rows * value_heads * key_dim * value_dim
