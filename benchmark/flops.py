"""Operations and bytes the algorithms need, computed from shapes. Kept
with the benchmark so that no later PR can move the yardstick. Copied from
`GPTForCausalLM.flops_per_token` (paddle_tpu/incubate/models/gpt.py), which
`PERF.md` lists for deletion."""
from __future__ import annotations


def train_flops_per_token(params, layers, hidden, seq):
    """Forward plus backward, PaLM appendix B: 6 N for the matrix products
    of N parameters and 12 L h s for attention's two products over the
    context. Recomputed operations are not counted."""
    return 6 * params + 12 * layers * hidden * seq


def flash_attention_train(batch, heads, seq, head_dim, bytes_per_value=2):
    """(operations, bytes) of one layer's causal attention, forward and
    backward, for the rows and heads one device holds.

    Operations: forward QK^T and PV, backward dV, dP, dQ and dK: six
    products of 2 * seq * seq * head_dim each for a head, halved because a
    causal mask leaves half the square. The backward pass's recomputation
    of QK^T is not needed by the algorithm and is not counted.
    Bytes: forward reads q, k, v and writes o; backward reads q, k, v, o
    and do and writes dq, dk, dv: twelve tensors of batch*seq*heads*dim."""
    ops = 6 * 2 * batch * heads * seq * seq * head_dim / 2
    moved = 12 * batch * seq * heads * head_dim * bytes_per_value
    return ops, moved


def roofline_seconds(ops, moved, peaks):
    """The least time the chip could take, and which peak bounds it."""
    by_compute = ops / peaks["bf16_flops_per_s"]
    by_memory = moved / peaks["hbm_bytes_per_s"]
    return max(by_compute, by_memory), \
        "compute" if by_compute >= by_memory else "memory"
