"""Operations and bytes the work needs, from shapes alone.

Model operations count what the forward (and, for training, backward)
pass requires: two operations per multiply-add of every weight matrix
and of every attended (query, key) pair; recomputation under remat is
not counted, and neither are the masked-out scores the program computes.
Kernel bytes come from the compiled programs' call sites
(``trace.hlo_sites``).
"""

from __future__ import annotations

from typing import Dict


def layer_matmul_params(dims: Dict) -> int:
    d, h, kv, hd, ff = (dims["d_model"], dims["heads"], dims["kv_heads"],
                        dims["head_dim"], dims["d_ff"])
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * ff


def matmul_params(dims: Dict) -> int:
    """Weights that a token multiplies: the layers and the head (the
    embedding is a lookup)."""
    return dims["layers"] * layer_matmul_params(dims) \
        + dims["d_model"] * dims["vocab"]


def attended_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of causal attention over ``seq`` positions,
    keys at most ``window`` back."""
    w = window or seq
    if seq <= w:
        return seq * (seq + 1) // 2
    return w * (w + 1) // 2 + (seq - w) * w


def attn_flops_per_pair(dims: Dict) -> int:
    """Scores and the weighted sum: 2 x 2 x head_dim per head."""
    return 4 * dims["heads"] * dims["head_dim"]


def train_flops(dims: Dict, batch: int, seq: int) -> float:
    """One training step of ``batch`` sequences: forward and backward
    (three times the forward)."""
    fwd = 2 * matmul_params(dims) * batch * seq \
        + attn_flops_per_pair(dims) * dims["layers"] * batch \
        * attended_pairs(seq, dims["window"])
    return 3.0 * fwd


def prefill_flops(dims: Dict, prompt: int) -> float:
    """A prompt's prefill: every position through the layers, the head
    for the last position only."""
    head = dims["d_model"] * dims["vocab"]
    return 2.0 * (matmul_params(dims) - head) * prompt + 2.0 * head \
        + attn_flops_per_pair(dims) * dims["layers"] \
        * attended_pairs(prompt, dims["window"])


def decode_flops(dims: Dict, tokens: int, context: int) -> float:
    """``tokens`` decoded tokens whose attended contexts add up to
    ``context`` positions."""
    return 2.0 * matmul_params(dims) * tokens \
        + attn_flops_per_pair(dims) * dims["layers"] * context


def serve_flops(dims: Dict, work: Dict) -> float:
    return decode_flops(dims, work["decode_tokens"],
                        work["decode_context"]) \
        + sum(prefill_flops(dims, p) for p in work["prefill"])
