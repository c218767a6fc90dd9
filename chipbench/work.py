"""Operations and bytes that the work of a window needs, from its shapes.

These are the numerators of the model-FLOP utilisations and the kernels'
roofline shares.  They count what the model requires, not what today's
program happens to execute: a program that computes more (masked cache
slots, recomputation in the backward pass) does not raise them.
"""

from __future__ import annotations


def _sizes(c: dict):
    d, h = c["hidden_size"], c["num_attention_heads"]
    kv = c["num_key_value_heads"]
    dh = c.get("head_dim") or d // h
    return d, h, kv, dh, c["intermediate_size"], c["vocab_size"], c["num_hidden_layers"]


def layer_matmul_params(c: dict) -> int:
    """Weights one layer multiplies per token: q, k, v, o and the gated FFN."""
    d, h, kv, dh, f, _, _ = _sizes(c)
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def readout_params(c: dict) -> int:
    d, _, _, _, _, v, _ = _sizes(c)
    return d * v


def attention_flops(c: dict, context: int) -> int:
    """Forward FLOPs of one token's attention over ``context`` keys in all
    layers: q.k and p.v, two FLOPs per multiply-add."""
    _, h, _, dh, _, _, n = _sizes(c)
    return 4 * n * h * dh * context


def serve_request_flops(c: dict, batch: int, prompt_len: int, new_tokens: int) -> int:
    """Model FLOPs of one closed-loop request: a prefill of ``prompt_len``
    tokens whose last position is read out, then ``new_tokens - 1`` decode
    steps of one token each (the last sampled token is not fed back), each
    over its live causal context."""
    n = _sizes(c)[-1]
    dense = 2 * n * layer_matmul_params(c)
    head = 2 * readout_params(c)
    if new_tokens <= 0:
        return 0
    prefill = prompt_len * dense + head
    prefill += sum(attention_flops(c, t + 1) for t in range(prompt_len))
    decode = sum(dense + head + attention_flops(c, prompt_len + i + 1)
                 for i in range(new_tokens - 1))
    return batch * (prefill + decode)


def train_step_flops(c: dict, batch: int, seq_len: int) -> int:
    """Forward and backward FLOPs of one step, the PaLM formula (Chowdhery
    et al. 2022, appendix B): 6N per token for the N multiplied weights
    (the readout over the seq_len - 1 predicted positions) plus
    12·L·H·Dh·T for attention.  Recomputation is not counted."""
    _, h, _, dh, _, _, n = _sizes(c)
    tokens = batch * seq_len
    return (6 * n * layer_matmul_params(c) * tokens
            + 6 * readout_params(c) * batch * (seq_len - 1)
            + 12 * n * h * dh * seq_len * tokens)


def softmax_bytes(c: dict, batch: int, q_len: int) -> int:
    """HBM bytes of one attention softmax over its live causal scores: each
    score read once in float32 and each weight written once at the compute
    dtype's width.  Query i sees i + 1 keys."""
    h = c["num_attention_heads"]
    out = 2 if c["compute_dtype"] == "bfloat16" else 4
    scores = batch * h * q_len * (q_len + 1) // 2
    return scores * (4 + out)


def uniform_bytes(c: dict) -> int:
    """Bytes one slot's uniform draw writes: one float32 per vocabulary
    entry."""
    return 4 * c["vocab_size"]
