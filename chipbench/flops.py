"""Operations and bytes that the work needs, from a configuration's sizes
and the shapes of a call.  Kept with the benchmark so that every PR counts
them the same way.

A multiply-add is two operations.  Counted: every projection (query, key,
value, output, the gated MLP's two inputs and its output), attention over
the real context (a causal token at position ``p`` attends ``p + 1`` keys,
through both the score and the value product), and the LM head for each
token whose logits are computed.  Not counted: norms, RoPE, softmax and the
embedding gather, which are small beside these.
"""

from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    mlp = 3 * d * m["d_ff"]
    return attn + mlp


def attention_flops(m: dict, pairs: int) -> int:
    """Score and value products of all layers for ``pairs`` (query, key)
    pairs per head."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * pairs


def forward_flops(m: dict, *, tokens: int, pairs: int, head_tokens: int) -> int:
    """A forward pass over ``tokens`` tokens that attend ``pairs`` keys in
    all, computing logits for ``head_tokens`` of them."""
    dense = 2 * tokens * m["n_layers"] * layer_matmul_params(m)
    head = 2 * head_tokens * m["d_model"] * m["vocab_size"]
    return dense + attention_flops(m, pairs) + head


def causal_pairs(start: int, end: int) -> int:
    """(query, key) pairs of the positions ``start .. end - 1`` attending
    causally from position 0."""
    return (end * (end + 1) - start * (start + 1)) // 2


def prefill_flops(m: dict, prompt_len: int) -> int:
    """One sequence's prefill; logits for its last token only."""
    return forward_flops(m, tokens=prompt_len, pairs=causal_pairs(0, prompt_len),
                         head_tokens=1)


def decode_flops(m: dict, position: int) -> int:
    """One decoded token at ``position`` (it attends ``position + 1`` keys)."""
    return forward_flops(m, tokens=1, pairs=position + 1, head_tokens=1)


def sequence_flops(m: dict, prompt_len: int, n_out: int) -> int:
    """A request's whole work: its prefill, which yields token 0, then
    ``n_out - 1`` decode steps at positions ``prompt_len .. prompt_len + n_out - 2``."""
    dec = n_out - 1
    return (prefill_flops(m, prompt_len)
            + forward_flops(m, tokens=dec, pairs=causal_pairs(prompt_len, prompt_len + dec),
                            head_tokens=dec))


def flash_attention_cost(m: dict, *, batch: int, seq: int, bytes_per_el: int = 2):
    """(operations, bytes) of one causal flash-attention call over one layer:
    ``batch`` sequences of ``seq`` tokens.  Bytes: Q, K and V read once and
    the output written once."""
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    ops = 4 * batch * hq * hd * causal_pairs(0, seq)
    nbytes = bytes_per_el * batch * seq * hd * (2 * hq + 2 * hkv)
    return ops, nbytes


def least_time_s(ops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the longer of compute at peak and traffic at peak."""
    return max(ops / peak["flops_bf16"], nbytes / peak["hbm_bytes_per_s"])
