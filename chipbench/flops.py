"""Operations and bytes that the algorithm needs, from shapes alone.

Only the work the algorithm needs is counted: real KV lengths, not the
pages a kernel walks; real query heads, not padded rows; the logits of
the last prompt position only in prefill, as the serving path needs.  A
share of a peak computed from these can therefore not pass 100% unless
the time leaves out part of the work.
"""
from __future__ import annotations

from typing import Dict, Iterable, Sequence


def layer_params(m: Dict) -> int:
    """Matrix parameters of one dense GQA layer (norms left out)."""
    d, h, hkv, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                         m["head_dim"], m["d_ff"])
    return d * hd * (2 * h + 2 * hkv) + 3 * d * ff


def head_params(m: Dict) -> int:
    return m["d_model"] * m["vocab_size"]


def attn_flops(m: Dict, q_len: int, kv_len: int, causal: bool) -> int:
    """QK^T and PV of one sequence in one layer."""
    h, hd = m["num_heads"], m["head_dim"]
    if causal:      # query i sees kv_len - q_len + i + 1 keys
        pairs = q_len * (kv_len - q_len) + q_len * (q_len + 1) // 2
    else:
        pairs = q_len * kv_len
    return 4 * h * hd * pairs


def decode_step_flops(m: Dict, lengths: Iterable[int]) -> int:
    """One decode step over the active slots; ``lengths`` are the keys
    each new token attends to (its own included)."""
    lengths = list(lengths)
    per_tok = 2 * (m["num_layers"] * layer_params(m) + head_params(m))
    attn = sum(m["num_layers"] * attn_flops(m, 1, L, False) for L in lengths)
    return per_tok * len(lengths) + attn


def prefill_flops(m: Dict, batch: int, seq: int) -> int:
    body = 2 * m["num_layers"] * layer_params(m) * batch * seq
    attn = m["num_layers"] * batch * attn_flops(m, seq, seq, True)
    return body + attn + 2 * head_params(m) * batch


def paged_attention_cost(m: Dict, lengths: Sequence[int],
                         kv_bytes: int = 2, q_bytes: int = 2,
                         out_bytes: int = 4) -> Dict[str, int]:
    """One layer's paged decode attention over the active slots: read
    each slot's K and V at its real length, its query, write its output."""
    h, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    tokens = sum(lengths)
    return {"flops": 4 * h * hd * tokens,
            "bytes": (2 * hkv * hd * kv_bytes * tokens
                      + len(lengths) * h * hd * (q_bytes + out_bytes))}


def flash_attention_cost(m: Dict, batch: int, seq: int,
                         itemsize: int = 2) -> Dict[str, int]:
    """One layer's causal prefill attention: read q, k, v, write out."""
    h, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return {"flops": batch * attn_flops(m, seq, seq, True),
            "bytes": batch * seq * hd * (2 * h + 2 * hkv) * itemsize}


def roofline_seconds(cost: Dict[str, int], peaks: Dict[str, float]
                     ) -> Dict[str, float]:
    """The least time the chip could take, and which bound sets it."""
    t_flop = cost["flops"] / peaks["bf16_flop_s"]
    t_byte = cost["bytes"] / peaks["hbm_byte_s"]
    return {"seconds": max(t_flop, t_byte),
            "bound": "flops" if t_flop >= t_byte else "bytes"}
