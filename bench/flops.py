"""Operations and bytes from shapes: the model's FLOPs per token, and the
work of each attention kernel call.

The numbers are what the algorithm needs, whatever implements it:
matrix products count 2 operations per multiply-add; attention counts
only the keys a query may see (the causal half in prefill, each row's
valid cache length in decode); elementwise work (norms, softmax, RoPE)
is left out.  Sizes come from the configuration files under
``bench/configs``, with Hugging Face's key names.
"""

from __future__ import annotations

from typing import Dict, Iterable

BF16 = 2  # bytes


def dims(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "L": int(cfg["num_hidden_layers"]), "H": h,
            "K": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "ff": int(cfg["intermediate_size"]), "V": int(cfg["vocab_size"])}


def layer_matmul_flops(cfg: Dict) -> int:
    """Per token, one layer: q, k, v, o projections and the SwiGLU MLP."""
    m = dims(cfg)
    qo = 2 * 2 * m["d"] * m["H"] * m["hd"]
    kv = 2 * 2 * m["d"] * m["K"] * m["hd"]
    mlp = 3 * 2 * m["d"] * m["ff"]
    return qo + kv + mlp


def head_flops(cfg: Dict) -> int:
    """Per position whose logits are computed: the vocabulary projection."""
    m = dims(cfg)
    return 2 * m["d"] * m["V"]


def attn_flops(cfg: Dict, keys: int) -> int:
    """One query against ``keys`` keys, one layer: QK^T and PV."""
    m = dims(cfg)
    return 4 * m["H"] * m["hd"] * keys


def prefill_flops(cfg: Dict, prompt: int) -> int:
    """A prompt of ``prompt`` tokens: every layer for every token, causal
    attention, and logits at the last position only."""
    m = dims(cfg)
    causal_keys = prompt * (prompt + 1) // 2
    return (m["L"] * (prompt * layer_matmul_flops(cfg)
                      + attn_flops(cfg, causal_keys)) + head_flops(cfg))


def decode_flops(cfg: Dict, keys: int) -> int:
    """One generated token whose query sees ``keys`` cached positions
    (itself included)."""
    m = dims(cfg)
    return (m["L"] * (layer_matmul_flops(cfg) + attn_flops(cfg, keys))
            + head_flops(cfg))


def train_step_flops(cfg: Dict, batch: int, seq: int) -> int:
    """Forward and backward (3x the forward), logits at every position,
    no recomputation counted."""
    m = dims(cfg)
    tokens = batch * seq
    causal_keys = batch * seq * (seq + 1) // 2
    fwd = (m["L"] * (tokens * layer_matmul_flops(cfg)
                     + attn_flops(cfg, causal_keys))
           + tokens * head_flops(cfg))
    return 3 * fwd


# -- kernels ----------------------------------------------------------------

def flash_decode_work(cfg: Dict, lengths: Iterable[int]) -> Dict[str, int]:
    """One ``flash_decode`` call (one layer): the active rows' valid K/V,
    their query and their output; idle slots and positions beyond a row's
    length need nothing."""
    m = dims(cfg)
    lengths = list(lengths)
    kv_bytes = sum(2 * n * m["K"] * m["hd"] * BF16 for n in lengths)
    qo_bytes = 2 * len(lengths) * m["H"] * m["hd"] * BF16
    return {"flops": sum(attn_flops(cfg, n) for n in lengths),
            "bytes": kv_bytes + qo_bytes}


def flash_attention_work(cfg: Dict, batch: int, seq: int) -> Dict[str, int]:
    """One causal ``flash_attention`` forward call (one layer)."""
    m = dims(cfg)
    causal_keys = batch * seq * (seq + 1) // 2
    nbytes = batch * seq * (2 * m["H"] + 2 * m["K"]) * m["hd"] * BF16
    return {"flops": attn_flops(cfg, causal_keys), "bytes": nbytes}
