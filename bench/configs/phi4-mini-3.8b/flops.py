"""Model FLOPs of the dense decoder of ``config.json``, from its shapes:
two per multiply-add of every matrix product a token needs (embedding
lookups need none), and attention's two products over the context."""
from __future__ import annotations

from typing import Any, Dict


def matmul_params(cfg: Dict[str, Any]) -> int:
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    f, v, L = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]
    per_layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * f
    return L * per_layer + d * v          # tied embedding as output head


def attention_flops(cfg: Dict[str, Any], context: int) -> int:
    """QK^T and attention-weighted V for one query over ``context`` keys."""
    return 4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * context


def decode_token(cfg: Dict[str, Any], context: int) -> int:
    """One generated token whose query attends ``context`` positions."""
    return 2 * matmul_params(cfg) + attention_flops(cfg, context)


def prefill(cfg: Dict[str, Any], n: int) -> int:
    """A prompt of ``n`` tokens, causal: query i attends i+1 positions.
    The output head runs for the last position only."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    body = 2 * (matmul_params(cfg) - d * v) * n
    return body + 2 * d * v + attention_flops(cfg, 1) * n * (n + 1) // 2
