"""Model FLOPs of the Mamba-2 language model of ``config.json``, from its
shapes: two per multiply-add of every matrix product a token needs (the
embedding lookup needs none), the causal convolution, and the
state-space recurrence in its linear form (per token and head, the
state update dt x B^T and the read-out of C); training counts the
backward pass as twice the forward."""
from __future__ import annotations

from typing import Any, Dict


def forward_token(cfg: Dict[str, Any]) -> int:
    d, L, v = cfg["d_model"], cfg["n_layer"], cfg["vocab_size"]
    d_in = cfg["expand"] * d
    nh = d_in // cfg["headdim"]
    n, cw = cfg["d_state"], cfg["d_conv"]
    proj = d * (2 * d_in + 2 * n + nh) + d_in * d
    conv = cw * (d_in + 2 * n)
    ssm = 2 * d_in * n                 # state update and read-out, MACs
    return 2 * (L * (proj + conv + ssm) + d * v)


def train_token(cfg: Dict[str, Any]) -> int:
    return 3 * forward_token(cfg)
