"""Operations the algorithm needs, computed from shapes.

Counts are the model's: forward + backward of every matrix product and
of causal attention.  Recomputation (remat), the optimizer's elementwise
update, norms, rope and the embedding lookup are NOT counted, so a share
of peak built on these cannot be raised by doing work twice.
"""
from __future__ import annotations


def dense_matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product, per token, of a
    Llama-shaped decoder (q/k/v/o, SwiGLU gate/up/down, output head).
    The embedding is a lookup and is left out."""
    h = cfg["hidden_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q = h * cfg["num_attention_heads"] * hd
    kv = 2 * h * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * h
    mlp = 3 * h * cfg["intermediate_size"]
    return cfg["num_hidden_layers"] * (q + kv + o + mlp) \
        + h * cfg["vocab_size"]


def causal_attention_flops_fwd(cfg: dict, seq: int) -> float:
    """QK^T and PV of one sequence over all layers, causal: half of the
    2 * 2 * S^2 * heads * head_dim of full attention."""
    h = cfg["hidden_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    return cfg["num_hidden_layers"] * 2.0 * seq * seq \
        * cfg["num_attention_heads"] * hd


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward (3x forward) per trained token."""
    matmul = 2.0 * dense_matmul_params(cfg)
    attn = causal_attention_flops_fwd(cfg, seq) / seq
    return 3.0 * (matmul + attn)


def mfu_percent(flops_per_token: float, tokens_per_s: float, chips: int,
                peak_flops_per_s: float) -> float:
    return 100.0 * flops_per_token * tokens_per_s / (chips * peak_flops_per_s)
