"""Operations and bytes the benchmark charges, computed from a
configuration's sizes alone (``perfbench/configs/*.json`` keys).

Model FLOPs follow the PaLM convention: 2 FLOPs per multiply-add,
forward = 2 x (matmul parameters per token) + attention, training = 3 x
forward (backward counted twice the forward), and no recomputed work.
Attention is charged over the whole context it attends to
(``12 * L * d_attn * T`` per trained token, PaLM appendix B). The
embedding gather is no matmul and is not charged; a tied LM head is.
"""
from __future__ import annotations


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"L": cfg["num_hidden_layers"], "d": d, "h": h,
            "kvh": cfg["num_key_value_heads"], "hd": hd,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "rank": cfg["sct"]["rank"] if cfg["sct"]["spectral_mlp"] else None}


def matmul_params_per_layer(cfg: dict) -> int:
    """Weights one token multiplies through in one layer: the dense
    q/k/v/o projections and the MLP's three projections, spectral
    (``k * (m + n)`` each) where the configuration says so."""
    g = dims(cfg)
    attn = g["d"] * g["h"] * g["hd"] * 2 + g["d"] * g["kvh"] * g["hd"] * 2
    if g["rank"] is None:
        mlp = 3 * g["d"] * g["ff"]
    else:
        mlp = 3 * g["rank"] * (g["d"] + g["ff"])
    return attn + mlp


def matmul_params(cfg: dict) -> int:
    """Matmul parameters per token across the model, LM head included."""
    g = dims(cfg)
    return g["L"] * matmul_params_per_layer(cfg) + g["V"] * g["d"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward model FLOPs per trained token at context
    ``seq``: ``6 N + 12 L (h hd) seq``."""
    g = dims(cfg)
    return 6.0 * matmul_params(cfg) + 12.0 * g["L"] * g["h"] * g["hd"] * seq


def forward_flops(cfg: dict, ctx: int) -> float:
    """Forward FLOPs of one token that attends to ``ctx`` positions
    (itself included): ``2 N + 4 L (h hd) ctx``."""
    g = dims(cfg)
    return 2.0 * matmul_params(cfg) + 4.0 * g["L"] * g["h"] * g["hd"] * ctx


def prompt_flops(cfg: dict, start: int, length: int) -> float:
    """Forward FLOPs of prompt positions ``[start, start + length)``,
    each attending causally to every earlier position and itself."""
    g = dims(cfg)
    ctx_sum = length * start + length * (length + 1) / 2.0
    return (2.0 * matmul_params(cfg) * length
            + 4.0 * g["L"] * g["h"] * g["hd"] * ctx_sum)


def paged_decode_terms(cfg: dict, ctx_lens, cache_bytes: int = 2) -> tuple:
    """(FLOPs, bytes) that the paged decode kernel must spend in one
    decode step, over every layer, for the decoding slots whose
    attention spans ``ctx_lens`` positions each (the new token
    included). Bytes: each position's K and V row of every kv head read
    once, plus the queries read and the outputs written. Copied from
    ``roofline/kernels.py:paged_gqa_decode_terms`` (paged branch)."""
    g = dims(cfg)
    rep = g["h"] // g["kvh"]
    flops = 0.0
    traffic = 0.0
    for n in ctx_lens:
        flops += 2 * 2 * g["kvh"] * rep * n * g["hd"]
        traffic += (2 * g["kvh"] * rep * g["hd"] * cache_bytes
                    + 2 * n * g["kvh"] * g["hd"] * cache_bytes)
    return g["L"] * flops, g["L"] * traffic
