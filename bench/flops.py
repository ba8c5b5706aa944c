"""Operations and bytes that the work needs, counted from shapes.

Model FLOPs count the multiply-adds of the algorithm once (two FLOPs
each): the parameter matmuls and the attention products.  A training
token costs three forward passes' worth (forward, and the backward's two
products); recomputation under rematerialisation does not count.  The
prefill step unembeds only each row's last position, as the program's
serving prefill does.

Kernel bytes are what the algorithm must move between HBM and the chip
at least: its inputs once and its output once, in the kernel's dtypes
(bfloat16 activations and KV cache).  A decode step attends only to the
positions already written, not to the whole cache buffer.
"""

from __future__ import annotations

BF16 = 2


def matmul_params(cfg: dict) -> tuple[int, int]:
    """(parameters in the blocks' matmuls, parameters of the output
    head's matmul)."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    f = cfg["intermediate_size"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer, d * cfg["vocab_size"]


def _window(cfg: dict, ctx: int) -> int:
    w = cfg.get("sliding_window")
    return min(ctx, w) if w else ctx


def attended_pairs(cfg: dict, seq: int) -> int:
    """(query, key) pairs a causal, possibly windowed, sequence scores."""
    return sum(_window(cfg, t + 1) for t in range(seq))


def _attn_flops(cfg: dict, pairs: int) -> int:
    # QK^T and PV, per query head and layer
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
            * cfg["num_hidden_layers"])


def train_flops(cfg: dict, batch: int, seq: int) -> int:
    blocks, head = matmul_params(cfg)
    fwd = (2 * (blocks + head) * batch * seq
           + _attn_flops(cfg, batch * attended_pairs(cfg, seq)))
    return 3 * fwd


def prefill_flops(cfg: dict, batch: int, seq: int) -> int:
    blocks, head = matmul_params(cfg)
    return (2 * blocks * batch * seq + 2 * head * batch
            + _attn_flops(cfg, batch * attended_pairs(cfg, seq)))


def decode_flops(cfg: dict, batch: int, ctx: float) -> float:
    """One token per row against ``ctx`` cached positions (the new one
    included)."""
    blocks, head = matmul_params(cfg)
    return (2 * (blocks + head) * batch
            + _attn_flops(cfg, batch * _window(cfg, ctx)))


def flash_cost(cfg: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's flash attention call on
    [batch, seq] queries, causal."""
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    flops = 4 * h * hd * batch * attended_pairs(cfg, seq)
    q_o = 2 * batch * seq * h * hd
    k_v = 2 * batch * seq * kvh * hd
    return flops, (q_o + k_v) * BF16


def decode_attn_cost(cfg: dict, batch: int, ctx: float) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's decode attention call: one query per
    row against ``ctx`` written cache positions."""
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    live = _window(cfg, ctx)
    flops = 4 * h * hd * batch * live
    q_o = 2 * batch * h * hd
    k_v = 2 * batch * live * kvh * hd
    return flops, (q_o + k_v) * BF16


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
