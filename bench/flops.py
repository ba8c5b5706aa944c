"""Operations and bytes that the work needs, counted from shapes: the
counting that architectures share.  Each architecture module
(``bench/arch``) counts its own model FLOPs and names its kernels' costs
with these.

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


def window(cfg: dict, ctx: int) -> int:
    """Positions a query sees among ``ctx``, under a sliding window."""
    w = cfg.get("sliding_window")
    return min(ctx, w) if w else ctx


def mean_context(shapes: dict) -> float:
    """Mean cached positions a decode step attends to: a rollout's step t
    (from 0) sees t + 1."""
    return (shapes["decode_steps"] + 1) / 2


def attended_pairs(cfg: dict, seq: int) -> int:
    """(query, key) pairs a causal, possibly windowed, sequence scores."""
    return sum(window(cfg, t + 1) for t in range(seq))


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
    live = window(cfg, ctx)
    flops = 4 * h * hd * batch * live
    q_o = 2 * batch * h * hd
    k_v = 2 * batch * live * kvh * hd
    return flops, (q_o + k_v) * BF16


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
