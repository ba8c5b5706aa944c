"""Pallas TPU flash-attention kernel (forward).

TPU-native adaptation: a 3-D grid (batch*heads, q_blocks, kv_blocks) in
which the kv axis is the innermost ("arbitrary") dimension.  Each (bh, qi)
program streams KV tiles HBM->VMEM through BlockSpec pipelining and keeps
the running (max, denominator, accumulator) in VMEM scratch, so the VMEM
working set is ``block_q x d + 2 x block_k x d + block_q x (d + 2)`` —
sized well under v5e VMEM with MXU-aligned (multiple-of-128) matmul dims.

Masks: causal, sliding-window, chunked-local (block-diagonal, llama4).
Fully-masked KV tiles are skipped with `pl.when` (the 2x causal FLOP
saving).

Validated on CPU in interpret mode against `ref.mha_reference`
(tests/test_kernel_flash_attention.py); the compiled path targets TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale, block_q, block_k, seq_q, seq_kv, causal, window,
               chunk, q_offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_kv = pl.num_programs(2)

    # tile bounds as scalars: Mosaic cannot index a vector by position
    q_lo = qi * block_q + q_offset
    q_hi = q_lo + block_q - 1
    k_lo = ki * block_k
    k_hi = k_lo + block_k - 1

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile-level early-out: skip tiles fully outside the mask
    live = (k_lo < seq_kv)
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= k_hi > q_lo - window
    if chunk is not None:
        live &= (((k_lo // chunk) <= (q_hi // chunk))
                 & ((k_hi // chunk) >= (q_lo // chunk)))

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale       # [bq, d]
        k = k_ref[...].astype(jnp.float32)               # [bk, d]
        v = v_ref[...].astype(jnp.float32)
        s = q @ k.T                                      # [bq, bk] on the MXU
        q_pos = q_lo + jax.lax.iota(jnp.int32, block_q)
        k_pos = k_lo + jax.lax.iota(jnp.int32, block_k)
        mask = (k_pos[None, :] < seq_kv) & (q_pos[:, None] < seq_q + q_offset)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        if chunk is not None:
            mask &= (k_pos[None, :] // chunk) == (q_pos[:, None] // chunk)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1)[:, None])  # [bq, 1]
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                  # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(ki == num_kv - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "chunk", "q_offset", "block_q",
                     "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=None, chunk=None,
                           q_offset=0, block_q=128, block_k=128,
                           interpret=False):
    """q: [B, Sq, H, D]; k/v: [B, Skv, KVH, D].  GQA by index-map folding:
    q-head ``bh`` reads kv row ``bh // (H // KVH)``."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    group = h // kvh
    scale = d ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, skv, d)

    grid = (b * h, pl.cdiv(sq, block_q), pl.cdiv(skv, block_k))

    kernel = functools.partial(
        _fa_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=sq, seq_kv=skv, causal=causal, window=window, chunk=chunk,
        q_offset=q_offset)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            # m, l, acc live in VMEM across kv grid steps
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
