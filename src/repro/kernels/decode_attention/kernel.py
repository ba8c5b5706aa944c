"""Pallas TPU flash-decoding kernel.

Grid: (batch x kv_heads, kv_blocks).  Each program owns one kv head's query
group ([group, D], padded to the 8-sublane MXU minimum), streams KV cache
tiles HBM->VMEM, and keeps running (m, l, acc) in VMEM scratch.  Without a
V cache the values are the keys' first ``dv`` lanes, read from the same
tile (latent attention's absorbed decode: one cache read, not two).  The
per-batch valid length arrives via a scalar-prefetch operand in SMEM so
fully-dead tiles are skipped (`pl.when`), which makes short-context decode
on a long cache cheap.

The distributed variant (KV cache sequence-sharded across the `model` mesh
axis with a psum log-sum-exp combine) lives in ops.sharded_decode — this
kernel is the per-shard workhorse.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(valid_ref, pos_ref, q_ref, k_ref, *refs, scale, block_k,
                   kvh, window, chunk, rolling, dv):
    if dv is None:
        v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    num_kv = pl.num_programs(1)
    b = bh // kvh
    valid = valid_ref[b]
    pos = pos_ref[b]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_lo = ki * block_k
    live = k_lo < valid
    if not rolling:
        if window is not None:
            live &= (k_lo + block_k - 1) > pos - window
        if chunk is not None:
            live &= (k_lo // chunk) <= (pos // chunk)
            live &= ((k_lo + block_k - 1) // chunk) >= (pos // chunk)

    @pl.when(live)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale        # [G, D]
        k = k_ref[...].astype(jnp.float32)                # [block_k, D]
        v = (v_ref[...].astype(jnp.float32) if dv is None
             else k[:, :dv])                              # [block_k, Dv]
        s = q @ k.T                                       # [G, block_k]
        k_pos = k_lo + jax.lax.iota(jnp.int32, block_k)
        mask = k_pos < valid
        if not rolling:
            if window is not None:
                mask &= k_pos > pos - window
            if chunk is not None:
                mask &= (k_pos // chunk) == (pos // chunk)
        s = jnp.where(mask[None, :], s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1)[:, None])
        p = jnp.exp(s - m_new)
        p = jnp.where(mask[None, :], p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)[:, None]
        acc_ref[...] = acc_ref[...] * alpha + p @ v
        m_ref[...] = m_new

    @pl.when(ki == num_kv - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "chunk", "rolling",
                                             "block_k", "interpret", "scale",
                                             "dv"))
def decode_attention_pallas(q, cache_k, cache_v, valid, *, pos=None,
                            window=None, chunk=None, rolling=False,
                            block_k=256, interpret=False, scale=None,
                            dv=None):
    """q: [B, H, D]; cache_k/v: [B, S, KVH, D]; valid/pos: [B] int32.
    ``scale`` defaults to ``D ** -0.5``.  With ``cache_v`` None the values
    are ``cache_k[..., :dv]`` and the output is [B, H, dv]."""
    b, h, d = q.shape
    _, s, kvh, _ = cache_k.shape
    group = h // kvh
    if scale is None:
        scale = d ** -0.5
    if cache_v is None:
        dv = dv or d
    else:
        dv = None                        # the V cache's own width, D
    do = dv or d
    block_k = min(block_k, s)
    if pos is None:
        pos = valid - 1

    # [B*KVH, G, D] query groups; pad G to the 8-sublane minimum
    qg = q.reshape(b, kvh, group, d).reshape(b * kvh, group, d)
    gpad = max(8, group)
    if gpad != group:
        qg = jnp.pad(qg, ((0, 0), (0, gpad - group), (0, 0)))
    kf = cache_k.transpose(0, 2, 1, 3).reshape(b * kvh, s, d)
    kv_spec = pl.BlockSpec((None, block_k, d), lambda bh, ki: (bh, ki, 0))
    operands, in_specs = [kf], [kv_spec]
    if cache_v is not None:
        operands.append(cache_v.transpose(0, 2, 1, 3).reshape(b * kvh, s, d))
        in_specs.append(kv_spec)

    grid = (b * kvh, pl.cdiv(s, block_k))
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, kvh=kvh,
        window=window, chunk=chunk, rolling=rolling, dv=dv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # valid
            pl.BlockSpec(memory_space=pltpu.SMEM),   # pos
            pl.BlockSpec((None, gpad, d), lambda bh, ki: (bh, 0, 0)),
            *in_specs,
        ],
        out_specs=pl.BlockSpec((None, gpad, do), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kvh, gpad, do), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((gpad, 1), jnp.float32),
            pltpu.VMEM((gpad, 1), jnp.float32),
            pltpu.VMEM((gpad, do), jnp.float32),
        ],
        interpret=interpret,
    )(valid.astype(jnp.int32), pos.astype(jnp.int32), qg, *operands)
    return out[:, :group].reshape(b, kvh, group, do).reshape(b, h, do)
