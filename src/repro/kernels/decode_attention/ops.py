"""Dispatching wrapper for decode attention + the distributed
flash-decoding combine.

- ``decode_attention``: per-device decode (pallas on TPU, jnp elsewhere).
  Under GSPMD with the KV cache sequence-sharded, the jnp einsum path
  compiles to a distributed softmax (all-reduce of max / sum) — the
  flash-decoding pattern — without gathering the cache.
- ``partial_decode`` + ``combine_partials``: explicit shard_map variant
  (psum log-sum-exp) used by the serving runtime when the cache is
  sequence-sharded along the `model` axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref
from .kernel import decode_attention_pallas

NEG_INF = -1e30


def decode_attention_jnp(q, cache_k, cache_v, valid, *, pos=None,
                         window=None, chunk=None, rolling=False):
    return ref.decode_reference(q, cache_k, cache_v, valid, pos=pos,
                                window=window, chunk=chunk, rolling=rolling)


def default_impl() -> str:
    """What ``impl="auto"`` runs: pallas on TPU, jnp elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def decode_attention(q, cache_k, cache_v, valid, *, pos=None, window=None,
                     chunk=None, rolling=False, impl="auto", interpret=None):
    if impl == "auto":
        impl = default_impl()
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return decode_attention_pallas(
            q, cache_k, cache_v, valid, pos=pos, window=window, chunk=chunk,
            rolling=rolling, interpret=interpret)
    return decode_attention_jnp(q, cache_k, cache_v, valid, pos=pos,
                                window=window, chunk=chunk, rolling=rolling)


# ---------------------------------------------------------------------------
# Explicit flash-decoding partials (for shard_map serving)
# ---------------------------------------------------------------------------

def partial_decode(q, k_shard, v_shard, shard_mask):
    """Unnormalised attention over one sequence shard.

    q: [B, H, D]; k/v_shard: [B, S_loc, KVH, D]; shard_mask: [B, S_loc]
    True for live slots.  Returns (acc [B,H,D], m [B,H], l [B,H]).
    """
    b, h, d = q.shape
    kvh = k_shard.shape[2]
    group = h // kvh
    qf = q.astype(jnp.float32) * (d ** -0.5)
    kf = jnp.repeat(k_shard.astype(jnp.float32), group, axis=2)
    vf = jnp.repeat(v_shard.astype(jnp.float32), group, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", qf, kf)
    s = jnp.where(shard_mask[:, None, :], s, NEG_INF)
    m = s.max(axis=-1)                                    # [B, H]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(shard_mask[:, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhs,bshd->bhd", p, vf)
    return acc, m, l


def combine_partials(acc, m, l, axis_name: str):
    """psum log-sum-exp combine across sequence shards (flash-decoding)."""
    m_glob = jax.lax.pmax(m, axis_name)                   # [B, H]
    corr = jnp.exp(m - m_glob)
    l_glob = jax.lax.psum(l * corr, axis_name)
    acc_glob = jax.lax.psum(acc * corr[..., None], axis_name)
    return acc_glob / jnp.maximum(l_glob, 1e-30)[..., None]
