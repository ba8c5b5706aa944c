"""Pure-jnp oracle for single-token decode attention against a KV cache."""

from __future__ import annotations

import jax.numpy as jnp


def decode_reference(q, cache_k, cache_v, valid, *, pos=None, window=None,
                     chunk=None, rolling=False, scale=None, dv=None):
    """q: [B, H, D]; cache_k/v: [B, S, KVH, D]; valid: [B] (# live slots);
    pos: [B] absolute position of the current token (needed for window /
    chunk masks on non-rolling caches).  Returns [B, H, D]; with
    ``cache_v`` None the values are ``cache_k[..., :dv]`` and it returns
    [B, H, dv].
    """
    if cache_v is None:
        cache_v = cache_k[..., :dv or cache_k.shape[-1]]
    b, h, d = q.shape
    _, s, kvh, _ = cache_k.shape
    group = h // kvh
    scale = scale if scale is not None else d ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = jnp.repeat(cache_k.astype(jnp.float32), group, axis=2)
    vf = jnp.repeat(cache_v.astype(jnp.float32), group, axis=2)
    logits = jnp.einsum("bhd,bshd->bhs", qf, kf)
    k_pos = jnp.arange(s)[None, :]                       # [1, S]
    mask = k_pos < valid[:, None]
    if not rolling and pos is not None:
        if window is not None:
            mask &= k_pos > (pos[:, None] - window)
        if chunk is not None:
            mask &= (k_pos // chunk) == (pos[:, None] // chunk)
    logits = jnp.where(mask[:, None, :], logits, -jnp.inf)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bhs,bshd->bhd", p, vf).astype(q.dtype)
