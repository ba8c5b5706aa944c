"""Dispatching wrapper for flash attention.

Three interchangeable implementations with identical semantics:

- ``pallas``      the TPU kernel (kernel.py); interpret=True on CPU tests;
- ``jnp_chunked`` a lax.scan over KV blocks with running softmax — O(S x B)
                  memory, used for dry-run lowering so the compiled HLO has
                  flash-like memory behaviour (no S^2 intermediate);
- ``ref``         the O(S^2) oracle (ref.py).

``flash_attention`` picks per backend: pallas on TPU, jnp_chunked
elsewhere.  All take q [B, Sq, H, D], k/v [B, Skv, KVH, D].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


from . import ref
from ..grad import reference_vjp
from .kernel import flash_attention_pallas

NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=("causal", "window", "chunk",
                                             "q_offset", "block_k"))
def flash_attention_jnp(q, k, v, *, causal=True, window=None, chunk=None,
                        q_offset=0, block_k=512):
    """Streaming softmax over KV blocks in pure jnp (flash semantics).

    NB (§Perf H3, refuted): pinning the blocked tensors / scan carry to
    batch-only shardings here makes traffic WORSE (3x) — GSPMD's chosen
    layouts beat hand pins; the productive fix for small models is
    dropping TP entirely (see §Perf H4), not fighting layout assignment."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    group = h // kvh
    scale = d ** -0.5
    block_k = min(block_k, skv)
    nk = -(-skv // block_k)
    pad = nk * block_k - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # [b,h,sq,d]
    kb = k.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b, kvh, nk, block_k, d).transpose(2, 0, 1, 3, 4)        # [nk,b,kvh,bk,d]
    vb = v.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b, kvh, nk, block_k, d).transpose(2, 0, 1, 3, 4)

    q_pos = jnp.arange(sq) + q_offset

    def step(carry, xs):
        m, l, acc, ki = carry[0], carry[1], carry[2], carry[3]
        kblk, vblk = xs
        kblk = jnp.repeat(kblk, group, axis=1)                  # [b,h,bk,d]
        vblk = jnp.repeat(vblk, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kblk)
        k_pos = ki * block_k + jnp.arange(block_k)
        mask = k_pos[None, :] < skv
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        if chunk is not None:
            mask &= (k_pos[None, :] // chunk) == (q_pos[:, None] // chunk)
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk)
        return (m_new, l_new, acc_new, ki + 1), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, d), jnp.float32)
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, jnp.int32(0)),
                                     (kb, vb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def default_impl() -> str:
    """What ``impl="auto"`` runs: pallas on TPU, jnp otherwise
    (CPU tests, the dry-run)."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def flash_attention(q, k, v, *, causal=True, window=None, chunk=None,
                    q_offset=0, impl="auto", interpret=None):
    """Dispatch on ``impl`` (``default_impl()`` for "auto")."""
    if impl == "auto":
        impl = default_impl()
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        kw = dict(causal=causal, window=window, chunk=chunk,
                  q_offset=q_offset)
        return reference_vjp(
            lambda *a: flash_attention_pallas(*a, **kw, interpret=interpret),
            lambda *a: flash_attention_jnp(*a, **kw), q, k, v)
    if impl == "jnp":
        return flash_attention_jnp(q, k, v, causal=causal, window=window,
                                   chunk=chunk, q_offset=q_offset)
    if impl == "ref":
        return ref.mha_reference(q, k, v, causal=causal, window=window,
                                 chunk=chunk, q_offset=q_offset)
    raise ValueError(f"unknown impl {impl!r}")
