"""Dispatching wrapper for the RWKV6 chunked scan.

- ``pallas``  the TPU kernel (kernel.py); interpret=True on CPU;
- ``jnp``     chunk-parallel jnp implementation (same math as the kernel,
              vmapped over chunks) — used for dry-run lowering so the HLO
              is chunk-structured rather than a T-step scan;
- ``ref``     the exact per-token recurrence (ref.py).

Also provides ``rwkv6_decode_step`` — the O(1) single-token state update
used by the serving path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from ..grad import reference_vjp
from .kernel import rwkv6_pallas, LOG_W_MIN


@functools.partial(jax.jit, static_argnames=("block_t",))
def rwkv6_jnp(r, k, v, w, u, *, block_t: int = 128):
    """Chunked linear attention in pure jnp (flash semantics, fp32 core)."""
    b, t, h, kk = r.shape
    block_t = min(block_t, t)
    assert t % block_t == 0, (t, block_t)
    nc = t // block_t

    def chunked(x):
        return (x.astype(jnp.float32).transpose(0, 2, 1, 3)
                .reshape(b * h, nc, block_t, kk))

    rc, kc, vc, wc = chunked(r), chunked(k), chunked(v), chunked(w)
    uf = jnp.broadcast_to(u.astype(jnp.float32)[None], (b, h, kk)
                          ).reshape(b * h, kk)

    logw = jnp.clip(jnp.log(jnp.maximum(wc, 1e-38)), LOG_W_MIN, 0.0)
    lw = jnp.cumsum(logw, axis=2)                  # [BH, NC, C, K] inclusive
    lw_prev = lw - logw

    c = block_t
    tpos = jnp.arange(c)
    strict = tpos[None, :] >= tpos[:, None]        # keep only s <= t-1

    # intra-chunk (vectorised over chunks)
    decay3 = jnp.exp(jnp.minimum(
        lw_prev[:, :, :, None, :] - lw[:, :, None, :, :], 0.0))
    prod = rc[:, :, :, None, :] * kc[:, :, None, :, :] * decay3
    scores = jnp.where(strict[None, None, :, :, None], 0.0, prod).sum(-1)
    bonus = jnp.einsum("gctk,gk->gct", rc * kc, uf)
    scores = scores + bonus[..., None] * jnp.eye(c, dtype=jnp.float32)
    o_intra = jnp.einsum("gcts,gcsk->gctk", scores, vc)

    # inter-chunk: scan the state across chunks
    l_end = lw[:, :, -1, :]                        # [BH, NC, K]
    k_dec = kc * jnp.exp(jnp.minimum(l_end[:, :, None, :] - lw, 0.0))
    chunk_kv = jnp.einsum("gctk,gctv->gckv", k_dec, vc)  # [BH, NC, K, V]
    a_chunk = jnp.exp(l_end)                       # [BH, NC, K]

    def step(s, xs):
        a, ckv = xs                                # [BH,K], [BH,K,V]
        out_s = s
        s = a[..., None] * s + ckv
        return s, out_s

    s0 = jnp.zeros((b * h, kk, kk), jnp.float32)
    _, s_in = jax.lax.scan(
        step, s0, (a_chunk.transpose(1, 0, 2), chunk_kv.transpose(1, 0, 2, 3)))
    s_in = s_in.transpose(1, 0, 2, 3)              # state entering each chunk
    o_inter = jnp.einsum("gctk,gckv->gctv", rc * jnp.exp(lw_prev), s_in)

    o = (o_intra + o_inter).reshape(b, h, t, kk).transpose(0, 2, 1, 3)
    return o.astype(r.dtype)


def rwkv6(r, k, v, w, u, *, impl: str = "auto", block_t: int = 128,
          interpret: bool | None = None):
    """Dispatch: pallas on TPU, chunked jnp otherwise (incl. dry-run)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return reference_vjp(
            lambda *a: rwkv6_pallas(*a, block_t=block_t, interpret=interpret),
            lambda *a: rwkv6_jnp(*a, block_t=block_t), r, k, v, w, u)
    if impl == "jnp":
        return rwkv6_jnp(r, k, v, w, u, block_t=block_t)
    if impl == "ref":
        return ref.rwkv6_reference(r, k, v, w, u)[0]
    raise ValueError(f"unknown impl {impl!r}")


def rwkv6_decode_step(state, r, k, v, w, u):
    """O(1) single-token update.  state: [B, H, K, V]; r/k/v/w: [B, H, K];
    u: [H, K].  Returns (o [B, H, V], new_state)."""
    sf = state.astype(jnp.float32)
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    kv = kf[..., :, None] * vf[..., None, :]
    o = jnp.einsum("bhi,bhij->bhj", rf,
                   sf + u.astype(jnp.float32)[None, :, :, None] * kv)
    new = wf[..., :, None] * sf + kv
    return o.astype(r.dtype), new.astype(state.dtype)
