"""Dispatching wrapper for the Mamba2 SSD chunked scan.

- ``pallas``  TPU kernel (kernel.py); interpret=True on CPU tests;
- ``jnp``     chunk-parallel jnp implementation (same chunked math,
              vmapped over chunks + lax.scan over chunk states) — used for
              dry-run lowering;
- ``ref``     exact per-token recurrence (ref.py).

Also ``ssd_decode_step`` — O(1) single-token state update for serving.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from ..grad import reference_vjp
from .kernel import ssd_pallas, LOG_A_MIN


@functools.partial(jax.jit, static_argnames=("block_t",))
def ssd_jnp(x, dt, a_log, b, c, d, *, block_t: int = 128):
    bs, t, h, p = x.shape
    n = b.shape[-1]
    block_t = min(block_t, t)
    assert t % block_t == 0, (t, block_t)
    nc = t // block_t

    xf = x.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        bs * h, nc, block_t, p)
    dtf = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(
        bs * h, nc, block_t)
    af = -jnp.exp(a_log.astype(jnp.float32))       # [H]
    af = jnp.tile(af, bs)[:, None, None]           # [BH, 1, 1] (b-major flat)
    # NB: flat index is b*h + h_idx -> a per g = a_log[g % h]
    af = -jnp.exp(jnp.tile(a_log.astype(jnp.float32), (bs,)))[:, None, None]
    bf = jnp.repeat(b.astype(jnp.float32).reshape(bs, 1, nc, block_t, n),
                    h, axis=1).reshape(bs * h, nc, block_t, n)
    cf = jnp.repeat(c.astype(jnp.float32).reshape(bs, 1, nc, block_t, n),
                    h, axis=1).reshape(bs * h, nc, block_t, n)
    df = jnp.tile(d.astype(jnp.float32), (bs,))[:, None, None, None]

    loga = jnp.clip(af * dtf, LOG_A_MIN, 0.0)      # [BH, NC, C]
    la = jnp.cumsum(loga, axis=-1)

    tpos = jnp.arange(block_t)
    tril = (tpos[None, :] <= tpos[:, None]).astype(jnp.float32)

    decay = jnp.exp(jnp.minimum(la[..., :, None] - la[..., None, :], 0.0))
    scores = jnp.einsum("gctn,gcsn->gcts", cf, bf) * decay * tril
    xbar = dtf[..., None] * xf
    y_intra = jnp.einsum("gcts,gcsp->gctp", scores, xbar)

    la_end = la[..., -1]                           # [BH, NC]
    b_dec = bf * jnp.exp(jnp.minimum(
        la_end[..., None, None] - la[..., None], 0.0))
    chunk_s = jnp.einsum("gctn,gctp->gcnp", b_dec, xbar)
    a_chunk = jnp.exp(la_end)                      # [BH, NC]

    def step(s, xs):
        a, cs = xs
        out_s = s
        s = a[:, None, None] * s + cs
        return s, out_s

    s0 = jnp.zeros((bs * h, n, p), jnp.float32)
    _, s_in = jax.lax.scan(step, s0, (a_chunk.T, chunk_s.transpose(1, 0, 2, 3)))
    s_in = s_in.transpose(1, 0, 2, 3)
    y_inter = jnp.einsum("gctn,gcnp->gctp", cf * jnp.exp(la)[..., None], s_in)

    y = y_intra + y_inter + df * xf
    return y.reshape(bs, h, t, p).transpose(0, 2, 1, 3).astype(x.dtype)


def ssd(x, dt, a_log, b, c, d, *, impl: str = "auto", block_t: int = 128,
        interpret: bool | None = None):
    """Dispatch: pallas on TPU, chunked jnp otherwise (incl. dry-run)."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return reference_vjp(
            lambda *a: ssd_pallas(*a, block_t=block_t, interpret=interpret),
            lambda *a: ssd_jnp(*a, block_t=block_t), x, dt, a_log, b, c, d)
    if impl == "jnp":
        return ssd_jnp(x, dt, a_log, b, c, d, block_t=block_t)
    if impl == "ref":
        return ref.ssd_reference(x, dt, a_log, b, c, d)[0]
    raise ValueError(f"unknown impl {impl!r}")


def ssd_decode_step(state, x, dt, a_log, b, c, d):
    """O(1) single-token update.  state: [B, H, N, P]; x: [B, H, P];
    dt: [B, H]; b/c: [B, N]; a_log/d: [H].  Returns (y [B,H,P], new_state)."""
    sf = state.astype(jnp.float32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(jnp.clip(
        -jnp.exp(a_log.astype(jnp.float32))[None, :] * dtf, LOG_A_MIN, 0.0))
    xbar = dtf[..., None] * xf
    upd = b.astype(jnp.float32)[:, None, :, None] * xbar[:, :, None, :]
    new = decay[..., None, None] * sf + upd
    y = (jnp.einsum("bn,bhnp->bhp", c.astype(jnp.float32), new)
         + d.astype(jnp.float32)[None, :, None] * xf)
    return y.astype(x.dtype), new.astype(state.dtype)
