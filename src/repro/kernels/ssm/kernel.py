"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU-native adaptation of the SSD algorithm (Dao & Gu 2024): the sequence is
split into chunks; each (batch x head, chunk) program computes

- the *intra-chunk* quadratic part on the MXU:
  ``Y_intra = ((C B^T) ⊙ exp(La[t]-La[s]) ⊙ (s<=t)) @ Xbar``;
- the *inter-chunk* contribution ``Y_inter = (C ⊙ e^{La}) @ S0``;
- the chunk-state recurrence ``S' = e^{La_end} S0 + (B ⊙ e^{La_end-La})^T Xbar``
  carried in VMEM scratch across the sequential chunk axis.

All decay exponents are differences ``La[t] - La[s]`` with ``s <= t`` and a
monotonically decreasing ``La``, so every exponent is <= 0 — overflow-safe.
Cumulative sums use lower-triangular ones matmuls (MXU) rather than an
unsupported in-kernel scan.

VMEM working set at C=128, N=128, P=64: three [C,N]/[C,P] tiles + the
[C, C] score matrix + the [N, P] state ~ 0.6 MB fp32.

Validated on CPU in interpret mode against ``ref.ssd_reference``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LOG_A_MIN = -60.0


def _ssd_kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, d_ref, y_ref, s_ref, *,
                block_t: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[...].astype(jnp.float32)            # [C, P]
    dt = dt_ref[...].astype(jnp.float32)          # [C, 1]
    a_log = alog_ref[...].astype(jnp.float32)     # [1, 1]
    b = b_ref[...].astype(jnp.float32)            # [C, N]
    c = c_ref[...].astype(jnp.float32)            # [C, N]
    d = d_ref[...].astype(jnp.float32)            # [1, 1]

    loga = jnp.clip(-jnp.exp(a_log[0, 0]) * dt, LOG_A_MIN, 0.0)  # [C, 1]

    cc = block_t
    row = jax.lax.broadcasted_iota(jnp.int32, (cc, cc), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (cc, cc), 1)
    tril_inc = (col <= row).astype(jnp.float32)
    la = tril_inc @ loga                          # [C, 1] inclusive cumsum

    # intra-chunk quadratic part (s <= t, diagonal included)
    decay = jnp.exp(jnp.minimum(la - la.T, 0.0))  # [C, C]
    scores = (c @ b.T) * decay * tril_inc
    xbar = dt * x                                 # [C, P]
    y_intra = scores @ xbar                       # MXU

    # inter-chunk
    s0 = s_ref[...]                               # [N, P]
    y_inter = (c * jnp.exp(la)) @ s0              # MXU [C,N]@[N,P]
    y_ref[...] = (y_intra + y_inter + d[0, 0] * x).astype(y_ref.dtype)

    # state update
    la_end = la[cc - 1, 0]
    b_dec = b * jnp.exp(jnp.minimum(la_end - la, 0.0))
    s_ref[...] = jnp.exp(la_end) * s0 + b_dec.T @ xbar


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ssd_pallas(x, dt, a_log, b, c, d, *, block_t: int = 128,
               interpret: bool = False):
    """x: [B,T,H,P]; dt: [B,T,H]; a_log/d: [H]; b/c: [B,T,N] -> y [B,T,H,P].

    T must be a multiple of ``block_t``.  Chunk axis is sequential, state in
    VMEM scratch.  B/C are shared across heads (single SSD group).
    """
    bs, t, h, p = x.shape
    n = b.shape[-1]
    block_t = min(block_t, t)
    assert t % block_t == 0, (t, block_t)

    xf = x.transpose(0, 2, 1, 3).reshape(bs * h, t, p)
    dtf = dt.transpose(0, 2, 1).reshape(bs * h, t, 1)
    grid = (bs * h, t // block_t)

    kernel = functools.partial(_ssd_kernel, block_t=block_t)
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_t, p), lambda g, ci: (g, ci, 0)),
            pl.BlockSpec((None, block_t, 1), lambda g, ci: (g, ci, 0)),
            # per-head scalars as [H, 1, 1]: a (1, 1) block of an [H, 1]
            # array is not (8, 128)-tileable, a squeezed-head one is
            pl.BlockSpec((None, 1, 1), lambda g, ci, h=h: (g % h, 0, 0)),
            pl.BlockSpec((None, block_t, n), lambda g, ci, h=h: (g // h, ci, 0)),
            pl.BlockSpec((None, block_t, n), lambda g, ci, h=h: (g // h, ci, 0)),
            pl.BlockSpec((None, 1, 1), lambda g, ci, h=h: (g % h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_t, p), lambda g, ci: (g, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((bs * h, t, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xf, dtf, a_log.reshape(h, 1, 1), b, c, d.reshape(h, 1, 1))
    return y.reshape(bs, h, t, p).transpose(0, 2, 1, 3)
