"""Building blocks of the plain reference, shared by the architecture
modules of ``bench/arch``: straightforward ``jax.numpy``, no kernels.

``precision="float32"`` is the reference: float32 everywhere, matmuls at
``Precision.HIGHEST`` (a float32 matmul on a TPU otherwise runs in
bfloat16 passes).  Any other precision is the control, the step below
the bfloat16 compute the configurations state: every matmul takes int8
operands (the weights rounded by the architecture's ``cast_params``, the
inputs here, one scale per row) and sums in float32, and the activations
between them are bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def quant_int8(w, red_axis):
    """Symmetric int8, one scale per slice across ``red_axis`` (the axis
    a matmul reduces over), returned as the float32 values it stands
    for."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=red_axis, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(w / jnp.maximum(scale, 1e-30)), -127, 127)
    # straight through: the control's gradient is the rounded values'
    return w + jax.lax.stop_gradient(q * scale - w)


def mm(a, b, precision):
    """A layer's matmul; under the control its input is rounded to int8
    per row (``b`` was rounded by ``cast_params``), and the exact int8
    products are summed in float32."""
    if precision != "float32":
        a = quant_int8(a, -1)
    return jnp.matmul(a, b, precision=HIGHEST)


def act(x, precision):
    """An activation between two matmuls, in the precision's type."""
    return x if precision == "float32" else x.astype(jnp.bfloat16)


def rms(x, w, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: [B, S, H, D], positions 0..S-1, rotate-half form over all of
    ``D``."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
