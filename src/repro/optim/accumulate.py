"""Gradient accumulation with collective deferral.

``jax.lax.scan`` over microbatches inside one jit'd step: per-microbatch
gradients are summed locally; any data-parallel all-reduce happens ONCE on
the accumulated tensor (XLA hoists the psum out of the scan because the
reduction is linear), so ICI traffic is independent of the microbatch
count."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class GradAccumulator:
    num_microbatches: int

    def split(self, batch):
        """[B, ...] -> [n, B/n, ...] for every leaf."""
        n = self.num_microbatches

        def re(x):
            return x.reshape((n, x.shape[0] // n) + x.shape[1:])

        return jax.tree.map(re, batch)

    def grads(self, loss_fn, params, batch):
        """Mean loss, mean grads and summed statistics over microbatches
        (scanned); ``loss_fn`` returns (loss, stats)."""
        n = self.num_microbatches
        vg = jax.value_and_grad(loss_fn, has_aux=True)
        if n <= 1:
            (loss, stats), g = vg(params, batch)
            return loss, g, stats
        micro = self.split(batch)

        def body(carry, mb):
            loss_acc, g_acc, s_acc = carry
            (loss, stats), g = vg(params, mb)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(a.dtype), g_acc, g)
            s_acc = jax.tree.map(jnp.add, s_acc, stats)
            return (loss_acc + loss, g_acc, s_acc), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        s0 = jax.tree.map(jnp.zeros_like, jax.eval_shape(
            loss_fn, params, jax.tree.map(lambda x: x[0], micro))[1])
        (loss, g, stats), _ = jax.lax.scan(
            body, (jnp.zeros(()), g0, s0), micro)
        return loss / n, jax.tree.map(lambda x: x / n, g), stats
