"""Gradients for the Pallas kernels.

A ``pallas_call`` has no transpose rule, so ``jax.grad`` through a kernel
fails as soon as a train step runs it on the chip.  ``reference_vjp`` runs
the kernel forward and differentiates a jnp implementation of the same
function for the backward pass.
"""

from __future__ import annotations

import jax


def reference_vjp(kernel, reference, *args):
    """``kernel(*args)``, with the VJP of ``reference`` (same semantics)."""
    @jax.custom_vjp
    def f(*a):
        return kernel(*a)

    def fwd(*a):
        return kernel(*a), a

    def bwd(a, g):
        return jax.vjp(reference, *a)[1](g)

    f.defvjp(fwd, bwd)
    return f(*args)
