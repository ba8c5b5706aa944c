"""Plain reference of the benchmark's models: the loss, logits and AdamW
step that the check compares the program's with.

The forward pass is the configuration's architecture module's
(``bench/arch``: ``hidden``, ``unembed`` and the control's
``cast_params``), built from ``bench/nn.py``; it reads only a
configuration file and a parameter tree in the module's layout, and
imports nothing of the program under test.

``precision="float32"`` is the reference: float32 everywhere, matmuls at
``Precision.HIGHEST``.  ``precision="int8"`` is the control, the step
below the bfloat16 compute the configurations state (``bench/nn.py``).

The training step is AdamW with global-norm clipping and a linear warmup,
as the traffic file's ``optimizer`` entry states it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import arch


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _logits_at(params, tokens, where, cfg_items, precision):
    cfg = dict(cfg_items)
    a = arch.of(cfg)
    h = a.hidden(a.cast_params(params, precision), tokens, cfg, precision)
    h = jnp.take_along_axis(h, where[:, :, None], axis=1)    # [B, P, d]
    return a.unembed(a.cast_params(params, precision), h, cfg, precision)


def _items(cfg: dict) -> tuple:
    """Every scalar entry of a configuration file, as a jit's static
    argument (its nested notes are not read)."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if v is None or isinstance(v, (bool, int, float,
                                                       str))))


def logits_at(params, tokens, where, cfg, precision="float32"):
    """Logits [B, P, V] at positions ``where`` [B, P] of ``tokens``."""
    return _logits_at(params, tokens, where, _items(cfg), precision)


def loss(params, tokens, labels, cfg, precision="float32"):
    a = arch.of(cfg)
    h = a.hidden(a.cast_params(params, precision), tokens, cfg, precision)
    lg = a.unembed(a.cast_params(params, precision), h, cfg, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


def _clipped_grad(params, tokens, labels, cfg, opt, precision):
    lval, g = jax.value_and_grad(loss)(params, tokens, labels, cfg,
                                       precision)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gnorm, 1e-12))
    return lval, jax.tree.map(lambda x: x * scale, g), gnorm


def leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.ravel())
                      for x in jax.tree.leaves(tree)])


@functools.partial(jax.jit, static_argnames=("cfg_items", "opt_items",
                                             "precision"),
                   donate_argnums=(0,))
def _train_step(state, tokens, labels, cfg_items, opt_items, precision):
    cfg, opt = dict(cfg_items), dict(opt_items)
    params, mu, nu, step = state
    lval, g, gnorm = _clipped_grad(params, tokens, labels, cfg, opt,
                                   precision)
    s = step.astype(jnp.float32)
    lr = opt["peak_lr"] * s / opt["warmup"]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    t = s + 1.0
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)

    def upd(p, m, v):
        wd = opt["weight_decay"] if p.ndim >= 2 else 0.0
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (u + wd * p)

    params = jax.tree.map(upd, params, mu, nu)
    return (params, mu, nu, step + 1), lval, leaf_norms(g), gnorm


@functools.partial(jax.jit, static_argnames=("cfg_items", "opt_items",
                                             "precision"))
def _grad_norms(params, tokens, labels, cfg_items, opt_items, precision):
    lval, g, gnorm = _clipped_grad(params, tokens, labels, dict(cfg_items),
                                   dict(opt_items), precision)
    return lval, leaf_norms(g), gnorm


def train_state(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return (params, z, jax.tree.map(jnp.zeros_like, params),
            jnp.zeros((), jnp.int32))


def train_step(state, tokens, labels, cfg, opt, precision="float32"):
    """One AdamW step; returns (state, loss, per-leaf norms of the clipped
    gradient, global norm of the gradient before clipping).  The learning rate is the linear warmup, which is all of
    the schedule an instance's few steps reach (the traffic file states
    the warmup)."""
    return _train_step(state, tokens, labels, _items(cfg),
                       tuple(sorted(opt.items())), precision)


def grad_norms(params, tokens, labels, cfg, opt, precision="float32"):
    """(loss, per-leaf norms of the clipped gradient, global norm before
    clipping) without a step."""
    return _grad_norms(params, tokens, labels, _items(cfg),
                       tuple(sorted(opt.items())), precision)
