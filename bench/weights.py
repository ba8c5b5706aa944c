"""Weights and inputs from the seed: the yardstick's own generators.

The parameter tree is the ``layout`` of the configuration's architecture
module (``bench/arch``), which follows the tree the program reads; every
size comes from the configuration file under ``bench/configs``.  Weights
are made on the device in one jitted call, in float32, the type the
program's train state holds them in.

Prompts and training batches are the ones ``Model.make_batch`` draws
from a key: uniform token ids from ``jax.random.randint`` on the first
and second of four split keys.  The harness names the key (an index it
owns); this module draws the same ids again for the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import arch

#: the largest value a task index may take: the program seeds its batches
#: with ``PRNGKey(100 + i)``, which keeps only 32 bits
INDEX_SPAN = 1 << 30


def seed_key(seed: int) -> jax.Array:
    """A key from all bits of ``seed`` (``PRNGKey`` alone keeps 32)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return out


def _draw(key, lay: dict) -> dict:
    flat = {}
    for n, (path, (shape, kind, std)) in enumerate(sorted(lay.items())):
        x = jax.random.normal(jax.random.fold_in(key, n), shape,
                              jnp.float32) * std
        flat[path] = x + 1.0 if kind == "scale" else x
    return _nest(flat)


@functools.partial(jax.jit, static_argnums=(1,))
def _draw_jit(key, lay: tuple) -> dict:
    return _draw(key, dict(lay))


def make_params(cfg: dict, seed: int) -> dict:
    """The parameter tree for ``seed``, made on the default device."""
    lay = arch.of(cfg).layout(cfg)
    return _draw_jit(seed_key(seed), tuple(sorted(lay.items())))


def params_fn(cfg: dict):
    """``key -> params`` unjitted, for callers that fuse it into a larger
    jitted function (a fresh train state, a difference of parameters)."""
    lay = arch.of(cfg).layout(cfg)
    return lambda key: _draw(key, lay)


def tokens(index: int, batch: int, seq: int, vocab: int):
    """Token ids and labels that ``Model.make_batch`` draws for
    ``PRNGKey(index)``."""
    ks = jax.random.split(jax.random.PRNGKey(index), 4)
    return (jax.random.randint(ks[0], (batch, seq), 0, vocab),
            jax.random.randint(ks[1], (batch, seq), 0, vocab))
