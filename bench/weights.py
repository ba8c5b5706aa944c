"""Weights and inputs from the seed: the yardstick's own generators.

The parameter tree follows the layout the program's decoder-only
transformer reads (stacked layers under ``blocks``); every size comes
from the configuration file under ``bench/configs``.  Weights are made
on the device in one jitted call, in float32, the type the program's
train state holds them in.  Norm scales and QKV biases are drawn away
from the program's own initialisation (ones, zeros) so that a program
that ignored them would read as wrong.

Prompts and training batches are the ones ``Model.make_batch`` draws
from a key: uniform token ids from ``jax.random.randint`` on the first
and second of four split keys.  The harness names the key (an index it
owns); this module draws the same ids again for the reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the largest value a task index may take: the program seeds its batches
#: with ``PRNGKey(100 + i)``, which keeps only 32 bits
INDEX_SPAN = 1 << 30


def layout(cfg: dict) -> dict:
    """``{path: (shape, kind, std)}`` for every parameter leaf, where
    ``kind`` is ``normal`` (zero mean), ``scale`` (mean one) or ``bias``."""
    d = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    L = cfg["num_hidden_layers"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    v = cfg["vocab_size"]
    out = {
        ("embedding",): ((v, d), "normal", 0.02),
        ("final_norm",): ((d,), "scale", 0.05),
        ("blocks", "ln1"): ((L, d), "scale", 0.05),
        ("blocks", "ln2"): ((L, d), "scale", 0.05),
        ("blocks", "attn", "wq"): ((L, d, q), "normal", d ** -0.5),
        ("blocks", "attn", "wk"): ((L, d, kv), "normal", d ** -0.5),
        ("blocks", "attn", "wv"): ((L, d, kv), "normal", d ** -0.5),
        ("blocks", "attn", "wo"): ((L, q, d), "normal", q ** -0.5),
        ("blocks", "mlp", "gate"): ((L, d, f), "normal", d ** -0.5),
        ("blocks", "mlp", "up"): ((L, d, f), "normal", d ** -0.5),
        ("blocks", "mlp", "down"): ((L, f, d), "normal", f ** -0.5),
    }
    if not cfg["tie_word_embeddings"]:
        out[("lm_head",)] = ((d, v), "normal", d ** -0.5)
    if cfg.get("attention_bias"):
        out[("blocks", "attn", "bq")] = ((L, q), "bias", 0.02)
        out[("blocks", "attn", "bk")] = ((L, kv), "bias", 0.02)
        out[("blocks", "attn", "bv")] = ((L, kv), "bias", 0.02)
    return out


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
    return _draw_jit(seed_key(seed), tuple(sorted(layout(cfg).items())))


def params_fn(cfg: dict):
    """``key -> params`` unjitted, for callers that fuse it into a larger
    jitted function (a fresh train state, a difference of parameters)."""
    lay = layout(cfg)
    return lambda key: _draw(key, lay)


def tokens(index: int, batch: int, seq: int, vocab: int):
    """Token ids and labels that ``Model.make_batch`` draws for
    ``PRNGKey(index)``."""
    ks = jax.random.split(jax.random.PRNGKey(index), 4)
    return (jax.random.randint(ks[0], (batch, seq), 0, vocab),
            jax.random.randint(ks[1], (batch, seq), 0, vocab))
