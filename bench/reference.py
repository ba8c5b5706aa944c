"""Plain reference of the benchmark's decoder-only transformers.

Written from the published descriptions (Qwen2, arXiv 2407.10671; H2O
Danube, arXiv 2401.16818), in straightforward ``jax.numpy``: pre-norm
blocks with RMSNorm, rotary embeddings (rotate-half form), grouped-query
causal attention with an optional sliding window and QKV bias, a SwiGLU
MLP, and a tied or separate output head.  No kernels, no cache, no
batching tricks.  It reads only a configuration file and a parameter
tree in the layout of ``bench/weights.py``; it imports nothing of the
program under test.

``precision="float32"`` is the reference: float32 everywhere, matmuls at
``Precision.HIGHEST`` (a float32 matmul on a TPU otherwise runs in
bfloat16 passes).  ``precision="int8"`` is the control, the step below
the bfloat16 compute the configurations state: every matmul of the
layers and the head takes int8 operands (weights with one scale per
output channel, inputs with one scale per row) and sums in float32, and
the activations between them are bfloat16.

The training step is AdamW with global-norm clipping and a linear warmup,
as the traffic file's ``optimizer`` entry states it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
#: weight matrices the control quantises (norm scales and biases stay)
_MATRICES = ("wq", "wk", "wv", "wo", "gate", "up", "down")


def _quant_int8(w, red_axis):
    """Symmetric int8, one scale per slice across ``red_axis`` (the axis
    a matmul reduces over), returned as the float32 values it stands
    for."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=red_axis, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(w / jnp.maximum(scale, 1e-30)), -127, 127)
    # straight through: the control's gradient is the rounded values'
    return w + jax.lax.stop_gradient(q * scale - w)


def cast_params(params, precision: str):
    """The weights as the chosen precision computes with them."""
    if precision == "float32":
        return params
    out = jax.tree.map(lambda x: x, params)
    blocks = out["blocks"]
    # stacked [L, in, out] matrices: one scale per (layer, output column)
    for grp in ("attn", "mlp"):
        for k, w in blocks[grp].items():
            if k in _MATRICES:
                blocks[grp][k] = _quant_int8(w, -2)
    if "lm_head" in out:
        out["lm_head"] = _quant_int8(out["lm_head"], -2)
    # the embedding table is also the tied head: one scale per token row
    out["embedding"] = _quant_int8(out["embedding"], -1)
    return out


def _mm(a, b, precision):
    """A layer's matmul; under the control its input is rounded to int8
    per row (``b`` was rounded by ``cast_params``), and the exact int8
    products are summed in float32."""
    if precision != "float32":
        a = _quant_int8(a, -1)
    return jnp.matmul(a, b, precision=HIGHEST)


def _act(x, precision):
    return x if precision == "float32" else x.astype(jnp.bfloat16)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [B, S, H, D], positions 0..S-1, rotate-half form."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, h, cfg, precision):
    b, s, _ = h.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = _mm(h, p["wq"], precision)
    k = _mm(h, p["wk"], precision)
    v = _mm(h, p["wv"], precision)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(b, s, nh, hd), cfg["rope_theta"])
    k = _rope(k.reshape(b, s, nkv, hd), cfg["rope_theta"])
    v = v.reshape(b, s, nkv, hd)
    group = nh // nkv
    k = jnp.repeat(k, group, axis=2)          # query head h reads kv h//group
    v = jnp.repeat(v, group, axis=2)
    if precision == "float32":
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    else:
        sc = jnp.einsum("bqhd,bkhd->bhqk", _act(q, precision),
                        _act(k, precision), preferred_element_type=jnp.float32)
    sc = sc * hd ** -0.5
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    mask = kp <= qp
    if cfg.get("sliding_window"):
        mask &= kp > qp - cfg["sliding_window"]
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    if precision == "float32":
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=HIGHEST)
    else:
        o = jnp.einsum("bhqk,bkhd->bqhd", _act(pr, precision),
                       _act(v, precision), preferred_element_type=jnp.float32)
    return _mm(o.reshape(b, s, nh * hd), p["wo"], precision)


def hidden(params, tokens, cfg, precision="float32"):
    """Final-normed hidden states [B, S, d] for ``tokens`` [B, S]."""
    eps = cfg["rms_norm_eps"]
    x = params["embedding"][tokens].astype(jnp.float32)
    blocks = params["blocks"]

    def layer(x, p):
        h = _act(_rms(x, p["ln1"], eps), precision)
        x = x + _attention(p["attn"], h, cfg, precision)
        h = _act(_rms(x, p["ln2"], eps), precision)
        m = p["mlp"]
        g = _mm(h, m["gate"], precision)
        u = _mm(h, m["up"], precision)
        x = x + _mm(_act(jax.nn.silu(g) * u, precision), m["down"], precision)
        return _act(x, precision).astype(jnp.float32), None

    x, _ = jax.lax.scan(layer, x, blocks)
    return _rms(x, params["final_norm"], eps)


def unembed(params, h, cfg, precision="float32"):
    w = (params["embedding"].T if cfg["tie_word_embeddings"]
         else params["lm_head"])
    return _mm(_act(h, precision), w, precision)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _logits_at(params, tokens, where, cfg_items, precision):
    cfg = dict(cfg_items)
    h = hidden(cast_params(params, precision), tokens, cfg, precision)
    h = jnp.take_along_axis(h, where[:, :, None], axis=1)    # [B, P, d]
    return unembed(cast_params(params, precision), h, cfg, precision)


def _items(cfg: dict) -> tuple:
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "head_dim", "num_attention_heads", "num_key_value_heads",
            "vocab_size", "tie_word_embeddings", "rope_theta",
            "rms_norm_eps", "sliding_window")
    return tuple((k, cfg.get(k)) for k in keys)


def logits_at(params, tokens, where, cfg, precision="float32"):
    """Logits [B, P, V] at positions ``where`` [B, P] of ``tokens``."""
    return _logits_at(params, tokens, where, _items(cfg), precision)


def loss(params, tokens, labels, cfg, precision="float32"):
    h = hidden(cast_params(params, precision), tokens, cfg, precision)
    lg = unembed(cast_params(params, precision), h, cfg, precision)
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
