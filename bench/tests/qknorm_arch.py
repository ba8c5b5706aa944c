"""A test's own architecture (``"arch": "bench.tests.qknorm_arch"``): the
decoder with per-head RMSNorm on queries and keys and rotary embeddings
on a share of each head's width, as stablelm-2 (arXiv 2402.17834;
``qk_layernorm``, ``partial_rotary_factor`` 0.25) has them and the
program runs them.  It shows that an architecture comes to the benchmark
as new files alone: this module and a configuration file.

The norms act on each head after the projections and their biases and
before the rotation; the rotation turns the first ``rot`` dimensions of
a head (rotate-half form over them, frequencies from ``rot``) and leaves
the rest.  Norm scales are drawn away from ones, as the decoder's are.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from bench import nn
from bench.arch import decoder

cast_params = decoder.cast_params
unembed = decoder.unembed
# the norms add no matmul, and the kernels run at the decoder's shapes
step_flops = decoder.step_flops
kernel_costs = decoder.kernel_costs


def program_config(cfg: dict, log=None):
    return dataclasses.replace(decoder.program_config(cfg, log),
                               qk_norm=True,
                               rope_pct=cfg["partial_rotary_factor"])


def layout(cfg: dict) -> dict:
    out = decoder.layout(cfg)
    shape = (cfg["num_hidden_layers"], cfg["head_dim"])
    out[("blocks", "attn", "q_norm")] = (shape, "scale", 0.05)
    out[("blocks", "attn", "k_norm")] = (shape, "scale", 0.05)
    return out


def _partial_rope(x, theta, rot):
    return jnp.concatenate([nn.rope(x[..., :rot], theta), x[..., rot:]], -1)


def qkv(p, h, cfg, precision):
    b, s, _ = h.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = nn.mm(h, p["wq"], precision)
    k = nn.mm(h, p["wk"], precision)
    v = nn.mm(h, p["wv"], precision)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = nn.rms(q.reshape(b, s, nh, hd), p["q_norm"], eps)
    k = nn.rms(k.reshape(b, s, nkv, hd), p["k_norm"], eps)
    rot = int(hd * cfg["partial_rotary_factor"]) // 2 * 2
    q = _partial_rope(q, cfg["rope_theta"], rot)
    k = _partial_rope(k, cfg["rope_theta"], rot)
    return q, k, v.reshape(b, s, nkv, hd)


def hidden(params, tokens, cfg, precision="float32"):
    return decoder.layers(params, tokens, cfg, precision, qkv)
