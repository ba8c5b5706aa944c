"""Decoder-only transformer covering the dense, MoE and VLM families
(qwen2-0.5b, minicpm-2b, h2o-danube, stablelm-12b, qwen3-moe, llama4-scout,
qwen2-vl, moonlight: latent attention and leading dense layers before the
scanned expert layers).

Layers are *scanned* (stacked parameters with a leading L dim) so the HLO —
and hence dry-run compile time at 512 devices — stays O(1) in depth.
Architectures with a periodic layer pattern (llama4: every ``global_every``-th
layer is global-attention NoPE, the rest chunked-local RoPE) are scanned in
groups of ``global_every`` with the heterogeneous layer unrolled inside the
group body.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.runtime.sharding import shard_act
from .attention import (attention_specs, cache_shape, decode_attention,
                        layer_mask_kind, mla_decode_attention,
                        self_attention)
from .config import ModelConfig
from .layers import (COMPUTE_DTYPE, cross_entropy, embed, embed_specs,
                     mlp_specs, rms_norm, swiglu, unembed)
from .moe import moe_block, moe_specs
from .params import spec


def _layer_specs(cfg: ModelConfig, layers: int, moe: bool):
    out = {
        "ln1": spec((layers, cfg.d_model), ("layers", "embed"), init="ones"),
        "ln2": spec((layers, cfg.d_model), ("layers", "embed"), init="ones"),
        "attn": attention_specs(cfg, layers),
    }
    if moe:
        out["moe"] = moe_specs(cfg, layers)
    else:
        out["mlp"] = mlp_specs(cfg, layers)
    return out


def transformer_specs(cfg: ModelConfig):
    """``blocks``: the scanned layers; ``dense``: the leading dense layers
    of an expert model (``first_k_dense_replace``); ``router_bias``: the
    selection bias of ``noaux_tc`` routing, one row of ``num_experts`` per
    expert layer, flat so that the optimizer, which decays matrices only,
    leaves it (it takes no gradient either)."""
    moe = cfg.family == "moe"
    dense = cfg.first_k_dense_replace
    out = {
        **embed_specs(cfg),
        "blocks": _layer_specs(cfg, cfg.num_layers - dense, moe),
        "final_norm": spec((cfg.d_model,), ("embed",), init="ones"),
    }
    if dense:
        out["dense"] = _layer_specs(cfg, dense, False)
    if cfg.noaux_tc:
        out["router_bias"] = spec((cfg.moe_layers * cfg.num_experts,),
                                  (None,), init="zeros")
    return out


#: leaves that ``forward`` and ``decode_step`` use only cast to
#: COMPUTE_DTYPE (the norm scales are used in float32, the QKV biases are
#: cast in-step and tiny, and MoE routes in float32)
_SERVING_CAST = {"attn": ("wq", "wk", "wv", "wo", "wkv_a", "wkv_b"),
                 "mlp": ("gate", "up", "down"),
                 "moe": ("w_gate", "w_up", "w_down", "s_gate", "s_up",
                         "s_down")}


def serving_params(params, cfg: ModelConfig):
    """``params`` with every leaf in ``_SERVING_CAST``, the embedding and
    the untied head cast to COMPUTE_DTYPE, the rest as they are.  The
    steps' own casts of these leaves are then no-ops, so ``forward`` and
    ``decode_step`` give the same numbers on either tree."""
    def cast(tree, names):
        return {k: v.astype(COMPUTE_DTYPE) if k in names else v
                for k, v in tree.items()}

    def layers(tree):
        return {k: cast(v, _SERVING_CAST[k]) if k in _SERVING_CAST else v
                for k, v in tree.items()}

    out = {**cast(params, ("embedding", "lm_head")),
           "blocks": layers(params["blocks"])}
    if "dense" in params:
        out["dense"] = layers(params["dense"])
    return out


def _layer_params(p, idx):
    """Slice one layer's parameters out of the stacked tree."""
    return jax.tree.map(lambda a: a[idx], p)


def _scanned(params, cfg: ModelConfig):
    """The scanned layers' parameters, with the selection bias split into
    each expert layer's ``moe`` as ``bias``."""
    blocks = params["blocks"]
    if "router_bias" in params:
        bias = params["router_bias"].reshape(cfg.moe_layers, -1)
        blocks = {**blocks, "moe": {**blocks["moe"], "bias": bias}}
    return blocks


def _scope(name: str | None):
    return jax.named_scope(name) if name else contextlib.nullcontext()


def _block(p, x, cfg: ModelConfig, positions, layer_idx: int, carry):
    """One transformer block (pre-norm).  layer_idx is static; ``carry``
    is (aux loss, assignments to each held expert, None without
    experts)."""
    mk = layer_mask_kind(cfg, layer_idx)
    h = rms_norm(x, p["ln1"].astype(jnp.float32), cfg.norm_eps)
    with _scope("mla" if cfg.mla else None):
        h = self_attention(p["attn"], h, cfg, positions, **mk)
    x = x + h * cfg.residual_scale
    h = rms_norm(x, p["ln2"].astype(jnp.float32), cfg.norm_eps)
    if "moe" in p:
        with jax.named_scope("moe"):
            h, a, c = moe_block(p["moe"], h, cfg)
        carry = (carry[0] + a, carry[1] + c)
    else:
        h = swiglu(p["mlp"], h)
    x = x + h * cfg.residual_scale
    x = shard_act(x, "batch", "seq", "act_embed")
    return x, carry


def _scan_blocks(params, x, cfg: ModelConfig, positions):
    """The leading dense layers, then a scan over the stacked layers;
    heterogeneous patterns scan in groups.  Returns (x, aux, counts)."""
    carry = (jnp.zeros((), jnp.float32),
             jnp.zeros((cfg.n_held,), jnp.float32)
             if cfg.family == "moe" else None)
    for i in range(cfg.first_k_dense_replace):
        x, carry = _block(_layer_params(params["dense"], i), x, cfg,
                          positions, i, carry)
    group = cfg.global_every if (cfg.chunk_size and cfg.global_every) else 1
    layers = cfg.num_layers - cfg.first_k_dense_replace
    n_groups = layers // group
    rem = layers - n_groups * group

    def body(carry, p):
        x, c = carry
        for j in range(group):
            pj = _layer_params(p, j) if group > 1 else p
            x, c = _block(pj, x, cfg, positions, j, c)
        return (x, c), None

    blocks = _scanned(params, cfg)
    stacked = jax.tree.map(
        lambda a: a[:n_groups * group].reshape(
            (n_groups, group) + a.shape[1:]) if group > 1
        else a[:n_groups * group],
        blocks)
    (x, carry), _ = jax.lax.scan(body, (x, carry), stacked)
    for i in range(rem):
        p = _layer_params(blocks, n_groups * group + i)
        x, carry = _block(p, x, cfg, positions, i, carry)
    return x, *carry


def _default_positions(cfg: ModelConfig, b: int, s: int):
    pos = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    if cfg.mrope_sections:
        return pos[None].repeat(3, 0)            # [3, B, S] (text layout)
    return pos


def _forward(params, batch: dict, cfg: ModelConfig, last_only: bool):
    if "embeds" in batch:                        # stub modality frontend
        x = shard_act(batch["embeds"].astype(COMPUTE_DTYPE) * cfg.embed_scale,
                      "batch", "seq", "act_embed")
        b, s = x.shape[:2]
    else:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = embed(params, tokens, cfg)
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, b, s)
    x, aux, counts = _scan_blocks(params, x, cfg, positions)
    if last_only:
        x = x[:, -1:]
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    return unembed(params, x, cfg), aux, counts


def forward(params, batch: dict, cfg: ModelConfig, *, last_only=False):
    """Training / prefill forward -> (logits [B,S,V], aux_loss).

    ``last_only`` slices the final position BEFORE the unembedding matmul
    (serving prefill needs one next-token distribution, not B x S x V)."""
    logits, aux, _ = _forward(params, batch, cfg, last_only)
    return logits, aux


def loss_fn(params, batch: dict, cfg: ModelConfig):
    return loss_and_stats(params, batch, cfg)[0]


def loss_and_stats(params, batch: dict, cfg: ModelConfig):
    """(loss, stats): an expert model's stats hold ``expert_tokens``, the
    assignments to each held expert summed over its layers."""
    logits, aux, counts = _forward(params, batch, cfg, False)
    loss = cross_entropy(logits, batch["labels"]) + aux
    return loss, ({"expert_tokens": counts} if cfg.family == "moe" else {})


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache_specs(cfg: ModelConfig, batch: int, s_max: int):
    shape, axes = cache_shape(cfg, batch, s_max)
    if cfg.mla:
        return {"kv": spec(shape, axes, init="zeros", dtype=COMPUTE_DTYPE)}
    return {"k": spec(shape, axes, init="zeros", dtype=COMPUTE_DTYPE),
            "v": spec(shape, axes, init="zeros", dtype=COMPUTE_DTYPE)}


def _decode_block(p, x, cfg: ModelConfig, cache: dict, pos, layer_idx: int):
    """One block on one new token; ``cache`` is this layer's."""
    mk = layer_mask_kind(cfg, layer_idx)
    h = rms_norm(x, p["ln1"].astype(jnp.float32), cfg.norm_eps)
    if cfg.mla:
        with jax.named_scope("mla"):
            h, kv = mla_decode_attention(p["attn"], h, cfg, cache["kv"], pos)
        cache = {"kv": kv}
    else:
        h, ck, cv = decode_attention(p["attn"], h, cfg, cache["k"],
                                     cache["v"], pos, **mk)
        cache = {"k": ck, "v": cv}
    x = x + h * cfg.residual_scale
    h = rms_norm(x, p["ln2"].astype(jnp.float32), cfg.norm_eps)
    if "moe" in p:
        with jax.named_scope("moe"):
            h = moe_block(p["moe"], h, cfg, decode=True)[0]
    else:
        h = swiglu(p["mlp"], h)
    x = x + h * cfg.residual_scale
    return x, cache


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    """tokens: [B, 1]; pos: [B] -> (logits [B, V], new cache)."""
    x = embed(params, tokens, cfg)
    dense = cfg.first_k_dense_replace
    heads = []
    for i in range(dense):
        x, c = _decode_block(_layer_params(params["dense"], i), x, cfg,
                             _layer_params(cache, i), pos, i)
        heads.append(c)
    group = cfg.global_every if (cfg.chunk_size and cfg.global_every) else 1
    layers = cfg.num_layers - dense
    n_groups = layers // group
    rem = layers - n_groups * group

    def body(x, xs):
        p, c = xs
        outs = []
        for j in range(group):
            pj = _layer_params(p, j) if group > 1 else p
            cj = _layer_params(c, j) if group > 1 else c
            x, cj = _decode_block(pj, x, cfg, cj, pos, j)
            outs.append(cj)
        if group > 1:
            return x, jax.tree.map(lambda *a: jnp.stack(a), *outs)
        return x, outs[0]

    def regroup(a, start=0):
        a = a[start:start + n_groups * group]
        return (a.reshape((n_groups, group) + a.shape[1:]) if group > 1
                else a)

    blocks = _scanned(params, cfg)
    x, scanned = jax.lax.scan(
        body, x, (jax.tree.map(regroup, blocks),
                  jax.tree.map(lambda a: regroup(a, dense), cache)))
    if group > 1:
        scanned = jax.tree.map(
            lambda a: a.reshape((n_groups * group,) + a.shape[2:]), scanned)
    tails = []
    for i in range(rem):
        li = n_groups * group + i
        x, c = _decode_block(_layer_params(blocks, li), x, cfg,
                             _layer_params(cache, dense + li), pos, i)
        tails.append(c)
    if heads or tails:
        scanned = jax.tree.map(
            lambda *a: jnp.concatenate(a, axis=0),
            *[jax.tree.map(lambda t: t[None], c) for c in heads],
            scanned,
            *[jax.tree.map(lambda t: t[None], c) for c in tails])
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    logits = unembed(params, x, cfg)
    return logits[:, 0], scanned
