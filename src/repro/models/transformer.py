"""Decoder-only transformer covering the dense, MoE and VLM families
(qwen2-0.5b, minicpm-2b, h2o-danube, stablelm-12b, qwen3-moe, llama4-scout,
qwen2-vl).

Layers are *scanned* (stacked parameters with a leading L dim) so the HLO —
and hence dry-run compile time at 512 devices — stays O(1) in depth.
Architectures with a periodic layer pattern (llama4: every ``global_every``-th
layer is global-attention NoPE, the rest chunked-local RoPE) are scanned in
groups of ``global_every`` with the heterogeneous layer unrolled inside the
group body.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.runtime.sharding import shard_act
from .attention import (attention_specs, cache_shape, decode_attention,
                        layer_mask_kind, self_attention)
from .config import ModelConfig
from .layers import (COMPUTE_DTYPE, cross_entropy, embed, embed_specs,
                     mlp_specs, rms_norm, swiglu, unembed)
from .moe import moe_block, moe_specs
from .params import spec


def transformer_specs(cfg: ModelConfig):
    L = cfg.num_layers
    blocks = {
        "ln1": spec((L, cfg.d_model), ("layers", "embed"), init="ones"),
        "ln2": spec((L, cfg.d_model), ("layers", "embed"), init="ones"),
        "attn": attention_specs(cfg, L),
    }
    if cfg.family == "moe":
        blocks["moe"] = moe_specs(cfg, L)
    else:
        blocks["mlp"] = mlp_specs(cfg, L)
    return {
        **embed_specs(cfg),
        "blocks": blocks,
        "final_norm": spec((cfg.d_model,), ("embed",), init="ones"),
    }


#: leaves that ``forward`` and ``decode_step`` use only cast to
#: COMPUTE_DTYPE (the norm scales are used in float32, the QKV biases are
#: cast in-step and tiny, and MoE routes in float32)
_SERVING_CAST = {"attn": ("wq", "wk", "wv", "wo"),
                 "mlp": ("gate", "up", "down")}


def serving_params(params, cfg: ModelConfig):
    """``params`` with every leaf in ``_SERVING_CAST``, the embedding and
    the untied head cast to COMPUTE_DTYPE, the rest as they are.  The
    steps' own casts of these leaves are then no-ops, so ``forward`` and
    ``decode_step`` give the same numbers on either tree."""
    def cast(tree, names):
        return {k: v.astype(COMPUTE_DTYPE) if k in names else v
                for k, v in tree.items()}

    blocks = {k: cast(v, _SERVING_CAST[k]) if k in _SERVING_CAST else v
              for k, v in params["blocks"].items()}
    return {**cast(params, ("embedding", "lm_head")), "blocks": blocks}


def _layer_params(p, idx):
    """Slice one layer's parameters out of the stacked tree."""
    return jax.tree.map(lambda a: a[idx], p)


def _block(p, x, cfg: ModelConfig, positions, layer_idx: int, aux):
    """One transformer block (pre-norm).  layer_idx is static."""
    mk = layer_mask_kind(cfg, layer_idx)
    h = rms_norm(x, p["ln1"].astype(jnp.float32), cfg.norm_eps)
    h = self_attention(p["attn"], h, cfg, positions, **mk)
    x = x + h * cfg.residual_scale
    h = rms_norm(x, p["ln2"].astype(jnp.float32), cfg.norm_eps)
    if cfg.family == "moe":
        h, a = moe_block(p["moe"], h, cfg)
        aux = aux + a
    else:
        h = swiglu(p["mlp"], h)
    x = x + h * cfg.residual_scale
    x = shard_act(x, "batch", "seq", "act_embed")
    return x, aux


def _scan_blocks(params, x, cfg: ModelConfig, positions):
    """Scan over stacked layers; heterogeneous patterns scan in groups."""
    aux0 = jnp.zeros((), jnp.float32)
    group = cfg.global_every if (cfg.chunk_size and cfg.global_every) else 1
    n_groups = cfg.num_layers // group
    rem = cfg.num_layers - n_groups * group

    def body(carry, p):
        x, aux = carry
        for j in range(group):
            pj = _layer_params(p, j) if group > 1 else p
            x, aux = _block(pj, x, cfg, positions, j, aux)
        return (x, aux), None

    stacked = jax.tree.map(
        lambda a: a[:n_groups * group].reshape(
            (n_groups, group) + a.shape[1:]) if group > 1
        else a[:n_groups * group],
        params["blocks"])
    (x, aux), _ = jax.lax.scan(body, (x, aux0), stacked)
    for i in range(rem):
        p = _layer_params(params["blocks"], n_groups * group + i)
        x, aux = _block(p, x, cfg, positions, i, aux)
    return x, aux


def _default_positions(cfg: ModelConfig, b: int, s: int):
    pos = jnp.arange(s, dtype=jnp.int32)[None, :].repeat(b, 0)
    if cfg.mrope_sections:
        return pos[None].repeat(3, 0)            # [3, B, S] (text layout)
    return pos


def forward(params, batch: dict, cfg: ModelConfig, *, last_only=False):
    """Training / prefill forward -> (logits [B,S,V], aux_loss).

    ``last_only`` slices the final position BEFORE the unembedding matmul
    (serving prefill needs one next-token distribution, not B x S x V)."""
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
    x, aux = _scan_blocks(params, x, cfg, positions)
    if last_only:
        x = x[:, -1:]
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    return unembed(params, x, cfg), aux


def loss_fn(params, batch: dict, cfg: ModelConfig):
    logits, aux = forward(params, batch, cfg)
    return cross_entropy(logits, batch["labels"]) + aux


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache_specs(cfg: ModelConfig, batch: int, s_max: int):
    shape, axes = cache_shape(cfg, batch, s_max)
    return {"k": spec(shape, axes, init="zeros", dtype=COMPUTE_DTYPE),
            "v": spec(shape, axes, init="zeros", dtype=COMPUTE_DTYPE)}


def decode_step(params, cache, tokens, pos, cfg: ModelConfig):
    """tokens: [B, 1]; pos: [B] -> (logits [B, V], new cache)."""
    x = embed(params, tokens, cfg)
    group = cfg.global_every if (cfg.chunk_size and cfg.global_every) else 1
    n_groups = cfg.num_layers // group
    rem = cfg.num_layers - n_groups * group

    def body(x, xs):
        p, ck, cv = xs
        cks, cvs = [], []
        for j in range(group):
            pj = _layer_params(p, j) if group > 1 else p
            ckj = ck[j] if group > 1 else ck
            cvj = cv[j] if group > 1 else cv
            mk = layer_mask_kind(cfg, j)
            h = rms_norm(x, pj["ln1"].astype(jnp.float32), cfg.norm_eps)
            h, ckj, cvj = decode_attention(pj["attn"], h, cfg, ckj, cvj,
                                           pos, **mk)
            x = x + h * cfg.residual_scale
            h = rms_norm(x, pj["ln2"].astype(jnp.float32), cfg.norm_eps)
            if cfg.family == "moe":
                h, _ = moe_block(pj["moe"], h, cfg, decode=True)
            else:
                h = swiglu(pj["mlp"], h)
            x = x + h * cfg.residual_scale
            cks.append(ckj)
            cvs.append(cvj)
        ck = jnp.stack(cks) if group > 1 else cks[0]
        cv = jnp.stack(cvs) if group > 1 else cvs[0]
        return x, (ck, cv)

    def regroup(a):
        return (a[:n_groups * group].reshape((n_groups, group) + a.shape[1:])
                if group > 1 else a[:n_groups * group])

    stacked = jax.tree.map(regroup, params["blocks"])
    ck, cv = regroup(cache["k"]), regroup(cache["v"])
    x, (ck, cv) = jax.lax.scan(body, x, (stacked, ck, cv))
    ck = ck.reshape((n_groups * group,) + ck.shape[2:]) if group > 1 else ck
    cv = cv.reshape((n_groups * group,) + cv.shape[2:]) if group > 1 else cv
    if rem:
        tails_k, tails_v = [], []
        for i in range(rem):
            li = n_groups * group + i
            p = _layer_params(params["blocks"], li)
            mk = layer_mask_kind(cfg, i)
            h = rms_norm(x, p["ln1"].astype(jnp.float32), cfg.norm_eps)
            h, cki, cvi = decode_attention(p["attn"], h, cfg, cache["k"][li],
                                           cache["v"][li], pos, **mk)
            x = x + h * cfg.residual_scale
            h = rms_norm(x, p["ln2"].astype(jnp.float32), cfg.norm_eps)
            if cfg.family == "moe":
                h, _ = moe_block(p["moe"], h, cfg, decode=True)
            else:
                h = swiglu(p["mlp"], h)
            x = x + h * cfg.residual_scale
            tails_k.append(cki)
            tails_v.append(cvi)
        ck = jnp.concatenate([ck, jnp.stack(tails_k)], axis=0)
        cv = jnp.concatenate([cv, jnp.stack(tails_v)], axis=0)
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
    logits = unembed(params, x, cfg)
    return logits[:, 0], {"k": ck, "v": cv}
