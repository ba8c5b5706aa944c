"""GQA attention for all transformer-family archs: full / sliding-window /
chunked-local(+periodic-global) masks, QKV bias, per-head qk-norm, partial
RoPE and M-RoPE; prefill and single-token decode against a KV cache.

Multi-head latent attention (deepseek-v2/v3, ``cfg.mla``): keys and values
come from one normed latent per token plus one rotary key that all heads
share.  Prefill and training expand the latent to per-head keys and
values; decode absorbs the key expansion into the query and attends over
a cache of [latent, rotary key] per token, whose first ``kv_lora_rank``
lanes are also the values, expanded after attending."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.kernels.flash_attention import ops as fa
from repro.kernels.decode_attention import ops as da
from repro.runtime.sharding import (current_flags, current_mesh,
                                    current_rules, gathered, shard_act)
from .config import ModelConfig
from .layers import apply_rope, rms_norm
from .params import spec


#: eps of the latent's RMSNorm (``kv_a_layernorm``), which the published
#: deepseek modeling code leaves at its norm's default
LATENT_NORM_EPS = 1e-6


def attention_specs(cfg: ModelConfig, layers: int):
    if cfg.mla:
        return mla_specs(cfg, layers)
    d, q, kv = cfg.d_model, cfg.q_dim, cfg.kv_dim
    L = (layers,)
    out = {
        "wq": spec(L + (d, q), ("layers", "embed", "heads")),
        "wk": spec(L + (d, kv), ("layers", "embed", "kv_heads")),
        "wv": spec(L + (d, kv), ("layers", "embed", "kv_heads")),
        "wo": spec(L + (q, d), ("layers", "heads", "embed")),
    }
    if cfg.qkv_bias:
        out |= {
            "bq": spec(L + (q,), ("layers", "heads"), init="zeros"),
            "bk": spec(L + (kv,), ("layers", "kv_heads"), init="zeros"),
            "bv": spec(L + (kv,), ("layers", "kv_heads"), init="zeros"),
        }
    if cfg.qk_norm:
        out |= {
            "q_norm": spec(L + (cfg.head_dim,), ("layers", None), init="ones"),
            "k_norm": spec(L + (cfg.head_dim,), ("layers", None), init="ones"),
        }
    return out


def _project_qkv(p, x, cfg: ModelConfig, positions, *, rope: bool):
    b, s, _ = x.shape
    q = x @ gathered(p["wq"], "embed", "heads", dtype=x.dtype)
    k = x @ gathered(p["wk"], "embed", "kv_heads", dtype=x.dtype)
    v = x @ gathered(p["wv"], "embed", "kv_heads", dtype=x.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"].astype(jnp.float32), cfg.norm_eps)
        k = rms_norm(k, p["k_norm"].astype(jnp.float32), cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       rope_pct=cfg.rope_pct,
                       mrope_sections=cfg.mrope_sections)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       rope_pct=cfg.rope_pct,
                       mrope_sections=cfg.mrope_sections)
    return q, k, v


def layer_mask_kind(cfg: ModelConfig, layer_idx) -> dict:
    """Per-layer mask parameters (llama4: every `global_every`-th layer is
    global full attention with NoPE; others chunked-local with RoPE)."""
    if cfg.chunk_size and cfg.global_every:
        is_global = (layer_idx + 1) % cfg.global_every == 0
        return dict(window=None,
                    chunk=None if is_global else cfg.chunk_size,
                    rope=not is_global)
    return dict(window=cfg.sliding_window, chunk=cfg.chunk_size, rope=True)


def _batch_axes(mesh, logical: str, b: int) -> tuple[str, ...]:
    """Mesh axes of the ``logical`` batch axis whose product divides ``b``
    (the model axis never carries the batch here)."""
    axes: list[str] = []
    size = 1
    for a in current_rules().mesh_axes_for(logical, mesh):
        if a != "model" and b % (size * mesh.shape[a]) == 0:
            axes.append(a)
            size *= mesh.shape[a]
    return tuple(axes)


def _entry(axes: tuple[str, ...]):
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def _shardmapped_flash(q, k, v, mesh, batch_axes, heads_axis, **kw):
    """Flash attention per device under shard_map, batch over
    ``batch_axes`` and heads over ``heads_axis`` (None: every rank of the
    model axis computes all heads).  Two callers:

    - the Pallas kernel on a mesh: GSPMD cannot partition a Mosaic call;
    - §Perf head parallelism: each model rank runs the flash scan on its
      own heads with NO collectives inside — the alternative (GSPMD
      inferring layouts for the blocked scan) reconciles fwd/remat/bwd
      layouts with score-sized all-gathers/all-reduces (measured
      580 GB/device/step on llama4 train)."""
    def body(q, k, v):
        return fa.flash_attention(q, k, v, **kw)

    spec = P(_entry(batch_axes), None, heads_axis, None)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _shardmapped_decode(q, cache_k, cache_v, valid, pos, mesh, batch_axes,
                        **kw):
    """Decode attention per device under shard_map, batch over
    ``batch_axes``: GSPMD cannot partition the Pallas decode kernel.
    ``cache_v`` None: the values are the keys' lanes (latent attention)."""
    caches = (cache_k,) if cache_v is None else (cache_k, cache_v)

    def body(q, *rest):
        *cs, valid, pos = rest
        ck, cv = cs if len(cs) == 2 else (cs[0], None)
        return da.decode_attention(q, ck, cv, valid, pos=pos, **kw)

    spec = P(_entry(batch_axes))
    return jax.shard_map(body, mesh=mesh,
                         in_specs=(spec,) * (3 + len(caches)),
                         out_specs=spec, check_vma=False)(
        q, *caches, valid, pos)


def mla_specs(cfg: ModelConfig, layers: int):
    d, r, h = cfg.d_model, cfg.kv_lora_rank, cfg.num_heads
    L = (layers,)
    return {
        "wq": spec(L + (d, cfg.q_dim), ("layers", "embed", "heads")),
        "wkv_a": spec(L + (d, r + cfg.qk_rope_head_dim),
                      ("layers", "embed", None)),
        "kv_norm": spec(L + (r,), ("layers", None), init="ones"),
        "wkv_b": spec(L + (r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                      ("layers", None, "heads")),
        "wo": spec(L + (h * cfg.v_head_dim, d), ("layers", "heads", "embed")),
    }


def _mla_project(p, x, cfg: ModelConfig, positions):
    """Queries [B, S, H, nope + rope] with rotary on their rope part; the
    normed latent [B, S, r]; the rotary key [B, S, 1, rope]."""
    b, s, _ = x.shape
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    rot = dict(theta=cfg.rope_theta)
    q = (x @ gathered(p["wq"], "embed", "heads", dtype=x.dtype)).reshape(
        b, s, cfg.num_heads, cfg.head_dim)
    q = jnp.concatenate([q[..., :dn],
                         apply_rope(q[..., dn:], positions, **rot)], -1)
    kv = x @ p["wkv_a"].astype(x.dtype)
    c = rms_norm(kv[..., :r], p["kv_norm"].astype(jnp.float32),
                 LATENT_NORM_EPS)
    k_rope = apply_rope(kv[..., None, r:], positions, **rot)
    return q, c, k_rope


def _flash(q, k, v, cfg: ModelConfig, **kw):
    """Flash attention on [B, S, H, D] queries, on a mesh under shard_map
    where GSPMD cannot partition the kernel or heads split over 'model'."""
    b = q.shape[0]
    mesh = current_mesh()
    m = mesh.shape.get("model", 1) if mesh is not None else 1
    split_heads = (m > 1 and cfg.num_heads % m == 0
                   and cfg.num_kv_heads % m == 0)
    if mesh is not None and (
            fa.default_impl() == "pallas"
            or (current_flags().get("headparallel_attn") and split_heads)):
        return _shardmapped_flash(q, k, v, mesh,
                                  _batch_axes(mesh, "batch", b),
                                  "model" if split_heads else None, **kw)
    q = shard_act(q, "batch", "seq", "act_heads", None)
    return fa.flash_attention(q, k, v, **kw)


def mla_self_attention(p, x, cfg: ModelConfig, positions):
    """Training / prefill latent attention, expanded: per-head keys
    [nope from the latent, the shared rotary key] and values from the
    latent; scale (nope + rope) ** -0.5.  The values are zero-padded to
    the key width for the flash kernel (one width for q, k and v) and
    sliced after.  x: [B, S, D]."""
    b, s, _ = x.shape
    h, dn, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    q, c, k_rope = _mla_project(p, x, cfg, positions)
    kv = (c @ gathered(p["wkv_b"], None, "heads", dtype=x.dtype)).reshape(
        b, s, h, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (b, s, h, k_rope.shape[-1]))],
        -1)
    v = jnp.pad(kv[..., dn:], ((0, 0),) * 3 + ((0, cfg.head_dim - dv),))
    out = _flash(q, k, v, cfg, causal=True)[..., :dv]
    return out.reshape(b, s, h * dv) @ gathered(p["wo"], "heads", "embed",
                                                dtype=x.dtype)


def self_attention(p, x, cfg: ModelConfig, positions, *, causal=True,
                   window=None, chunk=None, rope=True):
    """Training / prefill attention.  x: [B, S, D]."""
    if cfg.mla:
        return mla_self_attention(p, x, cfg, positions)
    q, k, v = _project_qkv(p, x, cfg, positions, rope=rope)
    b, s = x.shape[:2]
    out = _flash(q, k, v, cfg, causal=causal, window=window, chunk=chunk)
    out = out.reshape(b, s, cfg.q_dim)
    return out @ gathered(p["wo"], "heads", "embed", dtype=x.dtype)


def _sharded_flash_decode(q, k, v, cache_k, cache_v, pos, mesh, batch_axes):
    """§Perf variant: explicit flash-decoding over a sequence-sharded
    cache.  shard_map over ('model' x batch axes): each model rank scores
    its local cache slots (partial softmax), the combine is a psum
    log-sum-exp, and the cache update is a LOCAL scatter on the owning
    shard (OOB indices drop elsewhere) — no implicit cache all-gather /
    re-shard, which is exactly what the baseline HLO shows."""
    s_max = cache_k.shape[1]
    m = mesh.shape["model"]
    s_loc = s_max // m
    bspec = _entry(batch_axes)

    def body(q, k, v, ck, cv, pos):
        rank = jax.lax.axis_index("model")
        local_slot = pos - rank * s_loc                       # [B]
        own = (local_slot >= 0) & (local_slot < s_loc)
        idx = jnp.where(own, local_slot, s_loc)               # OOB -> drop
        bi = jnp.arange(q.shape[0])
        ck = ck.at[bi, idx].set(k[:, 0].astype(ck.dtype), mode="drop")
        cv = cv.at[bi, idx].set(v[:, 0].astype(cv.dtype), mode="drop")
        slot_pos = rank * s_loc + jnp.arange(s_loc)
        mask = slot_pos[None, :] <= pos[:, None]              # causal+valid
        acc, mx, l = da.partial_decode(q[:, 0], ck, cv, mask)
        out = da.combine_partials(acc, mx, l, "model")
        return out[:, None].astype(q.dtype), ck, cv

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec),
                  P(bspec, "model"), P(bspec, "model"), P(bspec)),
        out_specs=(P(bspec), P(bspec, "model"), P(bspec, "model")),
        check_vma=False,
    )(q, k, v, cache_k, cache_v, pos)


def decode_attention(p, x, cfg: ModelConfig, cache_k, cache_v, pos, *,
                     window=None, chunk=None, rope=True):
    """Single-token decode.  x: [B, 1, D]; cache_[kv]: [B, S_max, KVH, Dh];
    pos: [B] number of tokens already in the cache.  Returns
    (out [B, 1, D], new_cache_k, new_cache_v)."""
    b = x.shape[0]
    positions = (pos[None, :, None].repeat(3, 0) if cfg.mrope_sections
                 else pos[:, None])
    q, k, v = _project_qkv(p, x, cfg, positions, rope=rope)
    s_max = cache_k.shape[1]

    mesh = current_mesh()
    if (current_flags().get("sharded_decode") and mesh is not None
            and "model" in mesh.axis_names and window is None
            and chunk is None and s_max % mesh.shape["model"] == 0):
        out, cache_k, cache_v = _sharded_flash_decode(
            q, k, v, cache_k, cache_v, pos, mesh,
            _batch_axes(mesh, "cache_batch", b))
        out = out.reshape(b, 1, cfg.q_dim)
        return out @ p["wo"].astype(x.dtype), cache_k, cache_v
    if window is not None and s_max <= window:
        # rolling cache: position modulo window (long-context decode)
        slot = pos % s_max
    else:
        slot = pos
    cache_k = _write_slot(cache_k, k, slot)
    cache_v = _write_slot(cache_v, v, slot)
    valid = jnp.minimum(pos + 1, s_max)
    kw = dict(window=window, chunk=chunk,
              rolling=window is not None and s_max <= window)
    if mesh is not None and da.default_impl() == "pallas":
        out = _shardmapped_decode(q[:, 0], cache_k, cache_v, valid, pos,
                                  mesh, _batch_axes(mesh, "cache_batch", b),
                                  **kw)
    else:
        out = da.decode_attention(q[:, 0], cache_k, cache_v, valid, pos=pos,
                                  **kw)
    out = out.reshape(b, 1, cfg.q_dim)
    return out @ p["wo"].astype(x.dtype), cache_k, cache_v


def _write_slot(cache, entry, slot):
    """``cache`` [B, S, ...] with ``entry`` [B, 1, ...] written at each
    row's ``slot``."""
    zeros = (0,) * (cache.ndim - 2)
    return jax.vmap(
        lambda c, e, i: jax.lax.dynamic_update_slice(c, e, (i,) + zeros)
    )(cache, entry.astype(cache.dtype), slot)


def mla_decode_attention(p, x, cfg: ModelConfig, cache, pos):
    """Single-token latent attention, absorbed.  x: [B, 1, D]; cache:
    [B, S_max, kv_lora_rank + rope] (normed latent, rotary key); pos: [B].
    The key expansion ``wkv_b[:, :, :nope]`` folds into the query, which
    attends over the one shared cache "head"; the attended latent (the
    cache's first ``kv_lora_rank`` lanes) is expanded by the value half
    after.  Returns (out [B, 1, D], new cache)."""
    b = x.shape[0]
    h, dn, dv, r = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                    cfg.kv_lora_rank)
    q, c, k_rope = _mla_project(p, x, cfg, pos[:, None])
    cache = _write_slot(cache, jnp.concatenate([c, k_rope[:, :, 0]], -1),
                        pos)
    wkv_b = p["wkv_b"].astype(x.dtype).reshape(r, h, dn + dv)
    q_lat = jnp.einsum("bhn,rhn->bhr", q[:, 0, :, :dn], wkv_b[..., :dn])
    qf = jnp.concatenate([q_lat, q[:, 0, :, dn:]], -1)      # [B, H, r+rope]
    valid = jnp.minimum(pos + 1, cache.shape[1])
    kw = dict(scale=cfg.head_dim ** -0.5, dv=r)
    mesh = current_mesh()
    if mesh is not None and da.default_impl() == "pallas":
        o = _shardmapped_decode(qf, cache[:, :, None], None, valid, pos,
                                mesh, _batch_axes(mesh, "cache_batch", b),
                                **kw)
    else:
        o = da.decode_attention(qf, cache[:, :, None], None, valid, pos=pos,
                                **kw)
    out = jnp.einsum("bhr,rhv->bhv", o, wkv_b[..., dn:])
    return out.reshape(b, 1, h * dv) @ p["wo"].astype(x.dtype), cache


def cache_shape(cfg: ModelConfig, batch: int, s_max: int):
    """KV cache ShapeDtypeStruct axes for one layer stack (latent
    attention: one [latent, rotary key] entry per token, no V)."""
    if cfg.mla:
        return ((cfg.num_layers, batch, s_max,
                 cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                ("layers", "cache_batch", "cache_seq", None))
    if cfg.sliding_window is not None:
        s_max = min(s_max, cfg.sliding_window)
    shape = (cfg.num_layers, batch, s_max, cfg.num_kv_heads, cfg.head_dim)
    axes = ("layers", "cache_batch", "cache_seq", None, None)
    return shape, axes
