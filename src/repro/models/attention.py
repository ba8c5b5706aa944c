"""GQA attention for all transformer-family archs: full / sliding-window /
chunked-local(+periodic-global) masks, QKV bias, per-head qk-norm, partial
RoPE and M-RoPE; prefill and single-token decode against a KV cache."""

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


def attention_specs(cfg: ModelConfig, layers: int):
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
    ``batch_axes``: GSPMD cannot partition the Pallas decode kernel."""
    def body(q, ck, cv, valid, pos):
        return da.decode_attention(q, ck, cv, valid, pos=pos, **kw)

    spec = P(_entry(batch_axes))
    return jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 5,
                         out_specs=spec, check_vma=False)(
        q, cache_k, cache_v, valid, pos)


def self_attention(p, x, cfg: ModelConfig, positions, *, causal=True,
                   window=None, chunk=None, rope=True):
    """Training / prefill attention.  x: [B, S, D]."""
    q, k, v = _project_qkv(p, x, cfg, positions, rope=rope)
    b, s = x.shape[:2]
    mesh = current_mesh()
    m = mesh.shape.get("model", 1) if mesh is not None else 1
    split_heads = (m > 1 and cfg.num_heads % m == 0
                   and cfg.num_kv_heads % m == 0)
    if mesh is not None and (
            fa.default_impl() == "pallas"
            or (current_flags().get("headparallel_attn") and split_heads)):
        out = _shardmapped_flash(q, k, v, mesh, _batch_axes(mesh, "batch", b),
                                 "model" if split_heads else None,
                                 causal=causal, window=window, chunk=chunk)
    else:
        q = shard_act(q, "batch", "seq", "act_heads", None)
        out = fa.flash_attention(q, k, v, causal=causal, window=window,
                                 chunk=chunk)
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
    idx = slot[:, None]
    cache_k = jax.vmap(
        lambda c, kk, i: jax.lax.dynamic_update_slice(c, kk, (i, 0, 0))
    )(cache_k, k.astype(cache_k.dtype), slot)
    cache_v = jax.vmap(
        lambda c, vv, i: jax.lax.dynamic_update_slice(c, vv, (i, 0, 0))
    )(cache_v, v.astype(cache_v.dtype), slot)
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


def cache_shape(cfg: ModelConfig, batch: int, s_max: int):
    """KV cache ShapeDtypeStruct axes for one layer stack."""
    if cfg.sliding_window is not None:
        s_max = min(s_max, cfg.sliding_window)
    shape = (cfg.num_layers, batch, s_max, cfg.num_kv_heads, cfg.head_dim)
    axes = ("layers", "cache_batch", "cache_seq", None, None)
    return shape, axes
