"""Mixture-of-Experts layer with expert parallelism.

Three dispatch strategies sharing the same capacity-based scatter/combine
helpers:

- ``moe_ep_a2a``   (train / prefill): shard_map over the full mesh; tokens
  are sharded over ('pod','data') x batch and 'model' x sequence, experts
  over 'model'.  Local top-k routing -> capacity-bounded send buffer
  [M, E_loc, C, D] -> ``lax.all_to_all`` over 'model' -> grouped expert
  matmul -> reverse all_to_all -> weighted combine.  The only cross-device
  traffic is the routed tokens (2 x k x capacity), not full activations.

- ``moe_ep_psum`` (decode, S == 1): tokens replicated over 'model'; each
  model rank routes identically, processes only assignments that target
  its local experts, and the combine is a psum.  No all_to_all on the
  latency-critical decode path; traffic is 2 x activation bytes.

- ``moe_local``   (no mesh / smoke tests): the same scatter-dispatch on a
  single device, no collectives.

Routing is classic top-k with optional renormalised weights (qwen3) and a
load-balance auxiliary loss (Shazeer-style f*P); overflowed tokens beyond
the capacity factor are dropped (counted into the aux metrics).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime.sharding import current_flags, current_mesh, current_rules
from .config import ModelConfig
from .params import spec


def moe_specs(cfg: ModelConfig, layers: int):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    L = (layers,)
    out = {
        "router": spec(L + (d, e), ("layers", "embed", None), scale=0.02),
        "w_gate": spec(L + (e, d, f), ("layers", "experts", "embed",
                                       "expert_ffn")),
        "w_up": spec(L + (e, d, f), ("layers", "experts", "embed",
                                     "expert_ffn")),
        "w_down": spec(L + (e, f, d), ("layers", "experts", "expert_ffn",
                                       "embed")),
    }
    if cfg.shared_expert:
        out |= {
            "s_gate": spec(L + (d, cfg.d_ff), ("layers", "embed", "ffn")),
            "s_up": spec(L + (d, cfg.d_ff), ("layers", "embed", "ffn")),
            "s_down": spec(L + (cfg.d_ff, d), ("layers", "ffn", "embed")),
        }
    return out


@dataclasses.dataclass(frozen=True)
class MoEOptions:
    capacity_factor: float = 1.25
    aux_weight: float = 0.01


# ---------------------------------------------------------------------------
# routing + scatter helpers (shared by all strategies)
# ---------------------------------------------------------------------------

def _route(router_w, x, cfg: ModelConfig):
    """x: [T, D] -> (weights [T, k], experts [T, k], aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    w, e = jax.lax.top_k(probs, cfg.experts_per_token)         # [T, k]
    if cfg.norm_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux: E * sum_e mean(onehot_e) * mean(prob_e)
    ids = jax.nn.one_hot(e[:, 0], cfg.num_experts, dtype=jnp.float32)
    aux = cfg.num_experts * jnp.mean(
        ids.mean(0) * probs.mean(0)) * cfg.num_experts
    return w, e, aux


def _positions_in_expert(flat_e, num_experts: int):
    """Rank of each assignment within its expert (stable arrival order)."""
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1                       # [A, E]
    return jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]


def _dispatch(x, flat_e, pos, capacity: int, num_experts: int):
    """Scatter tokens into [E, C, D]; overflow (pos >= C) is dropped."""
    keep = pos < capacity
    e_idx = jnp.where(keep, flat_e, num_experts)               # OOB -> drop
    buf = jnp.zeros((num_experts, capacity) + x.shape[1:], x.dtype)
    return buf.at[e_idx, jnp.minimum(pos, capacity - 1)].set(
        x, mode="drop"), keep


def _collect(buf, flat_e, pos, capacity, keep):
    """Gather per-assignment outputs back out of [E, C, D]."""
    out = buf[jnp.minimum(flat_e, buf.shape[0] - 1),
              jnp.minimum(pos, capacity - 1)]
    return jnp.where(keep[:, None], out, 0.0)


def _expert_ffn(p, buf):
    """buf: [E, C, D] with per-expert weight stacks [E, D, F]/[E, F, D]."""
    h = (jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                                p["w_gate"].astype(buf.dtype)))
         * jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(buf.dtype)))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(buf.dtype))


def _capacity(tokens: int, num_experts: int, k: int, factor: float) -> int:
    c = math.ceil(tokens * k / num_experts * factor)
    return max(8, -(-c // 8) * 8)                              # pad to 8


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------

def _moe_tokens(p, xt, cfg: ModelConfig, opts: MoEOptions):
    """Single-device dispatch on a flat token batch xt: [T, D]."""
    t, dd = xt.shape
    w, e, aux = _route(p["router"], xt, cfg)
    k = cfg.experts_per_token
    cap = _capacity(t, cfg.num_experts, k, opts.capacity_factor)
    flat_e = e.reshape(t * k)
    pos = _positions_in_expert(flat_e, cfg.num_experts)
    x_rep = jnp.repeat(xt, k, axis=0)                          # [T*k, D]
    buf, keep = _dispatch(x_rep, flat_e, pos, cap, cfg.num_experts)
    out_buf = _expert_ffn(p, buf)
    y = _collect(out_buf, flat_e, pos, cap, keep)              # [T*k, D]
    y = (y.reshape(t, k, dd) * w[..., None].astype(y.dtype)).sum(axis=1)
    return y, aux


def moe_local(p, x, cfg: ModelConfig, opts: MoEOptions = MoEOptions()):
    b, s, dd = x.shape
    y, aux = _moe_tokens(p, x.reshape(b * s, dd), cfg, opts)
    return y.reshape(b, s, dd), aux


def _dev_groups(mesh):
    """(model-axis size, experts per model rank)."""
    return mesh.shape["model"]


def moe_ep_a2a(p, x, cfg: ModelConfig, opts: MoEOptions = MoEOptions()):
    """Training/prefill EP: shard_map with all_to_all dispatch.

    x: [B, S, D] sharded P(('pod','data'), 'model', None) inside.
    Expert stacks sharded on the expert dim over 'model'.
    """
    mesh = current_mesh()
    m = mesh.shape["model"]
    e_loc = cfg.num_experts // m
    k = cfg.experts_per_token
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(router_w, w_gate, w_up, w_down, xs):
        bl, sl, dd = xs.shape
        t = bl * sl
        xt = xs.reshape(t, dd)
        w, e, aux = _route(router_w, xt, cfg)
        aux = jax.lax.pmean(aux, ("model",) + batch_axes)
        cap = _capacity(t, cfg.num_experts, k, opts.capacity_factor)
        flat_e = e.reshape(t * k)
        # rank within expert (global expert id -> also rank within
        # (dest device, local expert) since e determines both)
        pos = _positions_in_expert(flat_e, cfg.num_experts)
        x_rep = jnp.repeat(xt, k, axis=0)
        buf, keep = _dispatch(x_rep, flat_e, pos, cap, cfg.num_experts)
        # [E, C, D] -> [M, E_loc, C, D] -> exchange over 'model'
        sb = buf.reshape(m, e_loc, cap, dd)
        rb = jax.lax.all_to_all(sb, "model", split_axis=0, concat_axis=0,
                                tiled=False)
        # rb: [M_src, E_loc, C, D] -> experts see M*C tokens each
        rb = rb.transpose(1, 0, 2, 3).reshape(e_loc, m * cap, dd)
        pl = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        ob = _expert_ffn(pl, rb)
        ob = ob.reshape(e_loc, m, cap, dd).transpose(1, 0, 2, 3)
        cb = jax.lax.all_to_all(ob, "model", split_axis=0, concat_axis=0,
                                tiled=False)
        y = _collect(cb.reshape(cfg.num_experts, cap, dd), flat_e, pos,
                     cap, keep)
        y = (y.reshape(t, k, dd) * w[..., None].astype(y.dtype)).sum(axis=1)
        return y.reshape(bl, sl, dd), aux

    rules = current_rules()
    baxes = tuple(a for a in rules.mesh_axes_for("batch", mesh)
                  if x.shape[0] % mesh.shape[a] == 0)
    # tokens are additionally split over 'model' along sequence unless the
    # batch dim already covers the model axis (full-DP variants)
    seq_entry = "model" if "model" not in baxes else None
    xspec = P(baxes if len(baxes) > 1 else (baxes[0] if baxes else None),
              seq_entry, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P("model"), P("model"), P("model"), xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)


def moe_ep_psum(p, x, cfg: ModelConfig, opts: MoEOptions = MoEOptions()):
    """Decode EP: tokens replicated over 'model'; each rank computes its
    local experts' share and the combine is a psum over 'model'."""
    mesh = current_mesh()
    m = mesh.shape["model"]
    e_loc = cfg.num_experts // m
    k = cfg.experts_per_token
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def body(router_w, w_gate, w_up, w_down, xs):
        bl, sl, dd = xs.shape
        t = bl * sl
        xt = xs.reshape(t, dd)
        w, e, aux = _route(router_w, xt, cfg)
        aux = jax.lax.pmean(aux, ("model",) + batch_axes)
        my = jax.lax.axis_index("model")
        local = (e // e_loc) == my                              # [T, k]
        le = jnp.where(local, e % e_loc, e_loc)                 # OOB -> drop
        cap = _capacity(t, e_loc, k, opts.capacity_factor * m)
        flat_e = le.reshape(t * k)
        pos = _positions_in_expert(flat_e, e_loc + 1)
        x_rep = jnp.repeat(xt, k, axis=0)
        buf, keep = _dispatch(x_rep, flat_e, pos, cap, e_loc)
        pl = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        ob = _expert_ffn(pl, buf)
        y = _collect(ob, flat_e, pos, cap, keep & (flat_e < e_loc))
        y = (y.reshape(t, k, dd) * w[..., None].astype(y.dtype)).sum(axis=1)
        y = jax.lax.psum(y, "model")
        return y.reshape(bl, sl, dd), aux

    rules = current_rules()
    xspec = P(rules.mesh_axes_for("batch", mesh) or None, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P("model"), P("model"), P("model"), xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)


def moe_block(p, x, cfg: ModelConfig, *, decode: bool = False,
              opts: MoEOptions = MoEOptions()):
    """Dispatching MoE entry point; adds the shared expert if configured.

    Returns (y [B,S,D], aux_loss scalar).

    Perf flag ``moe_gather_bf16`` (§Perf hillclimb): expert weight stacks
    are cast to bf16 BEFORE the shard_map boundary, so the ZeRO-style
    all-gather over the 'data' axis moves half the bytes (fp32 master
    copies stay in the optimizer; the cast is differentiable and the
    backward reduce-scatter is bf16 too).
    """
    mesh = current_mesh()
    s = x.shape[1]
    if current_flags().get("moe_gather_bf16"):
        p = dict(p)
        for k in ("w_gate", "w_up", "w_down"):
            p[k] = p[k].astype(jnp.bfloat16)
    use_ep = (mesh is not None and "model" in mesh.axis_names
              and mesh.shape["model"] > 1
              and cfg.num_experts % mesh.shape["model"] == 0)
    if not use_ep:
        y, aux = moe_local(p, x, cfg, opts)
    elif decode or s % mesh.shape["model"] != 0:
        y, aux = moe_ep_psum(p, x, cfg, opts)
    else:
        y, aux = moe_ep_a2a(p, x, cfg, opts)
    if cfg.shared_expert:
        from repro.runtime.sharding import gathered
        h = (jax.nn.silu(x @ gathered(p["s_gate"], "embed", "ffn",
                                      dtype=x.dtype))
             * (x @ gathered(p["s_up"], "embed", "ffn", dtype=x.dtype)))
        y = y + h @ gathered(p["s_down"], "ffn", "embed", dtype=x.dtype)
    return y, aux * opts.aux_weight
