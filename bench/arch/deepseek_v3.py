"""The DeepSeek-V3 block (``"arch": "deepseek_v3"``), as Moonlight-16B-A3B
configures it.

Written from the published descriptions (DeepSeek-V2, arXiv 2405.04434:
multi-head latent attention, fine-grained routed experts beside shared
ones; DeepSeek-V3, arXiv 2412.19437: sigmoid affinity scores, top-k
selection on score plus a per-expert bias, weights the selected scores
normalised and scaled).  Pre-norm blocks with RMSNorm; the first
``first_k_dense_replace`` layers have a dense SwiGLU MLP, the rest an
expert layer; an untied output head.

Latent attention, without a query latent (``q_lora_rank`` null), in its
expanded form only: per head, the query's first ``qk_nope_head_dim``
dims meet keys expanded from the token's normed latent, its last
``qk_rope_head_dim`` dims, rotated, meet one rotated key that all heads
share; values are expanded from the latent; the scale is the query
width to the power -1/2.

Expert layer: this chip holds ``n_routed_experts`` of the router's
``router_experts`` outputs, from ``first_held_expert``.  The router
scores every expert and selects over all of them; only the held
experts' part is added (dense over the held experts: each runs on every
token, masked by the selection), and the shared experts on every token.
The selection bias is a parameter that no step moves: it is one flat
leaf (``router_bias``), which the AdamW step does not decay, and no
gradient reaches it through the selection.

The parameter tree follows the layout the program reads.  Norm scales
are drawn away from ones and the bias away from zeros (std
``router_bias_std``); every matrix at its fan-in scale.  The reference
imports nothing of the program under test.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops, nn
from bench.arch import DECODE, PREFILL, TRAIN

#: weight matrices the control quantises, by group (norm scales, the
#: router and the selection bias stay)
_MATRICES = {"attn": ("wq", "wkv_a", "wkv_b", "wo"),
             "mlp": ("gate", "up", "down"),
             "moe": ("w_gate", "w_up", "w_down", "s_gate", "s_up", "s_down")}


def _sizes(cfg: dict) -> dict:
    h = cfg["num_attention_heads"]
    return dict(d=cfg["hidden_size"], h=h, dn=cfg["qk_nope_head_dim"],
                dr=cfg["qk_rope_head_dim"], dv=cfg["v_head_dim"],
                r=cfg["kv_lora_rank"], dense=cfg["first_k_dense_replace"],
                moe=cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
                e=cfg["router_experts"], held=cfg["n_routed_experts"],
                fe=cfg["moe_intermediate_size"],
                fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
                f=cfg["intermediate_size"], v=cfg["vocab_size"])


# -- the program --------------------------------------------------------------
def program_config(cfg: dict, log=None):
    """The program's ModelConfig for a configuration file (see
    ``bench/arch``)."""
    import dataclasses

    from repro.models.config import ModelConfig

    z = _sizes(cfg)
    mc = ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=z["d"],
        num_heads=z["h"], num_kv_heads=cfg["num_key_value_heads"],
        d_ff=z["f"], vocab_size=z["v"], kv_lora_rank=z["r"],
        qk_nope_head_dim=z["dn"], qk_rope_head_dim=z["dr"],
        v_head_dim=z["dv"], num_experts=z["e"],
        experts_per_token=cfg["num_experts_per_tok"], moe_d_ff=z["fe"],
        shared_expert=cfg["n_shared_experts"] > 0, shared_d_ff=z["fs"],
        norm_topk=cfg["norm_topk_prob"], scoring_func=cfg["scoring_func"],
        topk_method=cfg["topk_method"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        held_experts=z["held"], first_held_expert=cfg["first_held_expert"],
        first_k_dense_replace=z["dense"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"])
    if cfg.get("program_arch") and log:
        from repro.configs import get_config

        # the cut (``reduced``): depth, the experts held, the vocabulary
        theirs = dataclasses.replace(
            get_config(cfg["program_arch"]), name=mc.name,
            num_layers=mc.num_layers, held_experts=mc.held_experts,
            first_held_expert=mc.first_held_expert,
            vocab_size=mc.vocab_size)
        for f in dataclasses.fields(mc):
            ours, reg = getattr(mc, f.name), getattr(theirs, f.name)
            if ours != reg:
                log(f"{cfg['name']}: the program's registry has {f.name} "
                    f"{reg!r}; the file's {ours!r} runs")
    return mc


# -- weights ------------------------------------------------------------------
def _layers(z: dict, n: int, moe: bool, prefix: str) -> dict:
    d, h, r = z["d"], z["h"], z["r"]
    out = {
        (prefix, "ln1"): ((n, d), "scale", 0.05),
        (prefix, "ln2"): ((n, d), "scale", 0.05),
        (prefix, "attn", "wq"): ((n, d, h * (z["dn"] + z["dr"])), "normal",
                                 d ** -0.5),
        (prefix, "attn", "wkv_a"): ((n, d, r + z["dr"]), "normal", d ** -0.5),
        (prefix, "attn", "kv_norm"): ((n, r), "scale", 0.05),
        (prefix, "attn", "wkv_b"): ((n, r, h * (z["dn"] + z["dv"])), "normal",
                                    r ** -0.5),
        (prefix, "attn", "wo"): ((n, h * z["dv"], d), "normal",
                                 (h * z["dv"]) ** -0.5),
    }
    if not moe:
        f = z["f"]
        out |= {(prefix, "mlp", "gate"): ((n, d, f), "normal", d ** -0.5),
                (prefix, "mlp", "up"): ((n, d, f), "normal", d ** -0.5),
                (prefix, "mlp", "down"): ((n, f, d), "normal", f ** -0.5)}
        return out
    held, fe, fs = z["held"], z["fe"], z["fs"]
    return out | {
        (prefix, "moe", "router"): ((n, d, z["e"]), "normal", d ** -0.5),
        (prefix, "moe", "w_gate"): ((n, held, d, fe), "normal", d ** -0.5),
        (prefix, "moe", "w_up"): ((n, held, d, fe), "normal", d ** -0.5),
        (prefix, "moe", "w_down"): ((n, held, fe, d), "normal", fe ** -0.5),
        (prefix, "moe", "s_gate"): ((n, d, fs), "normal", d ** -0.5),
        (prefix, "moe", "s_up"): ((n, d, fs), "normal", d ** -0.5),
        (prefix, "moe", "s_down"): ((n, fs, d), "normal", fs ** -0.5),
    }


def layout(cfg: dict) -> dict:
    z = _sizes(cfg)
    d, v = z["d"], z["v"]
    out = {
        ("embedding",): ((v, d), "normal", 0.02),
        ("lm_head",): ((d, v), "normal", d ** -0.5),
        ("final_norm",): ((d,), "scale", 0.05),
        ("router_bias",): ((z["moe"] * z["e"],), "bias",
                           cfg["router_bias_std"]),
    }
    out |= _layers(z, z["moe"], True, "blocks")
    if z["dense"]:
        out |= _layers(z, z["dense"], False, "dense")
    return out


# -- the plain reference ------------------------------------------------------
def cast_params(params, precision: str):
    if precision == "float32":
        return params
    out = jax.tree.map(lambda x: x, params)
    for stack in ("blocks", "dense"):
        for grp, names in _MATRICES.items():
            for k in names:
                if k in out.get(stack, {}).get(grp, {}):
                    # [..., in, out]: one scale per output column
                    out[stack][grp][k] = nn.quant_int8(out[stack][grp][k], -2)
    out["lm_head"] = nn.quant_int8(out["lm_head"], -2)
    out["embedding"] = nn.quant_int8(out["embedding"], -1)
    return out


def _dot(a, b, precision):
    """Operands and keywords of an einsum of two activations: float32 at
    ``HIGHEST``, or, under the control, bfloat16 summed in float32."""
    if precision == "float32":
        return a, b, dict(precision=nn.HIGHEST)
    return (nn.act(a, precision), nn.act(b, precision),
            dict(preferred_element_type=jnp.float32))


def attention(p, h, cfg, precision):
    """Latent attention, expanded: [B, S, d] -> [B, S, d]."""
    z = _sizes(cfg)
    b, s, _ = h.shape
    nh, dn, dr, dv, r = z["h"], z["dn"], z["dr"], z["dv"], z["r"]
    theta = cfg["rope_theta"]
    q = nn.mm(h, p["wq"], precision).reshape(b, s, nh, dn + dr)
    q = jnp.concatenate([q[..., :dn], nn.rope(q[..., dn:], theta)], -1)
    kv = nn.mm(h, p["wkv_a"], precision)
    c = nn.rms(kv[..., :r], p["kv_norm"], cfg["kv_a_layernorm_eps"])
    k_rope = nn.rope(kv[..., None, r:], theta)                 # [B, S, 1, dr]
    kvb = nn.mm(nn.act(c, precision), p["wkv_b"], precision).reshape(
        b, s, nh, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn], jnp.broadcast_to(k_rope, (b, s, nh, dr))], -1)
    v = kvb[..., dn:]
    qa, ka, kw = _dot(q, k, precision)
    sc = jnp.einsum("bqhd,bkhd->bhqk", qa, ka, **kw) * (dn + dr) ** -0.5
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    pr = jax.nn.softmax(jnp.where(mask[None, None], sc, -jnp.inf), axis=-1)
    pa, va, kw = _dot(pr, v, precision)
    o = jnp.einsum("bhqk,bkhd->bqhd", pa, va, **kw).reshape(b, s, nh * dv)
    return nn.mm(o, p["wo"], precision)


def _swiglu(h, gate, up, down, precision):
    g = nn.mm(h, gate, precision)
    u = nn.mm(h, up, precision)
    return nn.mm(nn.act(jax.nn.silu(g) * u, precision), down, precision)


def route(h, router, bias, cfg):
    """Selection and weights of every token [T, d] over all of the
    router's experts: (weights [T, k], experts [T, k]).  Float32: sigmoid
    scores; the top ``num_experts_per_tok`` of score plus bias (one
    group); the selected scores, normalised to sum to one, times
    ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(jnp.matmul(h.astype(jnp.float32), router,
                                       precision=nn.HIGHEST))
    _, e = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, e, axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"], e


def experts(p, bias, h, cfg, precision):
    """The held experts' part of the expert layer plus the shared experts:
    [B, S, d] -> [B, S, d]."""
    z = _sizes(cfg)
    b, s, d = h.shape
    x = h.reshape(b * s, d)
    w, e = route(x, p["router"], bias, cfg)
    held = cfg["first_held_expert"] + jnp.arange(z["held"])
    # each held expert's weight for each token; nought where not selected
    gates = jnp.sum(jnp.where(e[:, :, None] == held[None, None, :],
                              w[:, :, None], 0.0), axis=1)     # [T, held]

    def emm(spec, a, wt):
        # an expert matmul: under the control its input is rounded to int8
        # per row, as ``nn.mm`` rounds a layer's
        if precision != "float32":
            a = nn.quant_int8(a, -1)
        return jnp.einsum(spec, a, wt, precision=nn.HIGHEST)

    g = emm("td,edf->tef", x, p["w_gate"])
    u = emm("td,edf->tef", x, p["w_up"])
    y = emm("tef,efd->ted", nn.act(jax.nn.silu(g) * u, precision),
            p["w_down"])
    routed = jnp.einsum("ted,te->td", y, gates, precision=nn.HIGHEST)
    shared = _swiglu(x, p["s_gate"], p["s_up"], p["s_down"], precision)
    return (routed + shared).reshape(b, s, d)


def _layer(x, p, bias, cfg, precision):
    eps = cfg["rms_norm_eps"]
    h = nn.act(nn.rms(x, p["ln1"], eps), precision)
    x = x + attention(p["attn"], h, cfg, precision)
    h = nn.act(nn.rms(x, p["ln2"], eps), precision)
    if "moe" in p:
        x = x + experts(p["moe"], bias, h, cfg, precision)
    else:
        m = p["mlp"]
        x = x + _swiglu(h, m["gate"], m["up"], m["down"], precision)
    return nn.act(x, precision).astype(jnp.float32)


def hidden(params, tokens, cfg, precision="float32"):
    z = _sizes(cfg)
    x = params["embedding"][tokens].astype(jnp.float32)
    for i in range(z["dense"]):
        p = jax.tree.map(lambda a: a[i], params["dense"])
        x = _layer(x, p, None, cfg, precision)
    bias = params["router_bias"].reshape(z["moe"], z["e"])
    x, _ = jax.lax.scan(
        lambda x, pb: (_layer(x, pb[0], pb[1], cfg, precision), None),
        x, (params["blocks"], bias))
    return nn.rms(x, params["final_norm"], cfg["rms_norm_eps"])


def unembed(params, h, cfg, precision="float32"):
    return nn.mm(nn.act(h, precision), params["lm_head"], precision)


# -- model FLOPs and kernel costs ---------------------------------------------
def matmul_params(cfg: dict) -> tuple[int, int]:
    """(parameters a token's matmuls use in the blocks, counting the
    routed experts at the expected share of a token that reaches a held
    expert, num_experts_per_tok x n_routed_experts / router_experts = 0.75
    here; parameters of the output head)."""
    z = _sizes(cfg)
    d, h = z["d"], z["h"]
    attn = (d * h * (z["dn"] + z["dr"]) + d * (z["r"] + z["dr"])
            + z["r"] * h * (z["dn"] + z["dv"]) + h * z["dv"] * d)
    share = cfg["num_experts_per_tok"] * z["held"] / z["e"]
    moe = d * z["e"] + 3 * d * z["fs"] + share * 3 * d * z["fe"]
    blocks = (cfg["num_hidden_layers"] * attn + z["dense"] * 3 * d * z["f"]
              + z["moe"] * moe)
    return blocks, d * z["v"]


def _attn_flops(cfg: dict, pairs: float, absorbed: bool = False) -> float:
    """QK^T and PV per query head, all layers: widths nope + rope and v
    expanded, latent + rope and latent absorbed."""
    z = _sizes(cfg)
    qk, pv = ((z["r"] + z["dr"], z["r"]) if absorbed
              else (z["dn"] + z["dr"], z["dv"]))
    return 2 * z["h"] * (qk + pv) * pairs * cfg["num_hidden_layers"]


def step_flops(cfg: dict, shapes: dict) -> dict:
    blocks, head = matmul_params(cfg)
    tb, ts = shapes["train_batch"], shapes["train_seq"]
    pb, ps = shapes["prefill_batch"], shapes["prefill_seq"]
    db = shapes["decode_batch"]
    ctx = flops.mean_context(shapes)
    return {
        TRAIN: 3 * (2 * (blocks + head) * tb * ts
                    + _attn_flops(cfg, tb * flops.attended_pairs(cfg, ts))),
        PREFILL: (2 * blocks * pb * ps + 2 * head * pb
                  + _attn_flops(cfg, pb * flops.attended_pairs(cfg, ps))),
        DECODE: (2 * (blocks + head) * db
                 + _attn_flops(cfg, db * ctx, absorbed=True)),
    }


def flash_cost(cfg: dict, batch: int, seq: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's expanded attention call: queries and
    keys of nope + rope, values and output of v per head, bfloat16."""
    z = _sizes(cfg)
    pairs = batch * flops.attended_pairs(cfg, seq)
    qk, v = z["dn"] + z["dr"], z["dv"]
    moved = batch * seq * z["h"] * (2 * qk + 2 * v)
    return 2 * z["h"] * (qk + v) * pairs, moved * flops.BF16


def decode_attn_cost(cfg: dict, batch: int, ctx: float) -> tuple[float,
                                                                   float]:
    """(FLOPs, bytes) of one layer's absorbed decode call: each row's
    queries of latent + rope against the written cache positions, read
    once (the values are their latent lanes), outputs of the latent's
    width."""
    z = _sizes(cfg)
    w = z["r"] + z["dr"]
    flops_ = 2 * z["h"] * (w + z["r"]) * batch * ctx
    moved = batch * (z["h"] * (w + z["r"]) + ctx * w)
    return flops_, moved * flops.BF16


def kernel_costs(cfg: dict, shapes: dict) -> dict:
    return {
        "flash_attention_pallas": {
            TRAIN: flash_cost(cfg, shapes["train_batch"], shapes["train_seq"]),
            PREFILL: flash_cost(cfg, shapes["prefill_batch"],
                                shapes["prefill_seq"])},
        "decode_attention_pallas": {
            DECODE: decode_attn_cost(cfg, shapes["decode_batch"],
                                     flops.mean_context(shapes))},
    }
