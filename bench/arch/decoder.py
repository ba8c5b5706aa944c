"""The dense decoder-only transformer (``"arch": "decoder"``).

Written from the published descriptions (Qwen2, arXiv 2407.10671; H2O
Danube, arXiv 2401.16818): pre-norm blocks with RMSNorm, rotary
embeddings (rotate-half form), grouped-query causal attention with an
optional sliding window and QKV bias, a SwiGLU MLP, and a tied or
separate output head.  The reference has no kernels, no cache and no
batching tricks, and imports nothing of the program under test.

The parameter tree follows the layout the program's decoder reads
(stacked layers under ``blocks``).  Norm scales and QKV biases are drawn
away from the program's own initialisation (ones, zeros) so that a
program that ignored them would read as wrong.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import flops, nn
from bench.arch import DECODE, PREFILL, TRAIN

#: weight matrices the control quantises (norm scales and biases stay)
_MATRICES = ("wq", "wk", "wv", "wo", "gate", "up", "down")


# -- the program --------------------------------------------------------------
def program_config(cfg: dict, log=None):
    """The program's ModelConfig for a configuration file (see
    ``bench/arch``)."""
    import dataclasses

    from repro.models.config import ModelConfig

    mc = ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=cfg["attention_bias"],
        sliding_window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=cfg["rms_norm_eps"])
    if cfg.get("program_arch"):
        from repro.configs import get_config

        theirs = dataclasses.replace(get_config(cfg["program_arch"]),
                                     name=mc.name)
        if "num_hidden_layers" in cfg.get("reduced", {}):
            theirs = dataclasses.replace(theirs, num_layers=mc.num_layers)
        for f in dataclasses.fields(mc):
            ours, reg = getattr(mc, f.name), getattr(theirs, f.name)
            if ours != reg and log:
                log(f"{cfg['name']}: the program's registry has {f.name} "
                    f"{reg!r}; the file's {ours!r} runs")
    return mc


# -- weights ------------------------------------------------------------------
def layout(cfg: dict) -> dict:
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


# -- the plain reference ------------------------------------------------------
def cast_params(params, precision: str):
    if precision == "float32":
        return params
    out = jax.tree.map(lambda x: x, params)
    blocks = out["blocks"]
    # stacked [L, in, out] matrices: one scale per (layer, output column)
    for grp in ("attn", "mlp"):
        for k, w in blocks[grp].items():
            if k in _MATRICES:
                blocks[grp][k] = nn.quant_int8(w, -2)
    if "lm_head" in out:
        out["lm_head"] = nn.quant_int8(out["lm_head"], -2)
    # the embedding table is also the tied head: one scale per token row
    out["embedding"] = nn.quant_int8(out["embedding"], -1)
    return out


def qkv(p, h, cfg, precision):
    """Queries [B, S, H, D], keys and values [B, S, KV, D] of one layer:
    projections, QKV bias, rotary embeddings."""
    b, s, _ = h.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = nn.mm(h, p["wq"], precision)
    k = nn.mm(h, p["wk"], precision)
    v = nn.mm(h, p["wv"], precision)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = nn.rope(q.reshape(b, s, nh, hd), cfg["rope_theta"])
    k = nn.rope(k.reshape(b, s, nkv, hd), cfg["rope_theta"])
    return q, k, v.reshape(b, s, nkv, hd)


def attend(q, k, v, cfg, precision):
    """Grouped-query causal attention, optionally windowed: [B, S, H * D]."""
    b, s, nh, hd = q.shape
    group = nh // k.shape[2]
    k = jnp.repeat(k, group, axis=2)          # query head h reads kv h//group
    v = jnp.repeat(v, group, axis=2)
    if precision == "float32":
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=nn.HIGHEST)
    else:
        sc = jnp.einsum("bqhd,bkhd->bhqk", nn.act(q, precision),
                        nn.act(k, precision),
                        preferred_element_type=jnp.float32)
    sc = sc * hd ** -0.5
    qp = jnp.arange(s)[:, None]
    kp = jnp.arange(s)[None, :]
    mask = kp <= qp
    if cfg.get("sliding_window"):
        mask &= kp > qp - cfg["sliding_window"]
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    if precision == "float32":
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v, precision=nn.HIGHEST)
    else:
        o = jnp.einsum("bhqk,bkhd->bqhd", nn.act(pr, precision),
                       nn.act(v, precision),
                       preferred_element_type=jnp.float32)
    return o.reshape(b, s, nh * hd)


def layers(params, tokens, cfg, precision, make_qkv):
    """Final-normed hidden states [B, S, d] of the pre-norm blocks over
    ``tokens`` [B, S]; ``make_qkv`` has ``qkv``'s signature."""
    eps = cfg["rms_norm_eps"]
    x = params["embedding"][tokens].astype(jnp.float32)

    def layer(x, p):
        h = nn.act(nn.rms(x, p["ln1"], eps), precision)
        a = p["attn"]
        o = attend(*make_qkv(a, h, cfg, precision), cfg, precision)
        x = x + nn.mm(o, a["wo"], precision)
        h = nn.act(nn.rms(x, p["ln2"], eps), precision)
        m = p["mlp"]
        g = nn.mm(h, m["gate"], precision)
        u = nn.mm(h, m["up"], precision)
        x = x + nn.mm(nn.act(jax.nn.silu(g) * u, precision), m["down"],
                      precision)
        return nn.act(x, precision).astype(jnp.float32), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return nn.rms(x, params["final_norm"], eps)


def hidden(params, tokens, cfg, precision="float32"):
    return layers(params, tokens, cfg, precision, qkv)


def unembed(params, h, cfg, precision="float32"):
    w = (params["embedding"].T if cfg["tie_word_embeddings"]
         else params["lm_head"])
    return nn.mm(nn.act(h, precision), w, precision)


# -- model FLOPs and kernel costs ---------------------------------------------
def matmul_params(cfg: dict) -> tuple[int, int]:
    """(parameters in the blocks' matmuls, parameters of the output
    head's matmul)."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    f = cfg["intermediate_size"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return cfg["num_hidden_layers"] * per_layer, d * cfg["vocab_size"]


def _attn_flops(cfg: dict, pairs: int) -> int:
    # QK^T and PV, per query head and layer
    return (4 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
            * cfg["num_hidden_layers"])


def train_flops(cfg: dict, batch: int, seq: int) -> int:
    blocks, head = matmul_params(cfg)
    fwd = (2 * (blocks + head) * batch * seq
           + _attn_flops(cfg, batch * flops.attended_pairs(cfg, seq)))
    return 3 * fwd


def prefill_flops(cfg: dict, batch: int, seq: int) -> int:
    blocks, head = matmul_params(cfg)
    return (2 * blocks * batch * seq + 2 * head * batch
            + _attn_flops(cfg, batch * flops.attended_pairs(cfg, seq)))


def decode_flops(cfg: dict, batch: int, ctx: float) -> float:
    """One token per row against ``ctx`` cached positions (the new one
    included)."""
    blocks, head = matmul_params(cfg)
    return (2 * (blocks + head) * batch
            + _attn_flops(cfg, batch * flops.window(cfg, ctx)))


def step_flops(cfg: dict, shapes: dict) -> dict:
    return {
        TRAIN: train_flops(cfg, shapes["train_batch"], shapes["train_seq"]),
        PREFILL: prefill_flops(cfg, shapes["prefill_batch"],
                               shapes["prefill_seq"]),
        DECODE: decode_flops(cfg, shapes["decode_batch"],
                             flops.mean_context(shapes)),
    }


def kernel_costs(cfg: dict, shapes: dict) -> dict:
    return {
        "flash_attention_pallas": {
            TRAIN: flops.flash_cost(cfg, shapes["train_batch"],
                                    shapes["train_seq"]),
            PREFILL: flops.flash_cost(cfg, shapes["prefill_batch"],
                                      shapes["prefill_seq"])},
        "decode_attention_pallas": {
            DECODE: flops.decode_attn_cost(cfg, shapes["decode_batch"],
                                           flops.mean_context(shapes))},
    }
