"""MoE dispatch correctness: the one-device dispatch is dropless and must
reproduce a brute-force dense mixture at any capacity factor; deepseek-v3
routing (sigmoid scores, a selection bias, normalised and scaled weights)
against hand numbers; a layer that holds a share of the experts adds only
its share, and the shares add up to the whole layer; the EP (shard_map)
paths must match the local path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe
from repro.models.moe import MoEOptions, moe_block, moe_local, moe_specs
from repro.models.params import init_params


def tiny_cfg(**kw):
    base = get_config("qwen3-moe-30b-a3b").reduced()
    return dataclasses.replace(base, **kw) if kw else base


def brute_force(p, x, cfg):
    """Dense mixture: every expert on every token, mask to top-k."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d).astype(jnp.float32)
    logits = xt @ p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, e = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"])) * \
        jnp.einsum("td,edf->tef", xt, p["w_up"])
    y_all = jnp.einsum("tef,efd->ted", h, p["w_down"])   # [T, E, D]
    picked = jnp.take_along_axis(y_all, e[..., None], axis=1)
    return (picked * w[..., None]).sum(1).reshape(b, s, d)


def test_moe_local_matches_brute_force():
    cfg = tiny_cfg()
    specs = moe_specs(cfg, 1)
    p = jax.tree.map(lambda a: a[0],
                     init_params(specs, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y, aux, _ = moe_local(p, x, cfg, MoEOptions(capacity_factor=16.0))
    want = brute_force(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert float(aux) > 0


@pytest.mark.parametrize("factor", [0.1, 1.25, 16.0])
def test_moe_capacity_drops_tokens(factor):
    """The one-device layer drops no assignment, whatever the capacity
    factor (which only the mesh paths' buffers use), and counts each
    expert's assignments."""
    cfg = tiny_cfg()
    specs = moe_specs(cfg, 1)
    p = jax.tree.map(lambda a: a[0],
                     init_params(specs, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model),
                          jnp.float32)
    y, _, counts = moe_local(p, x, cfg, MoEOptions(capacity_factor=factor))
    want = brute_force(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    assert float(counts.sum()) == 2 * 16 * cfg.experts_per_token


def test_moe_block_adds_shared_expert():
    cfg = tiny_cfg(shared_expert=True)
    specs = moe_specs(cfg, 1)
    p = jax.tree.map(lambda a: a[0],
                     init_params(specs, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model),
                          jnp.float32)
    y, _, _ = moe_block(p, x, cfg, opts=MoEOptions(capacity_factor=16.0))
    y_no_shared, _, _ = moe_local(p, x, cfg,
                                  MoEOptions(capacity_factor=16.0))
    assert float(jnp.abs(y - y_no_shared).max()) > 1e-4


def test_moe_grads_flow_to_router():
    cfg = tiny_cfg()
    specs = moe_specs(cfg, 1)
    p = jax.tree.map(lambda a: a[0],
                     init_params(specs, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model),
                          jnp.float32)

    def loss(p):
        y, aux, _ = moe_local(p, x, cfg, MoEOptions(capacity_factor=4.0))
        return jnp.sum(y ** 2) + aux

    g = jax.grad(loss)(p)
    assert float(jnp.abs(g["router"]).sum()) > 0
    assert float(jnp.abs(g["w_gate"]).sum()) > 0


# ---------------------------------------------------------------------------
# deepseek-v3 routing and a held share of the experts (moonlight)
# ---------------------------------------------------------------------------

def ds_cfg(**kw):
    return dataclasses.replace(get_config("moonlight-16b-a3b").reduced(),
                               **kw)


def test_sigmoid_bias_routing_hand_numbers():
    """Selection on score + bias, weights the selected scores normalised
    to one and scaled by 2.446; no auxiliary loss."""
    cfg = ds_cfg(num_experts=4, experts_per_token=2)
    logits = np.array([[2.0, 1.0, 0.0, -1.0]], np.float32)
    x = jnp.ones((1, 4), jnp.float32)                   # x @ router = logits
    router = jnp.diag(jnp.asarray(logits[0]))
    bias = jnp.asarray([0.0, -0.5, 0.4, 0.0], jnp.float32)
    w, e, aux = moe._route(router, x, cfg, bias)
    s = 1 / (1 + np.exp(-logits[0]))                    # sigmoid scores
    # score + bias: [0.881, 0.231, 0.9, 0.269] -> experts 2 and 0
    assert sorted(np.asarray(e)[0].tolist()) == [0, 2]
    want = np.array([s[2], s[0]]) / (s[2] + s[0]) * 2.446
    got = dict(zip(np.asarray(e)[0].tolist(), np.asarray(w)[0].tolist()))
    np.testing.assert_allclose([got[2], got[0]], want, rtol=1e-6)
    assert float(aux) == 0.0


@pytest.mark.parametrize("scoring,topk", [("sigmoid", "greedy"),
                                          ("softmax", "noaux_tc")])
def test_untested_routing_pairings_are_refused(scoring, topk):
    """Only the two published pairings of scoring and selection run."""
    with pytest.raises(ValueError, match="routing"):
        ds_cfg(scoring_func=scoring, topk_method=topk)


def _ds_brute_force(p, x, cfg, bias):
    """Every held expert on every token, weighted by its routing weight
    where selected (the router spans all ``num_experts``)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    sc = jax.nn.sigmoid(xt @ p["router"])
    _, e = jax.lax.top_k(sc + bias, cfg.experts_per_token)
    w = jnp.take_along_axis(sc, e, 1)
    w = w / w.sum(-1, keepdims=True) * cfg.routed_scaling_factor
    held = cfg.first_held_expert + jnp.arange(cfg.n_held)
    gates = jnp.where(e[:, :, None] == held, w[:, :, None], 0).sum(1)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", xt, p["w_gate"])) * \
        jnp.einsum("td,edf->tef", xt, p["w_up"])
    y = jnp.einsum("tef,efd,te->td", h, p["w_down"], gates)
    return y.reshape(b, s, d)


def _ds_layer(cfg, seed=0):
    p = jax.tree.map(lambda a: a[0], init_params(moe_specs(cfg, 1),
                                                 jax.random.PRNGKey(seed)))
    p["router"] = p["router"] * 50            # spread the scores
    p["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                        (cfg.num_experts,))
    return p


def test_held_share_matches_brute_force():
    cfg = ds_cfg(held_experts=3, first_held_expert=2)
    p = _ds_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
    y, _, counts = moe_local(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_ds_brute_force(p, x, cfg,
                                                          p["bias"])),
                               rtol=2e-4, atol=2e-5)
    _, e, _ = moe._route(p["router"], x.reshape(16, -1), cfg, p["bias"])
    want = [int((np.asarray(e) == 2 + j).sum()) for j in range(3)]
    assert np.asarray(counts).tolist() == want


def test_shares_add_up_to_the_whole_layer():
    """The shares of every chip of an expert-parallel group, with the
    shared expert counted once, add up to the uncut layer."""
    whole = ds_cfg()                          # 8 experts, all held
    p = _ds_layer(whole)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, whole.d_model))
    want, _, _ = moe_block(p, x, whole)
    n = 4                                     # 4 chips of 2 experts
    total = 0.0
    for i in range(n):
        cut = dataclasses.replace(whole, held_experts=2,
                                  first_held_expert=2 * i)
        share = {k: (v[2 * i:2 * i + 2] if k.startswith("w_") else v)
                 for k, v in p.items()}
        y, _, _ = moe_local(share, x, cut)
        total = total + y
    shared = (jax.nn.silu(x @ p["s_gate"]) * (x @ p["s_up"])) @ p["s_down"]
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


_RAGGED_DOT = jax.lax.ragged_dot


def _unspecified_past_groups(lhs, rhs, group_sizes, **kw):
    """``jax.lax.ragged_dot`` with the rows past its last group left as
    NaN, in its output and in its input's cotangent: what the operation
    leaves unspecified, and a TPU need not write."""
    def rows_in_groups(n):
        return (jnp.arange(n) < group_sizes.sum())[:, None]

    @jax.custom_vjp
    def f(a, w):
        return jnp.where(rows_in_groups(a.shape[0]),
                         _RAGGED_DOT(a, w, group_sizes, **kw), jnp.nan)

    def fwd(a, w):
        return f(a, w), (a, w)

    def bwd(res, ct):
        _, vjp = jax.vjp(lambda a, w: _RAGGED_DOT(a, w, group_sizes, **kw),
                         *res)
        da, dw = vjp(ct)
        return jnp.where(rows_in_groups(da.shape[0]), da, jnp.nan), dw

    f.defvjp(fwd, bwd)
    return f(lhs, rhs)


@pytest.mark.parametrize("held", [1, 3])
def test_dropless_layer_reads_no_row_past_its_groups(monkeypatch, held):
    """The held share's output and gradients do not change where the
    grouped matmul leaves every row past its groups unspecified (the
    assignments to experts held elsewhere sort there)."""
    cfg = ds_cfg(held_experts=held, first_held_expert=8 - held)
    p = _ds_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, cfg.d_model))

    def loss(p, x):
        y, _, _ = moe_local(p, x, cfg)
        return jnp.sum(y * y)

    want = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot", _unspecified_past_groups)
    got = jax.value_and_grad(loss, argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)
