"""Multi-head latent attention (deepseek-v2/v3): the absorbed decode step
through the latent cache equals the expanded attention that prefill and
training run, position by position; the cache holds one [latent, rotary
key] entry per token and layer and no V."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import build_model
from repro.models.attention import (mla_decode_attention, mla_self_attention,
                                    mla_specs)
from repro.models.params import init_params


def _cfg(**kw):
    return dataclasses.replace(get_config("moonlight-16b-a3b").reduced(),
                               **kw)


@pytest.mark.parametrize("heads", [4, 2])
def test_absorbed_decode_matches_expanded(heads):
    cfg = _cfg(num_heads=heads, num_kv_heads=heads)
    p = jax.tree.map(lambda a: a[0], init_params(mla_specs(cfg, 1),
                                                 jax.random.PRNGKey(0)))
    p["kv_norm"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(4),
                                               p["kv_norm"].shape)
    b, t, s_max = 2, 7, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, cfg.d_model))
    positions = jnp.arange(t)[None].repeat(b, 0)
    want = mla_self_attention(p, x, cfg, positions)
    cache = jnp.zeros((b, s_max, cfg.kv_lora_rank + cfg.qk_rope_head_dim))
    for i in range(t):
        got, cache = mla_decode_attention(p, x[:, i:i + 1], cfg, cache,
                                          jnp.full((b,), i, jnp.int32))
        np.testing.assert_allclose(np.asarray(got[:, 0]),
                                   np.asarray(want[:, i]),
                                   rtol=1e-4, atol=1e-4)
    # the slots past the last position are untouched
    assert not np.asarray(cache[:, t:]).any()


def test_latent_cache_has_no_v():
    cfg = get_config("moonlight-16b-a3b")
    specs = build_model(cfg).cache_specs(8, 256)
    assert list(specs) == ["kv"]
    assert specs["kv"].shape == (27, 8, 256, 512 + 64)
    assert specs["kv"].dtype == jnp.bfloat16


def test_published_widths():
    cfg = get_config("moonlight-16b-a3b")
    assert (cfg.head_dim, cfg.q_dim, cfg.shared_width, cfg.moe_layers) == (
        192, 16 * 192, 2816, 26)
    p = mla_specs(cfg, 1)
    assert p["wkv_a"].shape == (1, 2048, 576)
    assert p["wkv_b"].shape == (1, 512, 16 * 256)
    assert p["wo"].shape == (1, 16 * 128, 2048)
