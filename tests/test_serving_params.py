"""``Model.serving_params``: the weights that prefill and decode cast to
bfloat16 at every use, cast once, give the same outputs as the float32
tree they came from."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.api import build_model
from repro.models.params import init_params
from repro.runtime.steps import build_decode_step, build_prefill_step

#: leaves the transformer families' steps only use cast to bfloat16
CAST = {"wq", "wk", "wv", "wo", "gate", "up", "down", "embedding", "lm_head",
        "wkv_a", "wkv_b", "w_gate", "w_up", "w_down", "s_gate", "s_up",
        "s_down"}


# tied, untied, latent attention with a leading dense layer and experts
@pytest.fixture(scope="module",
                params=["qwen2-0.5b", "h2o-danube-1.8b", "moonlight-16b-a3b"])
def model_params(request):
    model = build_model(get_config(request.param).reduced())
    return model, init_params(model.specs(), jax.random.PRNGKey(1))


def _leaves(tree) -> dict:
    return {path[-1].key: leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def test_serving_tree_dtypes(model_params):
    """The listed matrices are bfloat16; norm scales and biases stay
    float32; shapes and structure are the float32 tree's."""
    model, params = model_params
    serving = model.serving_params(params)
    assert jax.tree.structure(serving) == jax.tree.structure(params)
    assert jax.tree.map(jnp.shape, serving) == jax.tree.map(jnp.shape, params)
    leaves = _leaves(serving)
    for name, leaf in leaves.items():
        want = jnp.bfloat16 if name in CAST else jnp.float32
        assert leaf.dtype == want, name
    kept = {"ln1", "ln2", "final_norm"}
    if model.cfg.qkv_bias:
        kept |= {"bq", "bk", "bv"}
    if model.cfg.mla:
        kept |= {"kv_norm", "router", "router_bias"}
    assert kept <= leaves.keys() and not kept & CAST
    assert ("lm_head" in leaves) == (not model.cfg.tie_embeddings)


def test_prefill_same_on_serving_tree(model_params):
    model, params = model_params
    prefill, _ = build_prefill_step(model)
    batch = model.make_batch(jax.random.PRNGKey(2), batch=2, seq=16,
                             mode="prefill")
    want = np.asarray(prefill(params, batch))
    got = np.asarray(prefill(model.serving_params(params), batch))
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())


def test_decode_same_on_serving_tree(model_params):
    """A greedy rollout from fresh caches: the same tokens and logits at
    every step."""
    model, params = model_params
    decode, _ = build_decode_step(model, batch=2, s_max=16)
    serving = model.serving_params(params)

    def rollout(tree):
        cache = init_params(model.cache_specs(2, 16), jax.random.PRNGKey(0))
        tok = jnp.full((2, 1), 3, jnp.int32)
        out = []
        for t in range(4):
            pos = jnp.full((2,), t, jnp.int32)
            nxt, logits, cache = decode(tree, cache, tok, pos)
            out.append((np.asarray(nxt), np.asarray(logits)))
            tok = nxt[:, None]
        return out

    for (tok_a, log_a), (tok_b, log_b) in zip(rollout(params),
                                              rollout(serving)):
        np.testing.assert_array_equal(tok_b, tok_a)
        np.testing.assert_allclose(log_b, log_a, rtol=1e-6,
                                   atol=1e-6 * np.abs(log_a).max())


def test_decode_converts_no_weight(model_params):
    """The decode step on the serving tree casts no weight: its program
    has no convert of a float32 value shaped as a cast weight (or one layer
    of one)."""
    model, params = model_params
    serving = model.serving_params(params)
    shapes = _cast_shapes(serving)
    decode, _ = build_decode_step(model, batch=2, s_max=16)
    cache = init_params(model.cache_specs(2, 16), jax.random.PRNGKey(0))
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    text = decode.lower(serving, cache, tok, pos).as_text()
    assert not _weight_converts(text, shapes)


def _cast_shapes(serving) -> set:
    """Shapes of the serving tree's bfloat16 matrices, and of one layer of
    each stacked one."""
    out = set()
    for leaf in jax.tree.leaves(serving):
        if leaf.dtype == jnp.bfloat16:
            out |= {leaf.shape} | ({leaf.shape[1:]} if leaf.ndim >= 3
                                   else set())
    return out


def _weight_converts(text: str, shapes: set) -> set:
    """Shapes among ``shapes`` of float32 values converted to bfloat16 in
    a lowered program's text (StableHLO, as the step asks for it, before a
    backend adds conversions of its own)."""
    import re

    found = re.findall(r"stablehlo\.convert %\S+ : \(tensor<([0-9x]+)xf32>\)"
                       r" -> tensor<[0-9x]+xbf16>", text)
    return {tuple(int(d) for d in dims.split("x")) for dims in found} & shapes


def test_weight_converts_seen_on_the_float32_tree():
    """The check above sees the casts where the step does them: the
    same step on the float32 tree converts weight-shaped operands."""
    model = build_model(get_config("moonlight-16b-a3b").reduced())
    params = init_params(model.specs(), jax.random.PRNGKey(1))
    shapes = _cast_shapes(model.serving_params(params))
    decode, _ = build_decode_step(model, batch=2, s_max=16)
    cache = init_params(model.cache_specs(2, 16), jax.random.PRNGKey(0))
    text = decode.lower(params, cache, jnp.zeros((2, 1), jnp.int32),
                        jnp.zeros((2,), jnp.int32)).as_text()
    assert _weight_converts(text, shapes)


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "zamba2-1.2b",
                                  "whisper-tiny"])
def test_families_without_a_set_unchanged(arch):
    model = build_model(get_config(arch).reduced())
    params = model.specs()
    assert model.serving_params(params) is params
