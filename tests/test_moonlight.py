"""Moonlight-16B-A3B (the deepseek-v3 block) through the program's normal
path against the benchmark's plain reference (``bench/arch/deepseek_v3.py``,
float32, written from the published equations) on seeded weights at a
small size: logits, loss and gradients; and the selection bias, which no
train step moves."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference, weights  # noqa: E402
from bench.arch import deepseek_v3  # noqa: E402
from repro.models import layers  # noqa: E402
from repro.models.api import build_model  # noqa: E402
from repro.runtime import TrainOptions  # noqa: E402
from repro.runtime.steps import TrainState, build_train_step  # noqa: E402
from repro.optim import adamw_init  # noqa: E402

CFG = json.loads((ROOT / "bench" / "tests" / "data"
                  / "tiny_moonlight.json").read_text())


@pytest.fixture()
def float32_program(monkeypatch):
    """The program computing in float32, as the reference does: with
    rounding out of the way, a routing decision can not flip at a near
    tie, and what is left is the mathematics."""
    monkeypatch.setattr(layers, "COMPUTE_DTYPE", jnp.float32)
    return build_model(deepseek_v3.program_config(CFG))


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_program_matches_reference(float32_program, seed):
    model = float32_program
    params = weights.make_params(CFG, seed)
    assert (jax.tree.structure(params)
            == jax.tree.structure(model.specs(),
                                  is_leaf=lambda x: hasattr(x, "shape")))
    toks, labels = weights.tokens(11, 2, 16, CFG["vocab_size"])
    where = jnp.broadcast_to(jnp.arange(16), toks.shape)
    want = np.asarray(reference.logits_at(params, toks, where, CFG))
    got = np.asarray(model.forward(params, {"tokens": toks})[0])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4 * want.std())

    batch = {"tokens": toks, "labels": labels}
    lp, gp = jax.value_and_grad(model.loss)(params, batch)
    lr, gr = jax.value_and_grad(reference.loss)(params, toks, labels, CFG)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gr)):
        scale = max(float(jnp.abs(b).max()), 1e-12)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3,
                                   atol=1e-3 * scale, err_msg=str(path))
    assert not np.asarray(gp["router_bias"]).any()


def test_bias_unchanged_by_a_train_step():
    model = build_model(deepseek_v3.program_config(CFG))
    params = weights.make_params(CFG, 7)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt=adamw_init(params))
    bias = np.asarray(params["router_bias"])
    router = np.asarray(params["blocks"]["moe"]["router"])
    # warmup 1: the learning rate is the peak's from the second step
    train, _ = build_train_step(model, opts=TrainOptions(warmup=1,
                                                         total_steps=10))
    toks, labels = weights.tokens(12, 2, 16, CFG["vocab_size"])
    for _ in range(3):
        state, metrics = train(state, {"tokens": toks, "labels": labels})
    np.testing.assert_array_equal(np.asarray(state.params["router_bias"]),
                                  bias)
    assert np.abs(np.asarray(state.params["blocks"]["moe"]["router"])
                  - router).max() > 0
    # each token's assignments to the held experts, over the two layers
    counts = np.asarray(metrics["expert_tokens"])
    assert counts.shape == (CFG["n_routed_experts"],)
    assert 0 < counts.sum() <= 2 * 16 * CFG["num_experts_per_tok"] * 2
