"""The DeepSeek-V3 block comes to the benchmark as new files alone, as
``test_arch.py`` shows for a second decoder: ``bench/arch/deepseek_v3.py``
and its configuration ``data/tiny_moonlight.json``.  The whole run, but
for the look for a chip, reads correct at the tiny size, and reads not
correct where the program's routing ignores its selection bias, leaves
out its weights' scale, scores by softmax in place of the sigmoid, or
drops the assignments past a capacity."""

import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.tests import tiny

MOONLIGHT = Path(__file__).resolve().parent / "data" / "tiny_moonlight.json"


def _run_moonlight(monkeypatch, seed: int = 3) -> dict:
    """The whole run of a tiny Moonlight, the program in float32: the
    reference's precision, so that no routing decision flips at a near tie
    between the two and the numbers read the mathematics alone (on the chip,
    in bfloat16, the cell's limits are set from readings that hold such
    flips)."""
    from repro.models import layers

    monkeypatch.setattr(layers, "COMPUTE_DTYPE", jnp.float32)
    cell = tiny.cell("ddmd.qwen2-0.5b")
    cell["cfg"] = json.loads(MOONLIGHT.read_text())
    return bench_run.run("ddmd.qwen2-0.5b", seed, 1.0, False,
                         require_chip=False, cell=cell, log=lambda *a: None)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_moonlight_is_correct(monkeypatch, seed):
    res = _run_moonlight(monkeypatch, seed)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["bias_ignored", "scale_omitted",
                                   "softmax", "capacity_drops"])
def test_moonlight_routing_fault_is_not_correct(monkeypatch, fault):
    import dataclasses

    import jax

    from repro.models import moe

    route, held_ids = moe._route, moe._held_ids

    def faulty(router_w, x, cfg, bias=None):
        if fault == "bias_ignored":
            bias = jnp.zeros_like(bias)
        elif fault == "scale_omitted":
            cfg = dataclasses.replace(cfg, routed_scaling_factor=1.0)
        elif fault == "softmax":
            probs = jax.nn.softmax(x.astype(jnp.float32)
                                   @ router_w.astype(jnp.float32), axis=-1)
            _, e = jax.lax.top_k(probs + bias, cfg.experts_per_token)
            w = jnp.take_along_axis(probs, e, axis=1)
            w = w / w.sum(-1, keepdims=True) * cfg.routed_scaling_factor
            return w, e, jnp.zeros((), jnp.float32)
        return route(router_w, x, cfg, bias)

    def dropping(e, cfg):
        # the mesh paths' capacity at a factor of one, on the held experts
        le = held_ids(e, cfg)
        flat = le.reshape(-1)
        cap = max(1, flat.size // cfg.num_experts)
        pos = moe._positions_in_expert(flat, cfg.n_held + 1)
        return jnp.where(pos < cap, flat, cfg.n_held).reshape(le.shape)

    monkeypatch.setattr(moe, "_route", faulty)
    if fault == "capacity_drops":
        monkeypatch.setattr(moe, "_held_ids", dropping)
    res = _run_moonlight(monkeypatch)
    assert not res["correct"], res["checks"]
