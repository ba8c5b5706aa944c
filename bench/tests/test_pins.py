"""The decoder architecture module computes what the benchmark computed
before architectures had modules of their own, bit for bit.

``data/pins.json`` was recorded from the tree before ``bench/arch``
existed, with the digests below: each configuration's parameter layout,
model FLOPs per step and kernel costs at the ``ddmd`` shapes, and, for
the tiny configuration on the CPU, its seeded parameters and the plain
reference's logits, loss, gradient norms and first AdamW step at float32
and under the control."""

import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, reference, weights

ROOT = Path(__file__).resolve().parents[2]
PINS = json.loads((Path(__file__).parent / "data" / "pins.json").read_text())
CONFIGS = {"qwen2-0.5b": "bench/configs/qwen2-0.5b.json",
           "h2o-danube-1.8b": "bench/configs/h2o-danube-1.8b.json",
           "tiny": "bench/tests/data/tiny.json"}
TRAFFIC = json.loads((ROOT / "bench/traffic/ddmd.json").read_text())


def _cfg(name):
    return json.loads((ROOT / CONFIGS[name]).read_text())


def _digest(x) -> str:
    a = np.ascontiguousarray(np.asarray(x))
    h = hashlib.sha256()
    h.update(f"{a.dtype}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()


def _tree_digest(tree) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
        h.update(str(path).encode())
        h.update(_digest(leaf).encode())
    return h.hexdigest()


@pytest.mark.parametrize("name", list(CONFIGS))
def test_layout_flops_and_kernel_costs(name):
    cfg = _cfg(name)
    mod = arch.of(cfg)
    lay = repr(sorted(mod.layout(cfg).items())).encode()
    assert hashlib.sha256(lay).hexdigest() == PINS["layout"][name]
    shapes = TRAFFIC["shapes"]
    assert mod.step_flops(cfg, shapes) == PINS["flops"][name]
    costs = {k: {p: list(c) for p, c in v.items()}
             for k, v in mod.kernel_costs(cfg, shapes).items()}
    assert costs == PINS["kernels"][name]


def test_tiny_weights_and_reference():
    cfg = _cfg("tiny")
    pins = PINS["tiny_reference"]
    params = weights.make_params(cfg, pins["seed"])
    assert _tree_digest(params) == pins["params"]
    toks, labels = weights.tokens(100 + 7, 2, 16, cfg["vocab_size"])
    where = jnp.broadcast_to(jnp.arange(16), toks.shape)
    opt = TRAFFIC["optimizer"]
    for prec in ("float32", "int8"):
        lg = reference.logits_at(params, toks, where, cfg, prec)
        assert _digest(lg) == pins[f"logits_{prec}"]
        assert float(reference.loss(params, toks, labels, cfg, prec)) == \
            pins[f"loss_{prec}"]
        loss, norms, gnorm = reference.grad_norms(params, toks, labels, cfg,
                                                  opt, prec)
        assert [float(loss), _digest(norms), float(gnorm)] == \
            pins[f"grad_norms_{prec}"]
    state, loss, norms, gnorm = reference.train_step(
        reference.train_state(params), toks, labels, cfg, opt)
    assert [float(loss), _digest(norms), float(gnorm),
            _tree_digest(state[0])] == pins["train_step"]
