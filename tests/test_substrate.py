"""Substrate tests: optimizer, schedules, compression, data pipeline,
checkpointing, fault tolerance, sharding rules."""

import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_pytree, save_pytree)
from repro.data import SyntheticTokenDataset
from repro.optim import (GradAccumulator, adamw_init, adamw_update,
                         clip_by_global_norm, compress_init,
                         cosine_schedule, topk_compress_update, wsd_schedule)
from repro.runtime import ShardingRules
from repro.runtime.fault import FailureInjector, NodeFailure, StragglerMonitor


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0]), "b": jnp.array(2.0)}
    state = adamw_init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state = adamw_update(g, state, params, lr=0.05,
                                     weight_decay=0.0)
    assert float(loss(params)) < 1e-3


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 20.0) < 1e-4
    total = jnp.sqrt(sum(jnp.sum(x ** 2) for x in jax.tree.leaves(clipped)))
    assert abs(float(total) - 1.0) < 1e-4


def test_schedules_shape():
    s = jnp.arange(0, 1000)
    cos = cosine_schedule(s, peak_lr=1e-3, warmup=100, total=1000)
    wsd = wsd_schedule(s, peak_lr=1e-3, warmup=100, total=1000)
    assert float(cos[0]) == 0.0 and float(cos[100]) == pytest.approx(1e-3)
    # WSD: stable plateau then decay
    assert float(wsd[500]) == pytest.approx(1e-3)
    assert float(wsd[999]) < 2e-4
    assert float(wsd[950]) < float(wsd[890])


def test_grad_accumulation_matches_full_batch():
    def loss(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2), {}

    key = jax.random.PRNGKey(0)
    p = {"w": jax.random.normal(key, (8, 4))}
    batch = {"x": jax.random.normal(key, (16, 8)),
             "y": jax.random.normal(key, (16, 4))}
    (l1, _), g1 = jax.value_and_grad(loss, has_aux=True)(p, batch)
    l2, g2, _ = GradAccumulator(4).grads(loss, p, batch)
    assert float(jnp.abs(l1 - l2)) < 1e-5
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                               rtol=1e-5, atol=1e-6)


def test_grad_accumulation_sums_stats():
    """The loss function's statistics come back summed over the
    microbatches, beside the mean loss and gradients."""
    def loss(p, batch):
        err = batch["x"] @ p["w"] - batch["y"]
        return jnp.mean(err ** 2), {"rows": jnp.float32(err.shape[0])}

    key = jax.random.PRNGKey(0)
    p = {"w": jax.random.normal(key, (8, 4))}
    batch = {"x": jax.random.normal(key, (16, 8)),
             "y": jax.random.normal(key, (16, 4))}
    (l1, _), g1 = jax.value_and_grad(loss, has_aux=True)(p, batch)
    l2, g2, stats = GradAccumulator(4).grads(loss, p, batch)
    assert float(jnp.abs(l1 - l2)) < 1e-5
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                               rtol=1e-5, atol=1e-6)
    assert float(stats["rows"]) == 16.0


def test_topk_compression_error_feedback():
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64,)),
                          jnp.float32)}
    st = compress_init(g)
    sparse, st = topk_compress_update(g, st, ratio=0.1)
    nz = int(jnp.sum(sparse["w"] != 0))
    assert nz <= 8 + 1
    # lossless bookkeeping: sparse + residual == original
    np.testing.assert_allclose(
        np.asarray(sparse["w"] + st.residual["w"]), np.asarray(g["w"]),
        rtol=1e-6, atol=1e-6)
    # second step re-injects the residual
    sparse2, st2 = topk_compress_update(
        {"w": jnp.zeros_like(g["w"])}, st, ratio=0.1)
    assert float(jnp.abs(sparse2["w"]).sum()) > 0


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_sharded():
    ds = SyntheticTokenDataset(vocab_size=1000, seq_len=16, global_batch=8,
                               seed=3)
    a = ds.host_batch(5)
    b = ds.host_batch(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # labels are next-token shifted
    full = ds.batch_slice(5, 0, 8)
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    # per-rank slices agree with the full batch
    lo = ds.batch_slice(5, 2, 4)
    np.testing.assert_array_equal(lo["tokens"], a["tokens"][2:4])
    assert (a["tokens"] < 1000).all() and (a["tokens"] >= 0).all()


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path / "ck")
    tree = {"a": jnp.arange(6).reshape(2, 3), "b": {"c": jnp.ones(4)}}
    save_pytree(tree, d, 7)
    assert latest_step(d) == 7
    out = restore_pytree(tree, d, 7)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))


def test_checkpoint_manager_async_and_gc(tmp_path):
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d, interval=2, max_keep=2)
    tree = {"w": jnp.zeros(3)}
    for s in range(10):
        mgr.maybe_save({"w": tree["w"] + s}, s)
    mgr.close()
    steps = sorted(int(f[5:13]) for f in os.listdir(d)
                   if f.startswith("step_"))
    assert len(steps) == 2 and steps[-1] == 8
    out = restore_pytree(tree, d, 8)
    assert float(out["w"][0]) == 8.0


def test_checkpoint_atomicity(tmp_path):
    """tmp files never count as a restorable checkpoint."""
    d = str(tmp_path / "ck")
    os.makedirs(d)
    with open(os.path.join(d, "tmp.3.npz"), "w") as f:
        f.write("partial")
    assert latest_step(d) is None


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------

def test_failure_injector_deterministic():
    inj1 = FailureInjector(rate=0.3, seed=9)
    inj2 = FailureInjector(rate=0.3, seed=9)
    fails1, fails2 = [], []
    for s in range(50):
        for inj, out in ((inj1, fails1), (inj2, fails2)):
            try:
                inj.check(s)
            except NodeFailure:
                out.append(s)
    assert fails1 == fails2 and len(fails1) > 5


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(window=16, threshold=2.0)
    for _ in range(16):
        assert not mon.observe(0.1)
    assert mon.observe(0.5)
    assert mon.flagged == 1


def test_elastic_mesh_rebuild():
    from repro.runtime.fault import ElasticMesh
    em = ElasticMesh(model_axis=1)
    mesh = em.make()
    assert mesh.shape["model"] == 1
    assert em.usable(5) == (5, 1)
    with pytest.raises(RuntimeError):
        em.usable(0)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

def test_sharding_rules_divisibility():
    import numpy as _np
    from jax.sharding import Mesh
    devs = _np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    rules = ShardingRules()
    # 14 heads on a 1-way model axis: fine; on >1 it must drop
    spec = rules.spec_for(("embed", "heads"), (896, 14 * 64), mesh)
    assert spec is not None


def test_sharding_rules_override():
    rules = ShardingRules().override(seq="model", ffn=None)
    assert rules.table["seq"] == ("model",)
    assert rules.table["ffn"] == ()
