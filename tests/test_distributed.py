"""Distributed-execution equivalence on forced multi-device CPU.

These tests spawn SUBPROCESSES with ``--xla_force_host_platform_device_
count=8`` (jax fixes the device count at first init, so the main pytest
process stays single-device) and assert that the sharded mesh execution
matches the single-device reference numerically — params FSDP/TP-sharded,
batch data-parallel, MoE expert-parallel with all_to_all."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str) -> dict:
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    out = subprocess.run(
        [sys.executable, "-c", prog],
        env={**os.environ, "PYTHONPATH": SRC, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    res = run_sub("""
        from repro.configs import get_config
        from repro.models.api import build_model
        from repro.runtime import ShardingRules, TrainOptions
        from repro.runtime.steps import build_train_step, make_train_state
        from jax.sharding import Mesh
        import numpy as _np

        cfg = get_config("qwen2-0.5b").reduced()
        model = build_model(cfg)
        batch = model.make_batch(jax.random.PRNGKey(1), batch=8, seq=32)
        opts = TrainOptions(total_steps=10, remat=False)

        # single device
        step1, _ = build_train_step(model, None, None, opts)
        s1 = make_train_state(model, jax.random.PRNGKey(0))
        s1, m1 = step1(s1, batch)

        # 4x2 mesh (data x model)
        mesh = Mesh(_np.asarray(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        step2, sh = build_train_step(model, mesh, ShardingRules(), opts)
        s2 = make_train_state(model, jax.random.PRNGKey(0))
        s2, m2 = step2(s2, batch)

        d = max(float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).max())
                for a, b in zip(jax.tree.leaves(s1.params),
                                jax.tree.leaves(s2.params)))
        print(json.dumps({
            "loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
            "max_param_diff": d}))
    """)
    assert abs(res["loss1"] - res["loss2"]) < 5e-2, res
    assert res["max_param_diff"] < 5e-2, res


@pytest.mark.slow
def test_moe_ep_matches_local():
    """EP all_to_all / psum vs the local reference.  Root cause of the
    historical xfail: the subprocess died before computing anything, on a
    jax without the top-level `jax.shard_map` — not numerics.  The drift is
    well inside 2e-3 (float32 dispatch order only)."""
    res = run_sub("""
        import dataclasses
        from repro.configs import get_config
        from repro.models.moe import MoEOptions, moe_ep_a2a, moe_ep_psum, \\
            moe_local, moe_specs
        from repro.models.params import init_params
        from repro.runtime import ShardingRules, use_sharding
        from jax.sharding import Mesh
        import numpy as _np

        cfg = get_config("qwen3-moe-30b-a3b").reduced()
        specs = moe_specs(cfg, 1)
        p = jax.tree.map(lambda a: a[0],
                         init_params(specs, jax.random.PRNGKey(0)))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              jnp.float32)
        opts = MoEOptions(capacity_factor=16.0)
        y_ref, aux_ref, n_ref = moe_local(p, x, cfg, opts)

        mesh = Mesh(_np.asarray(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        with use_sharding(mesh, ShardingRules()):
            y_a2a, aux_a2a, n_a2a = jax.jit(
                lambda p, x: moe_ep_a2a(p, x, cfg, opts))(p, x)
            y_psum, aux_psum, n_psum = jax.jit(
                lambda p, x: moe_ep_psum(p, x, cfg, opts))(p, x)
        print(json.dumps({
            "d_a2a": float(jnp.abs(y_a2a - y_ref).max()),
            "d_psum": float(jnp.abs(y_psum - y_ref).max()),
            "aux_ref": float(aux_ref), "aux_a2a": float(aux_a2a),
            "counts": [_np.asarray(n).tolist()
                       for n in (n_ref, n_a2a, n_psum)]}))
    """)
    assert res["d_a2a"] < 2e-3, res
    assert res["d_psum"] < 2e-3, res
    # each mesh path counts its own routing's assignments, summed over the
    # devices its tokens are split over: the local path's counts
    n_ref, n_a2a, n_psum = res["counts"]
    assert n_a2a == n_ref and n_psum == n_ref, res


@pytest.mark.slow
def test_flash_decoding_shard_map_combine():
    """Explicit sequence-sharded decode: shard_map partial softmax + psum
    log-sum-exp combine equals the dense reference.  The float32 log-sum-exp
    combine is stable to <1e-4."""
    res = run_sub("""
        from repro.kernels.decode_attention import ops as da
        from repro.kernels.decode_attention import ref as dref
        from jax.sharding import Mesh, PartitionSpec as P
        import numpy as _np

        b, smax, h, kvh, d = 2, 64, 4, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
        ck = jax.random.normal(ks[1], (b, smax, kvh, d), jnp.float32)
        cv = jax.random.normal(ks[2], (b, smax, kvh, d), jnp.float32)
        valid = jnp.asarray([40, 64])
        want = dref.decode_reference(q, ck, cv, valid)

        mesh = Mesh(_np.asarray(jax.devices()[:8]).reshape(8,), ("model",))
        pos = jnp.arange(smax)

        def shard_fn(q, ck, cv, valid, pos):
            mask = pos[None, :] < valid[:, None]
            acc, m, l = da.partial_decode(q, ck, cv, mask)
            return da.combine_partials(acc, m, l, "model")

        out = jax.jit(jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), P(None, "model"), P(None, "model"), P(),
                      P("model")),
            out_specs=P(), check_vma=False))(q, ck, cv, valid, pos)
        print(json.dumps(
            {"diff": float(jnp.abs(out.reshape(b, h, d) - want).max())}))
    """)
    assert res["diff"] < 1e-4, res


@pytest.mark.slow
def test_pallas_attention_on_a_mesh():
    """On a mesh the Pallas attention kernels run per device under
    shard_map (GSPMD cannot partition a Mosaic call).  The TPU dispatch is
    forced here, with the kernels interpreted, and the sharded train loss
    and decode logits must match the single-device jnp path."""
    res = run_sub("""
        from repro.configs import get_config
        from repro.kernels.decode_attention import ops as da
        from repro.kernels.flash_attention import ops as fa
        from repro.models.api import build_model
        from repro.models.params import init_params
        from repro.runtime import ShardingRules, TrainOptions
        from repro.runtime.steps import (build_decode_step,
                                         build_train_step, make_train_state)
        from jax.sharding import Mesh
        import numpy as _np

        cfg = get_config("qwen2-0.5b").reduced()
        model = build_model(cfg)
        batch = model.make_batch(jax.random.PRNGKey(1), batch=8, seq=32)
        opts = TrainOptions(total_steps=10, remat=False)
        params = init_params(model.specs(), jax.random.PRNGKey(0))
        ks = jax.random.split(jax.random.PRNGKey(2), 2)
        cache = {n: jax.random.normal(k, s.shape).astype(s.dtype)
                 for (n, s), k in zip(model.cache_specs(8, 64).items(), ks)}
        tok = jnp.arange(8, dtype=jnp.int32)[:, None]
        pos = jnp.arange(8, dtype=jnp.int32) * 5

        def run(mesh, rules):
            step, _ = build_train_step(model, mesh, rules, opts)
            state = make_train_state(model, jax.random.PRNGKey(0))
            jaxpr = str(jax.make_jaxpr(step)(state, batch))
            _, m = step(state, batch)
            dec, _ = build_decode_step(model, mesh, rules, batch=8, s_max=64)
            _, logits, _ = dec(params, jax.tree.map(jnp.copy, cache), tok,
                               pos)
            return float(m["loss"]), _np.asarray(logits), jaxpr

        loss1, logits1, _ = run(None, None)
        fa.default_impl = da.default_impl = lambda: "pallas"
        mesh = Mesh(_np.asarray(jax.devices()).reshape(4, 2),
                    ("data", "model"))
        loss2, logits2, jaxpr = run(mesh, ShardingRules())
        print(json.dumps({
            "loss1": loss1, "loss2": loss2,
            "logits_diff": float(_np.abs(logits1 - logits2).max()),
            "kernel_under_shard_map": "shard_map" in jaxpr
                                      and "pallas_call" in jaxpr}))
    """)
    assert res["kernel_under_shard_map"], res
    assert abs(res["loss1"] - res["loss2"]) < 5e-2, res
    assert res["logits_diff"] < 5e-2, res
