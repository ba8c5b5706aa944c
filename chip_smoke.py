"""Smoke run of the system's main path on a TPU, at qwen2-0.5b's published
full width (24 layers, d_model 896, 14 heads / 2 KV heads, vocab 151936),
with random weights from fixed seeds.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the data-parallel path of a 4-chip host

One chip, one process, four phases:
  kernels   the Pallas flash and decode attention kernels at qwen2 widths
            against the float32 references
  executor  DeepDriveMD with real payloads (decode rollouts, aggregation,
            train steps, prefill scoring) through ``RealExecutor``, in
            sequential and in async mode; the compiled prefill and decode
            steps must contain the Pallas kernels (``tpu_custom_call``)
  train     ``repro.launch.train.train(["--full", "--steps", "3"])``
  serve     ``repro.launch.serve.serve(["--full", ...])``

``--four-chips`` runs only this: three train steps on the four-chip mesh
through ``train()``, the same three steps on one chip with the same global
batch, their largest loss difference, and one serve pass on the mesh.

There is no fallback: without a TPU, or when any phase raises or any check
fails, the script exits nonzero and prints no result.  On success the last
line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
#: max |Pallas - reference| in float32; XLA and Mosaic may run float32
#: matmuls as bf16 passes on the MXU, the reference runs at "highest"
KERNEL_TOL = 2e-2
#: one chip vs the four-chip mesh after three steps: dispatch and
#: reduction order differ, nothing else
LOSS_TOL = 5e-2
#: train-launcher steps and global batch (tokens 8 x 128 per step)
STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 128
#: serve-launcher requests and generated tokens per request
REQUESTS, GEN_LEN = 8, 16
#: executor-phase payload shapes at full width
FULL_SHAPES = dict(train_batch=TRAIN_BATCH, train_seq=TRAIN_SEQ,
                   decode_batch=8, cache_len=256, decode_steps=8,
                   prefill_batch=8, prefill_seq=128)


def _gib(n: int) -> str:
    return f"{n / 2**30:.3f} GiB"


def _config(full: bool):
    from repro.configs import get_config
    cfg = get_config(ARCH)
    return cfg if full else cfg.reduced()


def _launcher_args(full: bool) -> list[str]:
    return ["--arch", ARCH] + (["--full"] if full else [])


def kernel_phase() -> None:
    """Pallas flash (prefill) and decode attention vs float32 references."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import ref as da_ref
    from repro.kernels.decode_attention.kernel import decode_attention_pallas
    from repro.kernels.flash_attention import ref as fa_ref
    from repro.kernels.flash_attention.kernel import flash_attention_pallas

    cfg = _config(True)
    h, kvh, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    f32 = jnp.float32
    q = jax.random.normal(ks[0], (2, 1024, h, d), f32)
    k = jax.random.normal(ks[1], (2, 1024, kvh, d), f32)
    v = jax.random.normal(ks[2], (2, 1024, kvh, d), f32)
    qd = jax.random.normal(ks[3], (8, h, d), f32)
    ck = jax.random.normal(ks[4], (8, 2048, kvh, d), f32)
    cv = jax.random.normal(ks[5], (8, 2048, kvh, d), f32)
    valid = jax.random.randint(ks[6], (8,), 1, 2049, jnp.int32)

    cases = {
        "flash": (flash_attention_pallas.lower(q, k, v, causal=True),
                  (q, k, v),
                  lambda: fa_ref.mha_reference(q, k, v, causal=True)),
        "decode": (decode_attention_pallas.lower(qd, ck, cv, valid),
                   (qd, ck, cv, valid),
                   lambda: da_ref.decode_reference(qd, ck, cv, valid)),
    }
    for name, (lowered, args, reference) in cases.items():
        compiled = lowered.compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no Pallas kernel in the program")
        got = compiled(*args)
        with jax.default_matmul_precision("highest"):
            want = reference()
        err = float(jnp.abs(got - want).max())
        print(f"kernel {name:6s} max|pallas - ref| = {err:.3e} "
              f"(tol {KERNEL_TOL:.0e})")
        if not err <= KERNEL_TOL:
            raise AssertionError(f"{name}: error {err} > {KERNEL_TOL}")


def _tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def memory_report(p, limit: int | None) -> None:
    """Each compiled payload step with the rest of the resident train
    state: arguments + outputs + temporaries - donated aliases."""
    state = _tree_bytes(p.state)
    reads = dict(train=state, prefill=_tree_bytes(p.state.params))
    reads["decode"] = reads["prefill"]
    print(f"resident train state {_gib(state)}")
    for kind, c in p.compiled.items():
        m = c.memory_analysis()
        total = (state + m.argument_size_in_bytes - reads[kind]
                 + m.output_size_in_bytes + m.temp_size_in_bytes
                 - m.alias_size_in_bytes)
        print(f"  {kind:8s} args {_gib(m.argument_size_in_bytes)}  "
              f"out {_gib(m.output_size_in_bytes)}  "
              f"temp {_gib(m.temp_size_in_bytes)}  "
              f"aliased {_gib(m.alias_size_in_bytes)}  "
              f"-> with the state {_gib(total)}")
        if limit is not None and total >= limit:
            raise AssertionError(f"{kind}: {total} bytes >= HBM {limit}")


def executor_phase(full: bool) -> None:
    """DeepDriveMD with real payloads, sequential then async.  At full
    width (on the chip) the compiled prefill and decode steps must hold
    the Pallas kernels, and each step must fit the chip's memory."""
    import jax
    from repro.core import relative_improvement
    from repro.launch import ddmd
    from repro.models.api import build_model

    shapes = ddmd.PayloadShapes(**FULL_SHAPES) if full else \
        ddmd.PayloadShapes()
    p = ddmd.DDMDPayloads(build_model(_config(full)), shapes)
    limit = None
    if full:
        for kind in ("prefill", "decode"):
            if "tpu_custom_call" not in p.compiled[kind].as_text():
                raise AssertionError(f"compiled {kind} step runs no Pallas "
                                     "kernel")
        print("compiled prefill and decode steps contain tpu_custom_call")
        limit = jax.devices()[0].memory_stats()["bytes_limit"]
    memory_report(p, limit)
    for fn in p.as_dict().values():   # the remaining small jits
        fn(0)

    walls = {}
    for mode in ("sequential", "async"):
        t0 = time.perf_counter()
        res, n_tasks = ddmd.run(p, mode)
        walls[mode] = time.perf_counter() - t0
        if res.tasks_total != n_tasks:
            raise AssertionError(f"{mode}: {res.tasks_total} of {n_tasks} "
                                 "tasks ran")
        print(f"{mode:10s} {res.tasks_total} tasks in {walls[mode]:.3f} s")
    losses = [float(x) for x in p.losses]
    if not (losses and all(math.isfinite(x) for x in losses)):
        raise AssertionError(f"train losses {losses}")
    if not (p.logits_finite and all(bool(x) for x in p.logits_finite)):
        raise AssertionError("non-finite logits in a payload")
    print(f"train losses {losses[0]:.4f} .. {losses[-1]:.4f} "
          f"({len(losses)} steps); {len(p.logits_finite)} logits checks "
          "finite")
    i = relative_improvement(walls["sequential"], walls["async"])
    print(f"I = {i:.3f} (smoke, not a measurement)")


def _train_argv(full: bool) -> list[str]:
    return _launcher_args(full) + [
        "--steps", str(STEPS), "--batch", str(TRAIN_BATCH),
        "--seq", str(TRAIN_SEQ), "--log-every", "1"]


def train_phase(full: bool) -> list[float]:
    from repro.launch.train import train

    with tempfile.TemporaryDirectory() as ckpt:
        losses = train(_train_argv(full) + ["--ckpt-dir", ckpt])
    if len(losses) != STEPS or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"train losses {losses}")
    return losses


def serve_phase(full: bool) -> None:
    from repro.launch.serve import serve

    gen = serve(_launcher_args(full) + [
        "--requests", str(REQUESTS), "--gen-len", str(GEN_LEN)])
    vocab = _config(full).vocab_size
    if gen.shape != (REQUESTS, GEN_LEN + 1) or not (
            (gen >= 0) & (gen < vocab)).all():
        raise AssertionError(f"generated ids {gen.shape}")


def one_chip_losses(full: bool) -> list[float]:
    """``train_phase``'s run without a mesh, on the first device, set up
    by the launcher's own ``setup`` from the same arguments."""
    from repro.launch.train import parse_args, setup

    args = parse_args(_train_argv(full))
    run = setup(args, None)
    state, losses = run.state, []
    for s in range(args.steps):
        state, metrics = run.step_fn(state, run.batch(s))
        losses.append(float(metrics["loss"]))
    return losses


def four_chip_phase(full: bool) -> None:
    mesh = train_phase(full)
    gc.collect()
    one = one_chip_losses(full)
    diff = max(abs(a - b) for a, b in zip(mesh, one))
    print(f"losses mesh {mesh}\nlosses one chip {one}\n"
          f"max |loss difference| = {diff:.3e} (tol {LOSS_TOL:.0e})")
    if not diff <= LOSS_TOL:
        raise AssertionError(f"mesh vs one chip: {diff} > {LOSS_TOL}")
    gc.collect()
    serve_phase(full)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the data-parallel train/serve path on a "
                         "four-chip host, against one chip")
    args = ap.parse_args(argv)

    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX backend {jax.default_backend()!r})"
                 "; this script does not fall back")
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache {use_compile_cache()}")

    devs = jax.devices()
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs))
    print(f"device {device}")

    if args.four_chips:
        if device["count"] != 4:
            sys.exit(f"chip_smoke: --four-chips needs 4 chips, found "
                     f"{device['count']}")
        phases = {"four-chip train + serve": lambda: four_chip_phase(True)}
    else:
        phases = {
            "kernels": kernel_phase,
            "executor": lambda: executor_phase(True),
            "train": lambda: train_phase(True),
            "serve": lambda: serve_phase(True),
        }
    for name, fn in phases.items():
        t0 = time.perf_counter()
        fn()
        gc.collect()
        stats = devs[0].memory_stats()
        print(f"phase {name}: {time.perf_counter() - t0:.1f} s wall "
              f"(compile included); peak_bytes_in_use "
              f"{stats['peak_bytes_in_use']} of {stats['bytes_limit']}",
              flush=True)
    stats = devs[0].memory_stats()
    if stats["peak_bytes_in_use"] >= stats["bytes_limit"]:
        raise AssertionError(f"peak device memory {stats}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
