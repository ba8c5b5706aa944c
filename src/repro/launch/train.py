"""Training launcher: ``python -m repro.launch.train --arch <id> ...``

Runs a real (CPU-scale by default) training loop with the full production
substrate: sharded data pipeline, AdamW + schedule, gradient accumulation,
async checkpointing, failure injection + elastic restart, straggler
monitoring.  On a TPU slice the same launcher runs the full config on the
production mesh (``--production-mesh``).
"""

from __future__ import annotations

import argparse
import logging
import time
from dataclasses import dataclass

import jax

from repro.checkpoint import CheckpointManager, latest_step, restore_pytree
from repro.configs import ARCH_IDS, get_config
from repro.data import SyntheticTokenDataset, make_global_batch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.api import build_model
from repro.runtime import (FailureInjector, ShardingRules, StragglerMonitor,
                           TrainOptions)
from repro.runtime.steps import build_train_step, make_train_state

log = logging.getLogger("repro.train")


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="use the smoke-sized config (CPU default)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default="cosine",
                    choices=("cosine", "wsd"))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-interval", type=int, default=20)
    ap.add_argument("--failure-rate", type=float, default=0.0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


@dataclass
class TrainSetup:
    """What ``train`` runs for its arguments on one mesh (or one device)."""

    mesh: object
    step_fn: object
    shardings: object
    state: object
    ds: SyntheticTokenDataset

    def batch(self, step: int) -> dict:
        if self.mesh is not None:
            return make_global_batch(self.ds, step, self.mesh)
        return {k: jax.numpy.asarray(v)
                for k, v in self.ds.host_batch(step).items()}


def setup(args: argparse.Namespace, mesh) -> TrainSetup:
    """Model, optimizer schedule, step, initial state and data for ``args``;
    ``mesh=None`` runs on the default device."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # minicpm trains under WSD per its paper
    schedule = "wsd" if args.arch == "minicpm-2b" else args.schedule
    model = build_model(cfg)
    opts = TrainOptions(peak_lr=args.lr, warmup=max(2, args.steps // 20),
                        total_steps=args.steps, schedule=schedule,
                        microbatches=args.microbatches)
    step_fn, shardings = build_train_step(model, mesh, ShardingRules(), opts)
    state = make_train_state(model, jax.random.PRNGKey(0))
    ds = SyntheticTokenDataset(cfg.vocab_size, args.seq, args.batch)
    return TrainSetup(mesh, step_fn, shardings, state, ds)


def train(argv=None):
    args = parse_args(argv)
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif len(jax.devices()) > 1:
        mesh = make_host_mesh()
    else:
        mesh = None
    run = setup(args, mesh)
    step_fn, shardings, state = run.step_fn, run.shardings, run.state
    start = 0
    mgr = CheckpointManager(args.ckpt_dir, interval=args.ckpt_interval)
    if args.resume and (ls := latest_step(args.ckpt_dir)) is not None:
        state = restore_pytree(state, args.ckpt_dir, ls,
                               shardings if mesh is not None else None)
        start = ls + 1
        log.info("resumed from step %d", ls)

    injector = FailureInjector(rate=args.failure_rate, seed=1)
    monitor = StragglerMonitor()

    losses = []
    s = start
    while s < args.steps:
        try:
            injector.check(s)
        except Exception:
            # elastic restart: reload latest checkpoint, continue
            mgr.wait()
            ls = latest_step(args.ckpt_dir)
            if ls is not None:
                state = restore_pytree(state, args.ckpt_dir, ls)
                s = ls + 1
            log.warning("injected failure; restarted at step %d", s)
            continue
        batch = run.batch(s)
        t0 = time.perf_counter()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        monitor.observe(time.perf_counter() - t0)
        losses.append(loss)
        mgr.maybe_save(state, s)
        if s % args.log_every == 0:
            print(f"step {s:5d}  loss {loss:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}")
        s += 1
    mgr.close()
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}); "
          f"stragglers flagged: {monitor.flagged}")
    return losses


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    use_compile_cache()
    train()
