"""DeepDriveMD with REAL heterogeneous JAX payloads, executed
asynchronously by the middleware (the paper's §6.1 experiment, with jitted
model steps instead of `stress`).

Task types (all real JAX work on a reduced qwen2; see repro/launch/ddmd.py):
  Simulation   autoregressive decode rollout (MD-like trajectory producer)
  Aggregation  reduction over produced trajectories (CPU-ish)
  Training     train_step()s on the aggregated tokens
  Inference    batched prefill scoring candidate sequences

The RealExecutor enforces the same (cpus, gpus) accounting as the paper's
middleware; sequential mode barriers each stage, async mode staggers the
three iterations — compare the measured makespans and the task throughput.

Run:  PYTHONPATH=src python examples/deepdrivemd_async.py
"""

import time

from repro.configs import get_config
from repro.core import RealExecutor, RunConfig, relative_improvement
from repro.core.resources import Allocation, NodeSpec, PoolSpec
from repro.launch import ddmd
from repro.models.api import build_model


def main():
    payloads = ddmd.DDMDPayloads(build_model(
        get_config("qwen2-0.5b").reduced()))
    # warm the remaining jit caches so the comparison measures orchestration
    for fn in payloads.as_dict().values():
        fn(0)

    t0 = time.perf_counter()
    seq, _ = ddmd.run(payloads, "sequential")
    t_seq = time.perf_counter() - t0

    t0 = time.perf_counter()
    asy, _ = ddmd.run(payloads, "async")
    t_async = time.perf_counter() - t0

    i = relative_improvement(t_seq, t_async)
    print(f"sequential: {t_seq:6.2f}s   ({seq.tasks_total} tasks, "
          f"{seq.throughput():.1f} tasks/s)")
    print(f"async:      {t_async:6.2f}s   ({asy.tasks_total} tasks, "
          f"{asy.throughput():.1f} tasks/s)")
    print(f"I = {i:.3f}  (real JAX payloads, real thread-level concurrency)")

    # heterogeneous allocation: an accelerator partition + a CPU partition;
    # gpu_bestfit packs the CPU-only Aggregation tasks onto the CPU nodes.
    hetero = Allocation("laptop-hybrid", (
        PoolSpec("accel", num_nodes=1, node=NodeSpec(cpus=4, gpus=4)),
        PoolSpec("cpu", num_nodes=1, node=NodeSpec(cpus=8, gpus=0)),
    ))
    het = RealExecutor(hetero, launch_latency=0.002).run(
        ddmd.dag(payloads), "async",
        config=RunConfig(scheduling="gpu_bestfit"))
    print(f"hybrid pools (gpu_bestfit): {het.per_pool_task_counts()}")
    return i


if __name__ == "__main__":
    main()
