"""Serving launcher: batched prefill + decode against a KV cache.

``python -m repro.launch.serve --arch qwen2-0.5b --requests 8``

Implements the production serving loop in miniature: a request queue is
batched, prefilled (one sharded forward over the prompt), then decoded
step-by-step with a persistent sharded cache.  On TPU the same loop runs
the full config on the production mesh.

At fleet scale these serving jobs are the arrival process of the
streaming-tenancy scheduler: `core/stream.py` models an open stream of
them (`examples/stream_tenancy.py`, `benchmarks/bench_streaming.py`)
with per-arrival SLOs, deadline-aware admission, and elastic capacity.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.api import build_model
from repro.models.params import init_params
from repro.runtime import ShardingRules
from repro.runtime.steps import build_decode_step, build_prefill_step


def serve(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    elif len(jax.devices()) > 1:
        mesh = make_host_mesh()
    else:
        mesh = None
    rules = ShardingRules()

    params = init_params(model.specs(), jax.random.PRNGKey(0))
    b = args.requests

    # ---- prefill ----------------------------------------------------------
    prefill, _ = build_prefill_step(model, mesh, rules)
    batch = model.make_batch(jax.random.PRNGKey(1), batch=b,
                             seq=args.prompt_len, mode="prefill")
    batch.pop("labels", None)
    t0 = time.perf_counter()
    last_logits = prefill(params, batch)
    # on the host: the decode step places the first tokens as it places
    # its own, whatever layout the prefill's logits came back in
    next_tok = np.asarray(jnp.argmax(last_logits, axis=-1).astype(jnp.int32))
    t_prefill = time.perf_counter() - t0
    print(f"prefill: {b} x {args.prompt_len} tokens in {t_prefill:.3f}s")

    # ---- decode -----------------------------------------------------------
    decode, _ = build_decode_step(model, mesh, rules, batch=b,
                                  s_max=args.cache_len)
    cache = init_params(model.cache_specs(b, args.cache_len),
                        jax.random.PRNGKey(2))
    pos = jnp.full((b,), args.prompt_len, jnp.int32)
    toks = [np.asarray(next_tok)]
    t0 = time.perf_counter()
    for i in range(args.gen_len):
        out = decode(params, cache, next_tok[:, None], pos + i)
        if len(out) == 3:
            next_tok, _, cache = out
        else:
            next_tok, cache = out
        toks.append(np.asarray(next_tok))
    dt = time.perf_counter() - t0
    gen = np.stack(toks, axis=1)
    print(f"decode: {args.gen_len} steps x {b} requests in {dt:.3f}s "
          f"({b * args.gen_len / dt:.1f} tok/s)")
    print("generated ids (first request):", gen[0][:12], "...")
    return gen


if __name__ == "__main__":
    use_compile_cache()
    serve()
