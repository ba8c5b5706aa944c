"""Record the small trace that ``test_trace.py`` reads for named scopes and
program spans, on one TPU:

    python bench/tests/record_scope_trace.py [--out <dir>]

A jitted training step of a one-matrix model, whose forward runs under
``jax.named_scope("demo")``, runs three times inside the harness's
``bench:window`` annotation.  Around the three runs are one program span
(``demo:step``, ``repro.core.tracing.span``) and, inside it, one of the
harness's own (``payload:demo``).  On the trace's clock the
device's events lead the host's by most of a millisecond, so of the three
runs the window holds the last.  Writes ``scope.xplane.pb`` and the
step's compiled HLO text, ``scope.hlo.txt``, to ``--out`` (by default
``data/``, where the test reads them).  Without a TPU it exits 2.
"""

from __future__ import annotations

import argparse
import glob
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
RUNS = 3


def loss(w, x):
    with jax.named_scope("demo"):
        y = jnp.tanh(x @ w)
    return jnp.mean(y * y)


def demo_step(w, x):
    value, grad = jax.value_and_grad(loss)(w, x)
    return w - 0.1 * grad, value


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=Path, default=DATA)
    out = ap.parse_args().out
    out.mkdir(parents=True, exist_ok=True)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.trace import WINDOW
    from repro.core.tracing import span

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        sys.exit(2)
    w = jax.random.normal(jax.random.PRNGKey(0), (512, 512), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 512), jnp.float32)
    step = jax.jit(demo_step).lower(w, x).compile()
    jax.block_until_ready(step(w, x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation(WINDOW):
            with span("demo:step", runs=RUNS):
                with jax.profiler.TraceAnnotation("payload:demo"):
                    for _ in range(RUNS):
                        w, _ = step(w, x)
                    jax.block_until_ready(w)
        jax.profiler.stop_trace()
        pb = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
        shutil.copy(pb, out / "scope.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    (out / "scope.hlo.txt").write_text(step.as_text())
    print({p.name: p.stat().st_size for p in out.glob("scope.*")})
