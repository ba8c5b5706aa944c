"""Step builders: jit-compiled, mesh-sharded train / prefill / decode steps.

``build_train_step`` returns (step_fn, state_shardings, input_shardings)
where step_fn: (TrainState, batch) -> (TrainState, metrics); the metrics
are the loss, the gradient's norm, the learning rate and the model's step
statistics (an expert model's ``expert_tokens``).  All sharding
comes from the logical-axis rules (runtime/sharding.py); the same builder
serves the real trainer, the smoke tests (mesh=None) and the dry-run
(ShapeDtypeStructs via .lower()).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.api import Model
from repro.models.params import (abstract_params, init_params,
                                 param_shardings)
from repro.optim import (AdamWState, GradAccumulator, adamw_init,
                         adamw_update, clip_by_global_norm, make_schedule)
from .sharding import ShardingRules, use_sharding


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt: AdamWState


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10_000
    schedule: str = "cosine"
    max_grad_norm: float = 1.0
    weight_decay: float = 0.1
    microbatches: int = 1
    remat: bool = True


def _remat_loss(model: Model):
    """Activation-checkpoint the loss at layer-scan granularity: the scan
    body is the natural remat unit, so `jax.checkpoint` with a
    dots-saveable policy keeps matmul outputs and recomputes the rest."""
    policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint(model.loss_and_stats, policy=policy)


def make_train_state(model: Model, key=None):
    specs = model.specs()
    params = init_params(specs, key if key is not None
                         else jax.random.PRNGKey(0))
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt=adamw_init(params))


def abstract_train_state(model: Model) -> TrainState:
    specs = model.specs()
    p = abstract_params(specs)
    f32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), p)
    return TrainState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        params=p,
        opt=AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32),
                       mu=f32, nu=jax.tree.map(lambda x: x, f32)))


def state_shardings(model: Model, mesh, rules: ShardingRules) -> TrainState:
    ps = param_shardings(model.specs(), mesh, rules)
    rep = NamedSharding(mesh, P())
    return TrainState(
        step=rep, params=ps,
        opt=AdamWState(step=rep, mu=jax.tree.map(lambda s: s, ps),
                       nu=jax.tree.map(lambda s: s, ps)))


def _batch_axes(rules: ShardingRules, mesh, batch_size: int):
    """Largest prefix of the configured batch axes that divides the batch
    (long_500k has global_batch=1 -> fully replicated)."""
    axes: list[str] = []
    size = 1
    for a in rules.mesh_axes_for("batch", mesh):
        if batch_size % (size * mesh.shape[a]) == 0:
            axes.append(a)
            size *= mesh.shape[a]
    return tuple(axes)


def _batch_entry(axes):
    return axes if len(axes) > 1 else (axes[0] if axes else None)


def batch_shardings(model: Model, batch_specs, mesh, rules: ShardingRules):
    """Data inputs: leading batch dim over ('pod','data') when divisible;
    special cases (positions [3,B,S]) spelled out by name."""
    def sh(name, s):
        bdim = s.shape[1] if name == "positions" else s.shape[0]
        spec_b = _batch_entry(_batch_axes(rules, mesh, bdim))
        if name == "positions":
            return NamedSharding(mesh, P(None, spec_b))
        return NamedSharding(mesh, P(spec_b))

    return {k: sh(k, v) for k, v in batch_specs.items()}


def build_train_step(model: Model, mesh=None,
                     rules: ShardingRules | None = None,
                     opts: TrainOptions = TrainOptions(),
                     flags: dict | None = None):
    """Returns (train_step, shardings) — train_step is NOT yet jitted with
    shardings when mesh is None (smoke path uses plain jit)."""
    rules = rules or ShardingRules()
    sched = make_schedule(
        opts.schedule, peak_lr=opts.peak_lr, warmup=opts.warmup,
        total=opts.total_steps)
    accum = GradAccumulator(opts.microbatches)
    loss_fn = _remat_loss(model) if opts.remat else model.loss_and_stats

    def train_step(state: TrainState, batch):
        with use_sharding(mesh, rules, flags):
            loss, grads, stats = accum.grads(loss_fn, state.params, batch)
            grads, gnorm = clip_by_global_norm(grads, opts.max_grad_norm)
            lr = sched(state.step)
            params, opt = adamw_update(
                grads, state.opt, state.params, lr=lr,
                weight_decay=opts.weight_decay)
        new = TrainState(step=state.step + 1, params=params, opt=opt)
        return new, {"loss": loss, "grad_norm": gnorm, "lr": lr, **stats}

    if mesh is None:
        # the state is donated, as on the mesh: the step updates it in
        # place instead of holding a second copy of params and moments
        return jax.jit(train_step, donate_argnums=(0,)), None

    shardings = state_shardings(model, mesh, rules)
    rep = NamedSharding(mesh, P())
    jitted = jax.jit(
        train_step,
        in_shardings=(shardings, None),      # batch sharding via data layer
        out_shardings=(shardings, rep),
        donate_argnums=(0,),
    )
    return jitted, shardings


def build_prefill_step(model: Model, mesh=None,
                       rules: ShardingRules | None = None,
                       flags: dict | None = None):
    rules = rules or ShardingRules()

    def prefill(params, batch):
        with use_sharding(mesh, rules, flags):
            # the hidden state is sliced to the final position BEFORE the
            # unembedding matmul: one next-token distribution per request,
            # not a [B, S, V] logits tensor
            logits, _ = model.forward(params, batch, last_only=True)
            return logits[:, -1].astype(jnp.float32)

    if mesh is None:
        return jax.jit(prefill), None
    ps = param_shardings(model.specs(), mesh, rules)
    out_sh = NamedSharding(
        mesh, rules.spec_for(("batch", "act_vocab"), (1, 1), mesh))
    return jax.jit(prefill, in_shardings=(ps, None),
                   out_shardings=out_sh), ps


def cache_shardings(model: Model, batch: int, s_max: int, mesh,
                    rules: ShardingRules):
    return param_shardings(model.cache_specs(batch, s_max), mesh, rules)


def build_decode_step(model: Model, mesh=None,
                      rules: ShardingRules | None = None, *,
                      batch: int, s_max: int, flags: dict | None = None):
    """One new token against a KV cache of ``s_max``.  Returns
    (decode_step, (param_shardings, cache_shardings))."""
    rules = rules or ShardingRules()

    def decode(params, cache, tokens, pos):
        with use_sharding(mesh, rules, flags):
            logits, cache = model.decode_step(params, cache, tokens, pos)
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return next_tok, logits.astype(jnp.float32), cache

    if mesh is None:
        return jax.jit(decode, donate_argnums=(1,)), None
    ps = param_shardings(model.specs(), mesh, rules)
    cs = cache_shardings(model, batch, s_max, mesh, rules)
    spec_b = _batch_entry(_batch_axes(rules, mesh, batch))
    tok_sh = NamedSharding(mesh, P(spec_b))
    jitted = jax.jit(
        decode,
        in_shardings=(ps, cs, tok_sh, tok_sh),
        out_shardings=(tok_sh, NamedSharding(mesh, P(spec_b)), cs),
        donate_argnums=(1,),
    )
    return jitted, (ps, cs)
