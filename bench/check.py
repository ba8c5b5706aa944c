"""What decides ``correct``: the timed path's outputs against the plain
reference, and the executor's schedule against the DAG.

Every number is a worst case over what the run checked; each has its
limit in ``bench/limits/<cell>.json``:

- ``exec_faults``: tasks not run exactly once, tasks started before
  every task of a parent set ended, and calls whose inputs differ from
  what the traffic draws (limit 0);
- ``train_loss_gap``: |program loss - reference loss| / reference loss,
  per train step;
- ``grad_global_norm_gap``: |program - reference| / reference for the
  global norm of the gradient before clipping, which the program's train
  step reports, per train step;
- ``grad_norm_gap``: per parameter leaf, the gap between the norms of the
  first clipped gradient as the program's optimizer holds it (its first
  moment after one step, over 1 - b1) and as the reference computes it,
  over the larger of that leaf's and the median leaf's reference norm;
- ``update_norm_gap``: the same for the parameters' change over the
  warm-up instance's steps, on the leaves whose reference gradient is
  not nought to rounding (at least a thousandth of the median leaf's);
- ``prefill_logit_err`` and ``decode_logit_err``: max |program logit -
  reference logit| over the reference logits' standard deviation, per
  call (prefill: each row's last position; decode: every
  ``logits_every``-th step of a sampled rollout);
- ``decode_token_gap``: how far a served token's reference logit lies
  below the reference's best at that position, in the same unit.

A version of the parameters is what a call read: version k is the state
after the instance's first k train steps.  The reference replays those
steps in the order the program ran them, from the seed's weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights

#: every number the check can compare, in the order it prints them
NAMES = ("exec_faults", "train_loss_gap", "grad_global_norm_gap",
         "grad_norm_gap", "update_norm_gap", "prefill_logit_err",
         "decode_logit_err", "decode_token_gap")


def schedule_faults(g, spans, inst: int) -> int:
    """Tasks of instance ``inst`` not run exactly once, plus tasks that
    started before every task of each parent set had ended."""
    runs: dict = {}
    for s in spans:
        if s.inst == inst:
            runs.setdefault((s.set, s.i), []).append(s)
    faults = 0
    for ts in g.nodes.values():
        for i in range(ts.num_tasks):
            faults += len(runs.get((ts.name, i), [])) != 1
    for ts in g.nodes.values():
        starts = [r.start for i in range(ts.num_tasks)
                  for r in runs.get((ts.name, i), [])]
        for parent in g.parents(ts.name):
            ends = [r.end for i in range(g.node(parent).num_tasks)
                    for r in runs.get((parent, i), [])]
            if ends:
                faults += sum(st < max(ends) for st in starts)
    return faults


def _err(a, r) -> float:
    """max |a - r| over the standard deviation of r."""
    a = np.asarray(a, np.float64)
    r = np.asarray(r, np.float64)
    return float(np.max(np.abs(a - r)) / max(np.std(r), 1e-30))


def _gap(r, tok) -> float:
    """How far token ``tok``'s logit lies below the best, in std units."""
    r = np.asarray(r, np.float64)
    return float((r.max() - r[int(tok)]) / max(np.std(r), 1e-30))


class Reference:
    """Replays one instance's train steps from the seed's weights."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed

    def batch(self, index: int):
        sh = self.traffic["shapes"]
        return weights.tokens(100 + index, sh["train_batch"],
                              sh["train_seq"], self.cfg["vocab_size"])

    def replay(self, train_calls, stand_in: str | None = None):
        """Yield (k, params) for k = 0..len(train_calls), running train
        step k+1 between yields.  Keeps each step's loss, the first
        step's gradient norms and the change over all steps; with
        ``stand_in``, also that precision's loss and gradient norms at
        the same parameters."""
        self.losses, self.grad_norms, self.change_norms = [], None, None
        self.global_norms, self.stand_global_norms = [], []
        self.stand_losses, self.stand_grad_norms = [], None
        opt = self.traffic["optimizer"]
        state = reference.train_state(weights.make_params(self.cfg, self.seed))
        for k, call in enumerate(train_calls):
            yield k, state[0]
            toks, labels = self.batch(call[0])
            if stand_in:
                loss, norms, gnorm = reference.grad_norms(
                    state[0], toks, labels, self.cfg, opt, stand_in)
                self.stand_losses.append(float(loss))
                self.stand_global_norms.append(float(gnorm))
                if k == 0:
                    self.stand_grad_norms = np.asarray(norms)
            state, loss, norms, gnorm = reference.train_step(
                state, toks, labels, self.cfg, opt)
            self.losses.append(float(loss))
            self.global_norms.append(float(gnorm))
            if k == 0:
                self.grad_norms = np.asarray(norms)
        yield len(train_calls), state[0]
        init = weights.make_params(self.cfg, self.seed)
        self.change_norms = np.asarray(reference.leaf_norms(
            jax.tree.map(jnp.subtract, state[0], init)))


def _norm_gap(prog, ref, keep=None) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.maximum(floor, 1e-30)
    if keep is not None:
        gap = gap[keep]
    return float(gap.max()) if gap.size else 0.0


def compare(cfg: dict, traffic: dict, seed: int, records: list,
            stand_in: str | None = None) -> dict:
    """The numbers for ``records`` (one per checked instance).

    ``stand_in`` puts the reference in the program's place at that
    precision (the control, "int8"): its logits, tokens and losses are
    compared instead of the program's."""
    out = {k: 0.0 for k in NAMES if k != "exec_faults"}
    sh = traffic["shapes"]
    vocab = cfg["vocab_size"]
    b1 = traffic["optimizer"]["b1"]
    for rec in records:
        ref = Reference(cfg, traffic, seed)
        for k, params in ref.replay(rec.train, stand_in):
            for version, index, _toks, logits in rec.prefill:
                if version != k:
                    continue
                toks, _ = weights.tokens(200 + index, sh["prefill_batch"],
                                         sh["prefill_seq"], vocab)
                where = jnp.full((toks.shape[0], 1), toks.shape[1] - 1)
                r = reference.logits_at(params, toks, where, cfg)[:, 0]
                if stand_in:
                    logits = reference.logits_at(params, toks, where, cfg,
                                                 stand_in)[:, 0]
                out["prefill_logit_err"] = max(out["prefill_logit_err"],
                                               _err(logits, r))
            for steps in rec.decode.values():
                _decode_numbers(out, steps, k, params, cfg, stand_in)
        prog_losses = (ref.stand_losses if stand_in
                       else [float(t[3]) for t in rec.train])
        for lp, lr in zip(prog_losses, ref.losses):
            out["train_loss_gap"] = max(out["train_loss_gap"],
                                        abs(lp - lr) / abs(lr))
        prog_gn = (ref.stand_global_norms if stand_in
                   else [float(t[4]) for t in rec.train])
        for gp, gr in zip(prog_gn, ref.global_norms):
            out["grad_global_norm_gap"] = max(out["grad_global_norm_gap"],
                                              abs(gp - gr) / abs(gr))
        if rec.mu_norms is not None and ref.grad_norms is not None:
            prog_g = (ref.stand_grad_norms if stand_in
                      else np.asarray(rec.mu_norms) / (1 - b1))
            out["grad_norm_gap"] = max(out["grad_norm_gap"],
                                       _norm_gap(prog_g, ref.grad_norms))
        if stand_in:
            out.pop("update_norm_gap", None)   # the stand-in takes no steps
        elif rec.change_norms is not None and ref.grad_norms is not None:
            g = ref.grad_norms
            keep = g >= 1e-3 * np.median(g)
            out["update_norm_gap"] = max(
                out["update_norm_gap"],
                _norm_gap(rec.change_norms, ref.change_norms, keep))
    return out


def _decode_numbers(out, steps, k, params, cfg, stand_in):
    """Compare a rollout's steps that read version ``k``."""
    if not any(st[0] == k for st in steps):
        return
    toks = np.stack([np.asarray(st[1])[:, 0] for st in steps], axis=1)
    rows: dict = {}                                         # distinct rows
    for r in toks:
        rows.setdefault(tuple(r), len(rows))
    seqs = jnp.asarray(np.array(list(rows)), jnp.int32)     # [R, T]
    where = jnp.broadcast_to(jnp.arange(seqs.shape[1]), seqs.shape)
    r_all = np.asarray(reference.logits_at(params, seqs, where, cfg))
    s_all = (np.asarray(reference.logits_at(params, seqs, where, cfg,
                                            stand_in))
             if stand_in else None)
    for j, (version, _tok, _pos, nxt, logits) in enumerate(steps):
        if version != k:
            continue
        nxt = np.asarray(nxt)
        for row, seq in enumerate(toks):
            r = r_all[rows[tuple(seq)], j]
            if stand_in:
                s = s_all[rows[tuple(seq)], j]
                served, lg = int(np.argmax(s)), s
            else:
                served = int(nxt[row])
                lg = None if logits is None else np.asarray(logits)[row]
            out["decode_token_gap"] = max(out["decode_token_gap"],
                                          _gap(r, served))
            if lg is not None:
                out["decode_logit_err"] = max(out["decode_logit_err"],
                                              _err(lg, r))


def input_faults(traffic: dict, cfg: dict, records: list) -> int:
    """Calls whose inputs differ from what the traffic draws for them, and
    rollouts whose next input is not the token served before it."""
    sh = traffic["shapes"]
    vocab = cfg["vocab_size"]
    bad = 0
    for rec in records:
        for index, toks, labels, *_ in rec.train:
            t, l = weights.tokens(100 + index, sh["train_batch"],
                                  sh["train_seq"], vocab)
            bad += not (np.array_equal(toks, t) and np.array_equal(labels, l))
        for _, index, toks, _ in rec.prefill:
            t, _ = weights.tokens(200 + index, sh["prefill_batch"],
                                  sh["prefill_seq"], vocab)
            bad += not np.array_equal(toks, t)
        for steps in rec.decode.values():
            for j in range(1, len(steps)):
                bad += not np.array_equal(np.asarray(steps[j][1])[:, 0],
                                          np.asarray(steps[j - 1][3]))
            bad += len(steps) != sh["decode_steps"]
    return bad


def half_batch(cfg: dict, traffic: dict, seed: int, record) -> dict:
    """The training numbers of a step that leaves out half of its batch
    and takes the mean over the rest: the reference's loss and gradient
    at the seed's weights over half of the warm-up's first batch, against
    the whole batch (a fault planted in the reference)."""
    params = weights.make_params(cfg, seed)
    toks, labels = Reference(cfg, traffic, seed).batch(record.train[0][0])
    h = toks.shape[0] // 2
    opt = traffic["optimizer"]
    full = reference.grad_norms(params, toks, labels, cfg, opt)
    half = reference.grad_norms(params, toks[:h], labels[:h], cfg, opt)
    return dict(train_loss_gap=abs(float(half[0]) - float(full[0]))
                / abs(float(full[0])),
                grad_global_norm_gap=abs(float(half[2]) - float(full[2]))
                / abs(float(full[2])),
                grad_norm_gap=_norm_gap(half[1], full[1]))


def exec_faults(wl, records: list) -> int:
    """Schedule faults over every instance the workload ran, and input
    faults over the checked records."""
    return (sum(schedule_faults(g, wl.spans, inst) for inst, g in wl.runs)
            + input_faults(wl.traffic, wl.cfg, records))


def verdict(numbers: dict, limits: dict) -> bool:
    return all(math.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in numbers)
