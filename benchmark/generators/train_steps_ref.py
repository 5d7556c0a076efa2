"""``train_steps`` with the reference the configuration names.

``train_steps.py`` binds ``reference/dense_lm.py`` and
``roofline.train_flops_per_token`` in code; a block that is not the dense one
needs its own reference and its own count.  This kind plays the same job
through the same ``run`` — blocks, one in flight, exact-time division, all
unchanged — with the module ``reference/<name>.py`` that the configuration
file's ``"reference"`` key names (``loss_and_grads(desc, params, ids,
round_to) -> (loss, grads, picks)``) and ``moe_train_counts.py``'s operations
per token.  Token ids are drawn below the configuration's ``vocab_size``: the
held slice.

``correct`` is ``train_steps``'s — the first step's loss against the
reference's on the same batch and the same bf16-rounded weights, every loss
finite — and what follows, each reading under a limit ``<name>_tolerance`` of
the traffic file.  All are of the first step, on the same 4 micro-batches:

- ``grad_norm``: the global gradient norm before clipping
  (``engine.get_global_grad_norm()``) against the norm of the reference's
  gradients, as ``|a - b| / b``.
- ``grad_tree``, ``grad_leaf``: the program's gradient leaf by leaf against
  the reference's, as ``|a - b| / |b|`` over the whole tree and over the
  worst leaf.  The fused step gives back no gradient; after a first step
  Adam's first moment is ``(1 - beta1)`` times the clipped gradient, so the
  gradient is read from the optimizer's state.  A loss and a norm are means,
  which unbiased rounding hardly moves; a difference of vectors adds up
  every element's error.
- ``param_change``: what the step did to the parameters against what plain
  AdamW (written out below: clip by the global norm, moments from zero, bias
  correction, decoupled decay, the schedule's first rate from the
  configuration file) does to the same parameters with the reference's
  gradients, as ``|a - b| / |b|`` over the whole tree.  A state left
  unchanged reads 1.  A leaf the family names a buffer (``describe``'s
  ``buffers``) must come back bit for bit, or the run is not correct
  whatever the readings.  AdamW's first step moves every element by the
  rate times its gradient's sign, so this reads twice the root of the share
  of elements whose sign bf16 turned (logged beside it) and is no rounding
  error of the update.
- ``picks``: per expert layer, the picks on each held expert that the program
  counted in that step (``engine.moe_stats()``) against the reference's on
  the same tokens: the sum of ``|a - b|`` over the sum of ``b``.
- ``held_picks_drift``: the window's picks on held experts a token against
  the first step's, as ``|a / b - 1|``: a share trained alone can learn to
  route around its experts (PERF.md, PR 32), and a window in which it did
  measures another load.

``negative_control`` in the traffic file (``{"mantissa_bits": n}``; no
committed file has it) puts the reference with its weights at that mantissa
in the program's place: its loss, gradients, picks and what plain AdamW does
with its gradients are read as the program's are, under the same limits, and
decide ``correct``, which has to come out false.  The result line then says
``"negative_control": true``.

The reference runs before the first step, beside the engine's state (its
gradients are added up on the host, one sequence at a time on the device);
the leaf-by-leaf comparison runs on the host in numpy.

The counters of the window — ``engine.moe_stats()`` is read, and so reset,
when the window starts and once more when it has ended — go on the result:
``moe`` (the dict as the engine returns it), ``moe_held_picks``,
``moe_rows_run``, ``moe_load_max_over_mean`` (the fullest held expert's picks
over the mean held expert's, averaged over the expert layers) and the
operations per token priced with the picks counted.  A program without
``moe_stats`` (a parent commit) never gets this far: it has no such family.
"""

from __future__ import annotations

import math
import time
from concurrent.futures import ThreadPoolExecutor
import types
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import harness, moe_train_counts, roofline

READINGS = ("grad_norm", "grad_tree", "grad_leaf", "param_change", "picks",
            "held_picks_drift")


def _sq(a) -> float:
    a = np.ravel(a)
    return float(np.dot(a, a))


def first_step_rate(engine_cfg: Dict[str, Any]) -> float:
    """The learning rate of the first step, from the configuration file."""
    sched = engine_cfg.get("scheduler")
    if not sched:
        return float(engine_cfg["optimizer"]["params"]["lr"])
    if sched["type"] != "WarmupLR":
        raise ValueError(f"first rate of a {sched['type']} schedule")
    # a warm-up of either shape starts at its least rate
    return float(sched["params"].get("warmup_min_lr", 0.0))


def adamw_first_step(g, theta, norm: float, engine_cfg: Dict[str, Any]):
    """theta_1 - theta_0 of plain AdamW on one leaf, moments from zero: the
    gradient clipped by the global ``norm``; m = (1 - b1) g and v = (1 - b2)
    g^2 are g and g^2 again once bias-corrected by 1 - b1 and 1 - b2; then
    -lr (m / (sqrt(v) + eps) + wd theta)."""
    opt = engine_cfg["optimizer"]["params"]
    if engine_cfg["optimizer"]["type"] != "AdamW":
        raise ValueError("the plain update is AdamW's")
    clip = float(engine_cfg.get("gradient_clipping", 0.0))
    if clip > 0:
        g = g * np.float32(min(1.0, clip / (norm + 1e-6)))
    update = g / (np.abs(g) + np.float32(opt.get("eps", 1e-8)))
    return np.float32(-first_step_rate(engine_cfg)) * (
        update + np.float32(opt.get("weight_decay", 0.0)) * theta)


def step_readings(n: int, leaf, engine_cfg=None, norm: float = 0.0
                  ) -> Dict[str, Any]:
    """``leaf(i)`` for i < n: (name, is a buffer, gradient, reference's
    gradient, parameter change, plain AdamW's change with the reference's
    gradient[, the parameter]), float32 numpy each; leaves are read on a few
    threads.  -> the gradient's relative error over the tree and over the
    worst leaf, the change's over the tree, the share of elements whose
    gradient changed sign, the buffers that moved and, given the parameter,
    ``engine_cfg`` and the gradient's own ``norm``, the change against plain
    AdamW with that same gradient (``param_change_own``: the update alone)."""
    def one(i):
        name, is_buffer, g, gr, d, dr, *theta = leaf(i)
        if is_buffer:
            return name, bool(np.any(d != 0))
        own = (adamw_first_step(g, theta[0], norm, engine_cfg)
               if theta and engine_cfg else None)
        return (name, _sq(g - gr), _sq(gr), _sq(d - dr), _sq(dr),
                int(np.count_nonzero(np.signbit(g) != np.signbit(gr))),
                g.size, 0.0 if own is None else _sq(d - own),
                0.0 if own is None else _sq(own))

    with ThreadPoolExecutor(max_workers=8) as pool:
        parts = list(pool.map(one, range(n)))
    moved = [p[0] for p in parts if len(p) == 2 and p[1]]
    parts = [p for p in parts if len(p) > 2]
    g_err, g_ref, d_err, d_ref, turned, count, o_err, o_ref = (
        sum(p[k] for p in parts) for k in range(1, 9))
    per_leaf = sorted(((math.sqrt(p[1] / p[2]) if p[2] else
                        (0.0 if p[1] == 0 else math.inf), p[0])
                       for p in parts), reverse=True)
    return {"grad_tree": math.sqrt(g_err / g_ref),
            "grad_leaf": per_leaf[0][0], "worst_leaves": per_leaf[:3],
            "param_change": math.sqrt(d_err / d_ref) if d_ref else math.inf,
            "param_change_own": math.sqrt(o_err / o_ref) if o_ref else None,
            "signs_turned": turned / count, "buffers_moved": moved}


def _named_leaves(tree) -> List[Tuple[str, Any]]:
    import jax

    return [("/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path), leaf)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


def _first_moment(opt_state):
    """Adam's first moment in an optax state: the one entry with ``mu``."""
    import jax

    found = [s.mu for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} first moments in the optimizer's "
                           f"state")
    return found[0]


def run(ctx: harness.Context) -> Dict[str, Any]:
    import deepspeed_tpu

    man, tr = ctx.manifest, ctx.traffic
    engine_cfg = ctx.config["engine"]
    train = man.module("generators", "train_steps")
    reference = man.module("reference", ctx.config["reference"])
    control = tr.get("negative_control")
    seen: Dict[str, Any] = {"calls": 0}

    def host(tree):
        return [np.asarray(a, np.float32) for _, a in _named_leaves(tree)]

    # ---- the reference: loss, gradients and picks of the first step
    def ref_loss(desc, params, ids, round_to=None):
        t0 = time.perf_counter()
        loss, grads, picks = reference.loss_and_grads(desc, params, ids,
                                                      round_to=round_to)
        seen.update(desc=desc, theta0=host(params),
                    names=[n for n, _ in _named_leaves(params)],
                    ref={"loss": loss, "grads": host(grads),
                         "picks": np.asarray(picks),
                         "norm": reference.grad_norm(grads)})
        if control:
            loss, grads, picks = reference.loss_and_grads(
                desc, params, ids, round_to=round_to,
                mantissa_bits=int(control["mantissa_bits"]))
            seen["low"] = {"loss": loss, "grads": host(grads),
                           "picks": np.asarray(picks),
                           "norm": reference.grad_norm(grads)}
        seen["t_ref"] = time.perf_counter() - t0
        return seen["ref"]["loss"]

    def leaf_of(grads, changes):
        """What ``step_readings`` reads: ``grads`` and ``changes`` give a
        leaf by its index."""
        ref = seen["ref"]

        def leaf(i):
            name, theta = seen["names"][i], seen["theta0"][i]
            return (name, any(b in name for b in seen["desc"]["buffers"]),
                    grads(i), ref["grads"][i], changes(i),
                    adamw_first_step(ref["grads"][i], theta, ref["norm"],
                                     engine_cfg), theta)

        return leaf

    # ---- the engine: the first step's norm, gradient, change and counters
    real_initialize = deepspeed_tpu.initialize

    def initialize(*args, **kwargs):
        out = real_initialize(*args, **kwargs)
        engine = seen["engine"] = out[0]
        real_train_batch = engine.train_batch

        def first_train_batch(batch):
            loss = real_train_batch(batch)
            engine.train_batch = real_train_batch
            t0 = time.perf_counter()
            norm = float(engine.get_global_grad_norm())
            clip = float(engine_cfg.get("gradient_clipping", 0.0))
            b1 = engine_cfg["optimizer"]["params"].get("betas", (0.9,))[0]
            # m = (1 - b1) x the clipped gradient
            back = 1.0 / ((1.0 - b1) * (min(1.0, clip / (norm + 1e-6))
                                        if clip > 0 else 1.0))
            mu = [a for _, a in _named_leaves(_first_moment(
                engine.state.opt_state))]
            after = [a for _, a in _named_leaves(engine.state.params)]
            seen.update(
                loss=float(loss), grad_norm=norm,
                first_moe=engine.moe_stats(),
                step=step_readings(len(mu), leaf_of(
                    lambda i: np.asarray(mu[i], np.float32)
                    * np.float32(back),
                    lambda i: np.asarray(after[i], np.float32)
                    - seen["theta0"][i]), engine_cfg, norm))
            if control:
                low = seen["low"]
                low["step"] = step_readings(len(mu), leaf_of(
                    lambda i: low["grads"][i],
                    lambda i: adamw_first_step(
                        low["grads"][i], seen["theta0"][i], low["norm"],
                        engine_cfg)))
                del low["grads"]
            del seen["theta0"], seen["ref"]["grads"]
            seen["t_step"] = time.perf_counter() - t0
            return loss

        engine.train_batch = first_train_batch
        return out

    real_start = ctx.start_trace

    def start_trace():
        seen["engine"].moe_stats()  # the warm-up's: read and reset
        real_start()

    def flops_per_token(desc, n_layers, seq):
        # train_steps asks once, after the window and before engine.close()
        seen["calls"] += 1
        seen["moe"] = moe = seen["engine"].moe_stats()
        seen["tokens"] = moe["steps"] * int(tr["sequences_per_step"]) * seq
        held = float(np.sum(moe["picks"]))
        return moe_train_counts.train_flops_per_token(
            desc, seq, held / max(1, seen["tokens"]))

    real_dense_lm = train.dense_lm
    train.dense_lm = types.SimpleNamespace(loss=ref_loss)
    train.roofline = types.SimpleNamespace(
        param_count=lambda desc, n_layers: moe_train_counts.param_count(desc),
        train_flops_per_token=flops_per_token)
    deepspeed_tpu.initialize = initialize
    ctx.start_trace = start_trace
    try:
        result = train.run(ctx)
    finally:
        deepspeed_tpu.initialize = real_initialize
        ctx.start_trace = real_start
        train.roofline = roofline
        train.dense_lm = real_dense_lm
    if seen["calls"] != 1:
        raise RuntimeError(f"train_steps asked for the operations per token "
                           f"{seen['calls']} times: the window's counters "
                           f"were read with them")

    # ---- the window's counters
    moe = seen["moe"]
    picks = np.asarray(moe["picks"], np.float64)
    if moe["steps"] != result["attempted"]:
        raise RuntimeError(f"counters of {moe['steps']} steps for a window "
                           f"of {result['attempted']}")
    held_a_token = picks.sum() / seen["tokens"]

    # ---- the comparisons
    def against(side, got_picks):
        want = seen["ref"]["picks"]
        return {"loss": abs(side["loss"] - seen["ref"]["loss"]),
                "grad_norm": abs(side["norm"] - seen["ref"]["norm"])
                / seen["ref"]["norm"],
                "picks": float(np.abs(got_picks - want).sum())
                / float(want.sum()),
                **{k: side["step"][k]
                   for k in ("grad_tree", "grad_leaf", "param_change")}}

    first_picks = np.asarray(seen["first_moe"]["picks"])
    first_a_token = first_picks.sum() / (
        int(tr["sequences_per_step"]) * result["seq"])
    drift = abs(held_a_token / first_a_token - 1.0)
    readings = against({"loss": seen["loss"], "norm": seen["grad_norm"],
                        "step": seen["step"]}, first_picks)
    readings["held_picks_drift"] = drift
    limits = {k: float(tr[k + "_tolerance"]) for k in ("loss",) + READINGS}
    step = seen["step"]
    ctx.say(f"train: first step against the float32 reference: gradient norm "
            f"{seen['grad_norm']:.6f} vs {seen['ref']['norm']:.6f}; "
            f"{step['signs_turned']:.3e} of the gradient's elements turned "
            f"sign (2 x root: {2 * math.sqrt(step['signs_turned']):.3f}; the "
            f"change against plain AdamW with the program's own gradient "
            f"{step['param_change_own']:.3e}); "
            f"worst leaves " + ", ".join(
                f"{n} {e:.3e}" for e, n in step["worst_leaves"])
            + f"; picks on held experts {int(first_picks.sum())} vs "
            f"{int(seen['ref']['picks'].sum())}; held picks a token "
            f"{first_a_token:.4f} -> {held_a_token:.4f} over the window; "
            f"buffers moved {step['buffers_moved']}; reference with "
            f"gradients took {seen['t_ref']:.1f} s, the comparison "
            f"{seen['t_step']:.1f} s")
    ctx.say("train: readings (limit): " + ", ".join(
        f"{k} {v:.3e} ({limits[k]})" for k, v in readings.items()))
    ok = not step["buffers_moved"] and all(
        readings[k] < limits[k] for k in READINGS)
    result["correct"] = bool(result["correct"] and ok)
    result["readings"] = readings
    if control:
        low = against(seen["low"], seen["low"]["picks"])
        low["held_picks_drift"] = drift
        ctx.say(f"train: NEGATIVE CONTROL, the reference with its weights at "
                f"{control['mantissa_bits']} mantissa bits in the program's "
                f"place: " + ", ".join(
                    f"{k} {v:.3e} ({limits[k]})" for k, v in low.items())
                + "; refused by " + ", ".join(
                    k for k in low if not low[k] < limits[k]))
        result["correct"] = bool(result["failed"] == 0 and all(
            low[k] < limits[k] for k in low))
        result["negative_control"] = True
        result["readings_control"] = low

    # ---- for the readers
    result.update(
        moe=moe, moe_held_picks=float(picks.sum()),
        moe_rows_run=float(np.sum(moe["rows_run"])),
        moe_load_max_over_mean=float(np.mean(
            picks.max(axis=1) / np.maximum(picks.mean(axis=1), 1e-9))))
    result["counts"]["moe_held_picks"] = int(picks.sum())
    ctx.say(f"train: window's held picks {int(picks.sum())} in "
            f"{moe['steps']} steps ({held_a_token:.4f} a "
            f"token over {len(picks)} expert layers), rows run "
            f"{int(result['moe_rows_run'])}, fullest / mean held expert "
            f"{result['moe_load_max_over_mean']:.4f}; "
            f"{result['flops_per_token'] / 1e9:.4f} GFLOP a token")
    return result
