"""Training job: steps run back to back through ``engine.train_batch`` with
the host input pipeline in the loop, timed in blocks of whole steps.

Traffic parameters: ``sequences_per_step``, ``sequence_length``,
``micro_batch_per_chip``, ``gradient_accumulation_steps``,
``steps_per_block``, ``blocks_in_flight`` (0 if absent), ``warmup_steps`` (a
multiple of ``steps_per_block``, at least 5: donation-variant compiles and
the device queue's ramp land in steps 2-4), ``loss_tolerance``, and
``reports``: which end-to-end metric takes which quantity this generator
measures (``total_rate``, ``setup_s``).

The window is cut into consecutive blocks of ``steps_per_block`` steps.  A
block's time runs from the completion (``block_until_ready`` on the loss) of
the block before to its own.  With ``blocks_in_flight`` 0 the host waits for
a block before it sends the next, and ``steps_per_print`` equals
``steps_per_block``: the device queue drains at every boundary, so whatever
the host takes to come back is device idle time.  With ``blocks_in_flight``
n the host sends n more blocks before it waits for one, and the engine never
drains the queue itself (``steps_per_print`` beyond the run): the device
idles only when the host falls a whole block behind, as in a job that prints
every few hundred steps.  Once ``--seconds`` have passed at a block boundary
no more is sent; the window ends when the last block sent completes, and
``total_rate`` is every token sent over the exact time to that boundary: a
stall of the device anywhere in the window counts, and no step is cut in
half by a nominal edge.  The rate at the median block time
(``block_median_rate``) is kept beside it for a per-layer metric: the two
part when a block stalls.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, roofline, stats
from benchmark.reference import dense_lm


# steps_per_print of a run that keeps blocks in flight: the engine drains the
# device queue at every multiple of it, so it lies beyond any run
NEVER_PRINT = 10 ** 9


def _batches(seed: int, vocab: int, gas: int, rows: int, seq: int):
    """The host input pipeline: fresh token ids from the seed every step."""
    rng = np.random.default_rng(seed)
    while True:
        yield rng.integers(0, vocab, (gas, rows, seq), dtype=np.int32)


def timed_blocks(send_block, wait, t0: float, seconds: float, ahead: int,
                 clock=time.perf_counter):
    """Send blocks back to back from ``t0``, ``ahead`` more in flight than
    the one waited for; time each from the completion of the one before to
    its own.  Once ``seconds`` have passed at a completion nothing more is
    sent, and every block sent is waited for and counted.  Returns the block
    times and what ``send_block`` returned for each, in order."""
    times: List[float] = []
    done: List[Any] = []
    sent: List[Any] = []
    t_prev, sending = t0, True
    while sending or sent:
        if sending:
            sent.append(send_block())
            if len(sent) <= ahead:
                continue
        handle = sent.pop(0)
        wait(handle)
        now = clock()
        times.append(now - t_prev)
        done.append(handle)
        t_prev = now
        if now - t0 >= seconds:
            sending = False
    return times, done


def run(ctx: harness.Context) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import initialize_topology
    from deepspeed_tpu.runtime.config import MeshConfig

    tr, sizes = ctx.traffic, ctx.model_sizes()
    family = ctx.family()
    desc = family.describe(sizes)
    seq, spb = int(tr["sequence_length"]), int(tr["steps_per_block"])
    ahead = int(tr.get("blocks_in_flight", 0))
    gas = int(tr["gradient_accumulation_steps"])
    micro = int(tr["micro_batch_per_chip"])
    warm_steps = int(tr["warmup_steps"])
    if not ctx.rehearse and (warm_steps % spb or warm_steps < 5):
        raise ValueError("warmup_steps must be a multiple of steps_per_block "
                         "and at least 5")
    n_layers = int(sizes["num_hidden_layers"])
    chips = len(ctx.devices)

    mesh = dict(ctx.config["mesh"])
    topo = initialize_topology(MeshConfig(**mesh), devices=ctx.devices)
    dp = topo.dp_world_size
    rows = micro * dp
    if rows * gas != int(tr["sequences_per_step"]):
        raise ValueError(f"micro {micro} x dp {dp} x gas {gas} != "
                         f"{tr['sequences_per_step']} sequences a step")
    tokens_per_step = rows * gas * seq
    model = family.build(sizes, n_layers, seq, jnp.float32)
    ds_config = dict(ctx.config["engine"])
    ds_config.update({
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": gas,
        "mesh": mesh,
        "seed": harness.seed31(ctx.seed),
        "steps_per_print": NEVER_PRINT if ahead else spb,
    })
    t0 = time.perf_counter()
    with harness.annotate("bench.initialize"):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=ds_config, topology=topo)
    ctx.say(f"train: {ctx.config['name']} layers={n_layers} "
            f"params={roofline.param_count(desc, n_layers) / 1e9:.4f} B "
            f"mesh={mesh} zero={ds_config['zero_optimization']} seq={seq} "
            f"rows={rows} gas={gas} tokens/step={tokens_per_step} "
            f"steps_per_block={spb} blocks_in_flight={ahead} "
            f"warmup_steps={warm_steps}; engine built "
            f"in {time.perf_counter() - t0:.2f} s")
    batches = _batches(ctx.seed, desc["vocab_size"], gas, rows, seq)

    # ---- correctness, outside the window: the first step's loss against
    # the float32 reference's loss of the same batch, same rounded weights
    first = next(batches)
    t0 = time.perf_counter()
    compute = jnp.bfloat16 if ds_config.get("bf16", {}).get("enabled") \
        else None
    ref_loss = dense_lm.loss(desc, engine.state.params,
                             first.reshape(-1, seq), round_to=compute)
    t_ref = time.perf_counter() - t0

    # ---- warm-up: every program this cell uses, and the queue's ramp
    losses: List[float] = []
    t0 = time.perf_counter()
    loss = engine.train_batch(first)
    losses.append(float(loss))
    t_first = time.perf_counter() - t0
    for _ in range(warm_steps - 1):
        loss = engine.train_batch(next(batches))
    jax.block_until_ready(loss)
    losses.append(float(loss))
    loss_err = abs(losses[0] - ref_loss)
    tol = float(tr["loss_tolerance"])
    ctx.say(f"train: first-step loss {losses[0]:.6f} vs float32 reference "
            f"{ref_loss:.6f}: |diff| {loss_err:.2e} (tolerance {tol}); "
            f"reference took {t_ref:.2f} s, first step {t_first:.2f} s, "
            f"{warm_steps} warm-up steps {time.perf_counter() - t0:.2f} s")

    # ---- the window
    compiles0 = harness.compiles()
    ctx.start_trace()
    t_w0 = time.perf_counter()
    setup_s = t_w0 - ctx.t_process_start

    def send_block():
        for _ in range(spb):
            with harness.annotate("bench.next_batch"):
                batch = next(batches)
            with harness.annotate("bench.train_batch"):
                loss = engine.train_batch(batch)
        return loss

    def wait(loss):
        with harness.annotate("bench.block_wait"):
            jax.block_until_ready(loss)

    blocks, block_losses = timed_blocks(send_block, wait, t_w0,
                                        ctx.window_seconds, ahead)
    t_w1 = t_w0 + sum(blocks)
    ctx.stop_trace()
    compiles_in_window = harness.compiles() - compiles0

    losses += [float(x) for x in block_losses]
    finite = all(math.isfinite(x) for x in losses)
    median_rate = stats.block_median_rate(blocks, spb * tokens_per_step, chips)
    total = stats.total_rate(blocks, spb * tokens_per_step, chips)
    ctx.say("train: block seconds " + " ".join(f"{b:.4f}" for b in blocks))
    ctx.say(f"train: {len(blocks)} blocks of {spb} steps in "
            f"{t_w1 - t_w0:.3f} s; median block {np.median(blocks):.4f} s, "
            f"min {min(blocks):.4f}, max {max(blocks):.4f}; "
            f"total-over-elapsed {total:.1f} tokens/s/chip, "
            f"at the median block {median_rate:.1f}; "
            f"loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
            f"compiles in window {compiles_in_window}")
    flops_tok = roofline.train_flops_per_token(desc, n_layers, seq)
    engine.close()
    quantities = {"total_rate": total, "setup_s": setup_s}
    return {
        "correct": finite and loss_err < tol,
        "attempted": len(blocks) * spb,
        "failed": 0 if finite else len(blocks) * spb,
        "end_to_end": {name: quantities[q]
                       for q, name in tr["reports"].items()},
        "counts": {"blocks": len(blocks), "steps": len(blocks) * spb,
                   "tokens_per_step": tokens_per_step,
                   "compiles_in_window": compiles_in_window},
        # for the readers
        "kind": "train", "desc": desc, "n_layers": n_layers, "seq": seq,
        "rows": rows, "gas": gas, "chips": chips, "steps_per_block": spb,
        "blocks": blocks, "tokens_per_step": tokens_per_step,
        "block_median_rate": median_rate, "total_rate": total,
        "flops_per_token": flops_tok,
        "compiles_in_window": compiles_in_window,
        "window": (t_w0, t_w1),
    }
