"""FLOPs profiler.

The reference counts MACs with module hooks and functional patching
(``profiling/flops_profiler/profiler.py``).  On TPU the compiler already
knows: ``jax.stage/lower(...).cost_analysis()`` reports exact flops and
bytes for the compiled program.  This profiler asks XLA for the cost of the
engine's compiled train step and reports flops/step, params, and achieved
FLOPS when stepping wall-time is available.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from ..utils.logging import logger


def count_params(params: Any) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def cost_analysis_of(fn, *args) -> Dict[str, float]:
    """Lower a jitted function and return XLA's cost analysis."""
    try:
        lowered = fn.lower(*args)
        compiled = lowered.compile()
        return dict(compiled.cost_analysis() or {})
    except Exception as e:  # pragma: no cover
        logger.warning(f"cost_analysis failed: {e}")
        return {}


def per_module_breakdown(cfg, params, batch_size: int = 1,
                         seq_len: Optional[int] = None,
                         measure: bool = False) -> list:
    """Per-module cost table for a transformer-family model (reference
    per-module MACs/params/latency table,
    ``profiling/flops_profiler/profiler.py`` — there via nn.Module hooks; on
    TPU each component is lowered separately and XLA's cost analysis prices
    it exactly).

    Returns rows ``{module, params, flops, macs, bytes, pct}`` for embed,
    each layer's attention and MLP, the final norm, and the LM head;
    ``measure=True`` adds per-module wall latency from timing the jitted
    component on the current backend."""
    import jax.numpy as jnp

    from ..models import transformer as T

    seq = int(seq_len or cfg.max_seq_len)
    cdtype = jax.tree_util.tree_leaves(params["embed"])[0].dtype
    ids_s = jax.ShapeDtypeStruct((batch_size, seq), jnp.int32)
    x_s = jax.ShapeDtypeStruct((batch_size, seq, cfg.hidden_size), cdtype)
    positions = np.broadcast_to(np.arange(seq), (batch_size, seq))
    attn_fn = T._pick_attn(cfg)

    def embed_fn(p, ids):
        x = p["embed"]["tok"][ids]
        if cfg.position == "learned":
            x = x + p["embed"]["pos"][:seq][None]
        return x

    def attn_part(layer, x):
        q, k, v = T.attn_qkv(cfg, layer, x, positions)
        if not getattr(attn_fn, "handles_gqa", False):
            q_rep = cfg.n_heads // cfg.kv_heads
            k, v = T._repeat_kv(k, q_rep), T._repeat_kv(v, q_rep)
        attn = attn_fn(q, k, v, cfg.causal, None)
        attn = attn.reshape(batch_size, seq, cfg.n_heads * cfg.head_dim)
        out = attn @ layer["attn"]["wo"]
        return out + (layer["attn"]["bo"] if cfg.use_bias else 0)

    def mlp_part(layer, x):
        return T.mlp_block(cfg, layer, x)[0]

    def norm_fn(p, x):
        if "final_norm" not in p:  # post-norm models end inside the block
            return x
        return T._norm(x, p["final_norm"]["scale"],
                       p["final_norm"].get("bias"), cfg.norm, cfg.norm_eps)

    def head_fn(p, x):
        return T.logits_fn(cfg, p, x)

    layer0 = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    layer_params = count_params(params["layers"]) // max(cfg.n_layers, 1)
    attn_params = count_params(layer0["attn"])

    # every layer is shape-identical (cost analysis ignores weight VALUES),
    # so attn/mlp are lowered+compiled ONCE and their row is reused per
    # layer — 5 compiles total instead of 2L+3, which matters when
    # print_profile fires this inside a training step on a deep model
    components = [
        ("embed", embed_fn, (params, ids_s), count_params(params["embed"]),
         None),
        ("__attn", attn_part, (layer0, x_s), attn_params, None),
        ("__mlp", mlp_part, (layer0, x_s), layer_params - attn_params, None),
        ("final_norm", norm_fn, (params, x_s),
         count_params(params.get("final_norm", {})), None),
        ("lm_head", head_fn, (params, x_s),
         0 if cfg.tie_embeddings else count_params(params.get("lm_head", {})),
         None),
    ]

    def cost_row(name, fn, args, n_params):
        jf = jax.jit(fn)
        costs = cost_analysis_of(jf, *args)
        row = {"module": name, "params": int(n_params),
               "flops": float(costs.get("flops", 0.0)),
               "macs": float(costs.get("flops", 0.0)) / 2.0,
               "bytes": float(costs.get("bytes accessed", 0.0))}
        if measure:
            concrete = [np.zeros(a.shape, a.dtype) if isinstance(
                a, jax.ShapeDtypeStruct) else a for a in args]
            out = jf(*concrete)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(3):
                out = jf(*concrete)
            jax.block_until_ready(out)
            row["latency_ms"] = (time.perf_counter() - t0) / 3 * 1e3
        return row

    base = {name: cost_row(name, fn, args, n)
            for name, fn, args, n, _ in components}
    rows = [base["embed"]]
    for i in range(cfg.n_layers):
        rows.append(dict(base["__attn"], module=f"layers.{i}.attn"))
        rows.append(dict(base["__mlp"], module=f"layers.{i}.mlp"))
    rows.append(base["final_norm"])
    rows.append(base["lm_head"])
    total = sum(r["flops"] for r in rows) or 1.0
    for r in rows:
        r["pct"] = 100.0 * r["flops"] / total
    return rows


def format_module_table(rows: list) -> str:
    """Render the breakdown the way the reference prints its per-module
    table: name, params, MACs, share of total."""
    hdr = (f"{'module':<20} {'params':>12} {'MACs':>14} {'bytes':>12} "
           f"{'%flops':>7}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['module']:<20} {r['params']:>12,} {r['macs']:>14,.0f} "
            f"{r['bytes']:>12,.0f} {r['pct']:>6.1f}%"
            + (f" {r['latency_ms']:.2f}ms" if "latency_ms" in r else ""))
    return "\n".join(lines)


class FlopsProfiler:
    """Engine plugin (reference FlopsProfiler API: start/stop/print)."""

    def __init__(self, engine, config):
        self.engine = engine
        self.config = config
        self.profile_step = config.profile_step
        self._active = False
        self._t0 = 0.0
        self._last_batch = None
        self.flops = 0.0
        self.duration = 0.0

    def start_profile_maybe(self, global_step: int, batch: Any = None) -> None:
        if batch is not None:
            self._last_batch = batch
        if global_step == self.profile_step and not self._active:
            self._active = True
            self._t0 = time.perf_counter()

    def stop_profile_maybe(self, global_step: int) -> None:
        if self._active and global_step >= self.profile_step:
            self.duration = time.perf_counter() - self._t0
            self._active = False
            self.print_profile()

    def get_total_flops(self) -> float:
        if self._last_batch is None:
            return 0.0
        eng = self.engine
        costs = cost_analysis_of(eng._micro_step, eng.state, self._last_batch,
                                 jax.random.PRNGKey(0))
        self.flops = float(costs.get("flops", 0.0))
        return self.flops

    def get_total_params(self) -> int:
        return count_params(self.engine.state.params)

    def print_profile(self) -> None:
        from ..telemetry.compile_sentinel import expect_recompile

        # the profile lowers+compiles components out of band — announce
        # the compiles so the sentinel doesn't blame the next step
        expect_recompile("flops_profiler")
        params = self.get_total_params()
        flops = self.get_total_flops()
        tput = flops / self.duration if self.duration > 0 else 0.0
        logger.info(
            f"flops profiler: params={params / 1e6:.2f}M "
            f"flops/micro-step={flops / 1e9:.2f}G "
            f"step_time={self.duration * 1e3:.1f}ms "
            f"achieved={tput / 1e12:.2f} TFLOPS")
        self._publish(params, flops, tput)
        if getattr(self.config, "module_depth", -1) != 0:
            self.print_model_profile()

    def _publish(self, params: int, flops: float, tput: float) -> None:
        """Land the one-shot profile on the telemetry registry too, so it
        reaches Prometheus/JSONL alongside the log line (the log scrolls
        away; the gauges survive to the next export)."""
        from ..telemetry.registry import get_registry

        reg = get_registry()
        reg.gauge("deepspeed_tpu_profile_params",
                  "parameter count from the flops profiler").set(params)
        reg.gauge("deepspeed_tpu_profile_flops_per_micro_step",
                  "XLA cost-analysis FLOPs of one micro-step").set(flops)
        reg.gauge("deepspeed_tpu_profile_achieved_tflops",
                  "achieved TFLOPS over the profiled step").set(tput / 1e12)

    def print_model_profile(self) -> None:
        """Per-module breakdown (reference print_model_profile) when the
        engine's model exposes a TransformerConfig."""
        cfg = getattr(self.engine.model, "config", None)
        if cfg is None or not hasattr(cfg, "n_layers"):
            return
        try:
            seq = None
            if self._last_batch is not None:
                leaf = jax.tree_util.tree_leaves(self._last_batch)[0]
                seq = int(np.shape(leaf)[-1])
            rows = per_module_breakdown(cfg, self.engine.state.params,
                                        seq_len=seq)
            logger.info("per-module profile:\n" + format_module_table(rows))
        except Exception as e:
            logger.warning(f"per-module profile failed: {e}")
