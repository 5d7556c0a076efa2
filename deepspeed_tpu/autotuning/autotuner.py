"""Autotuner.

Reference: ``Autotuner`` (autotuning/autotuner.py:42) — mutates the ds_config
over a search space (zero stage, micro batch, ...), runs short experiments,
picks the fastest within memory.  TPU version: candidates are compiled and
timed IN PROCESS (no cluster scheduler needed — XLA compile + a few steps on
the local mesh is the experiment), with HBM feasibility pre-checked from the
compiled executable's memory analysis before anything runs.
"""

from __future__ import annotations

import itertools
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from ..utils.logging import logger

DEFAULT_TUNING_SPACE = {
    "zero_stage": [0, 1, 2, 3],
    "micro_batch": [1, 2, 4, 8],
}


class Autotuner:
    def __init__(self, model_factory: Callable[[], Any], base_config: Dict[str, Any],
                 batch_factory: Callable[[int], Any],
                 tuning_space: Optional[Dict[str, List]] = None,
                 steps_per_trial: int = 3, max_trials: int = 24,
                 mode: str = "grid"):
        """``model_factory()`` -> fresh ModelSpec; ``batch_factory(micro_bs)``
        -> a train_batch input (with gas leading dim)."""
        self.model_factory = model_factory
        self.base_config = dict(base_config)
        self.batch_factory = batch_factory
        self.space = tuning_space or dict(DEFAULT_TUNING_SPACE)
        self.steps_per_trial = steps_per_trial
        self.max_trials = max_trials
        self.mode = mode
        self.results: List[Dict[str, Any]] = []

    def _candidates(self) -> List[Dict[str, Any]]:
        keys = list(self.space)
        combos = [dict(zip(keys, vals))
                  for vals in itertools.product(*self.space.values())]
        if self.mode in ("random", "model"):
            # model mode keeps the FULL grid as the proposal pool (the
            # max_trials budget limits runs, not the searchable space) and
            # shuffles so the seed trials span it
            rng = np.random.RandomState(0)
            rng.shuffle(combos)
        if self.mode == "model":
            return combos
        return combos[:self.max_trials]

    # -- cost model (reference autotuning/tuner/model_based_tuner.py +
    # cost_model.py: fit observed trials, propose the best predicted) -------
    def _featurize(self, cand: Dict[str, Any]) -> np.ndarray:
        feats = []
        for key, values in self.space.items():
            onehot = [1.0 if cand.get(key) == v else 0.0 for v in values]
            feats.extend(onehot)
            if isinstance(cand.get(key), (int, float)):
                feats.append(float(np.log2(max(cand[key], 1))))
            else:
                feats.append(0.0)
        return np.asarray(feats + [1.0])

    def _fit_predict(self, tried: List[Tuple[Dict[str, Any], float]],
                     pool: List[Dict[str, Any]]) -> List[float]:
        """Ridge regression over one-hot + log features: a dependency-free
        stand-in for the reference's XGBoost cost model."""
        X = np.stack([self._featurize(c) for c, _ in tried])
        y = np.asarray([t for _, t in tried])
        lam = 1e-3
        w = np.linalg.solve(X.T @ X + lam * np.eye(X.shape[1]), X.T @ y)
        return [float(self._featurize(c) @ w) for c in pool]

    def _param_count(self) -> Optional[int]:
        if not hasattr(self, "_n_params"):
            try:
                import jax

                spec = self.model_factory()
                shapes = jax.eval_shape(spec.init_params, jax.random.PRNGKey(0))
                self._n_params = sum(int(np.prod(l.shape)) for l in
                                     jax.tree_util.tree_leaves(shapes))
            except Exception:
                self._n_params = None
        return self._n_params

    def _estimate_state_bytes(self, cand: Dict[str, Any]) -> Optional[int]:
        """Analytical ZeRO memory floor (reference fast-mode memory
        estimators): live params + master + moments + grads, divided by the
        stage's shard group.  Activations are excluded (lower bound)."""
        import jax

        n = self._param_count()
        if n is None:
            return None
        stage = cand.get("zero_stage",
                         self.base_config.get("zero_optimization", {}).get("stage", 0))
        shards = max(1, len(jax.devices()))
        live = 2 * n / (shards if stage >= 3 else 1)
        grads = 4 * n / (shards if stage >= 2 else 1)
        state = 12 * n / (shards if stage >= 1 else 1)  # fp32 master + m + v
        return int(live + grads + state)

    def _device_memory(self) -> Optional[int]:
        import jax

        try:
            stats = jax.devices()[0].memory_stats()
            return int(stats.get("bytes_limit", 0)) or None
        except Exception:
            return None

    def _trial_config(self, cand: Dict[str, Any]) -> Dict[str, Any]:
        cfg = dict(self.base_config)
        cfg.setdefault("zero_optimization", {})
        cfg["zero_optimization"] = dict(cfg["zero_optimization"])
        if "zero_stage" in cand:
            cfg["zero_optimization"]["stage"] = cand["zero_stage"]
        if "micro_batch" in cand:
            cfg["train_micro_batch_size_per_gpu"] = cand["micro_batch"]
            cfg.pop("train_batch_size", None)
        if "fused_kernel" in cand:
            # Pallas single-pass Adam vs the XLA-fused optax chain: a
            # legitimate tunable (tune with e.g.
            # tuning_space={"fused_kernel": [False, True], ...})
            opt = dict(cfg.get("optimizer", {"type": "FusedAdam",
                                             "params": {}}))
            if str(opt.get("type", "adamw")).lower() not in (
                    "adam", "adamw", "fusedadam", "deepspeedcpuadam"):
                # non-adam optimizers ignore the knob — injecting it would
                # double the grid with identical trials and let timing
                # noise pick a dead param as "best"
                logger.warning(
                    f"autotuner: fused_kernel is not tunable for optimizer "
                    f"type {opt.get('type')!r}; dropping the knob")
            else:
                opt["params"] = {**opt.get("params", {}),
                                 "fused_kernel": bool(cand["fused_kernel"])}
                cfg["optimizer"] = opt
        return cfg

    def _run_trial(self, cand: Dict[str, Any]) -> Optional[float]:
        import jax

        import deepspeed_tpu
        from ..parallel import mesh as mesh_mod

        cfg = self._trial_config(cand)
        mesh_mod.reset_topology()
        try:
            engine, *_ = deepspeed_tpu.initialize(
                model=self.model_factory(), config=cfg)
            batch = self.batch_factory(cfg["train_micro_batch_size_per_gpu"])
            loss = engine.train_batch(batch)  # compile + warmup
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            for _ in range(self.steps_per_trial):
                loss = engine.train_batch(batch)
            jax.block_until_ready(loss)
            dt = (time.perf_counter() - t0) / self.steps_per_trial
            tokens = np.prod([d for d in np.shape(
                jax.tree_util.tree_leaves(batch)[0])])
            return float(tokens) / dt
        except Exception as e:  # OOM / invalid combo
            logger.warning(f"autotuning trial {cand} failed: {e}")
            return None

    def _pruned_pool(self) -> List[Dict[str, Any]]:
        """Candidates minus those whose analytical memory floor exceeds
        device HBM (reference fast-mode estimators) — shared by the
        sequential and parallel drivers."""
        pool = self._candidates()
        hbm = self._device_memory()
        if hbm:
            kept = []
            for cand in pool:
                est = self._estimate_state_bytes(cand)
                if est is not None and est > hbm:
                    logger.info(f"autotuning: {cand} pruned (state floor "
                                f"{est / 1e9:.1f}GB > HBM {hbm / 1e9:.1f}GB)")
                    self.results.append({"config": cand, "throughput": None,
                                         "pruned": True})
                else:
                    kept.append(cand)
            pool = kept
        return pool

    def tune_parallel(self, runner, nodes=None, slots_per_exp: int = 1,
                      max_parallel: Optional[int] = None,
                      early_stop_patience: Optional[int] = None) -> Dict[str, Any]:
        """Dispatch grid/random candidates CONCURRENTLY over host slots
        (reference ResourceManager + experiment scheduler,
        autotuning/scheduler.py:32).  ``runner(exp, reservation)`` executes
        one trial — use ``SubprocessTrialRunner`` for real out-of-process
        experiments.  mode="model" proposes each candidate from the
        previous results, which is inherently sequential — use tune()."""
        from ..utils.platform import holds_tpu
        from .scheduler import (_LOCAL_HOSTS, Node, ResourceManager,
                                SubprocessTrialRunner)

        if self.mode == "model":
            raise ValueError("model-based tuning is sequential; use tune()")
        nodes = nodes or [Node("localhost", 1)]
        if isinstance(runner, SubprocessTrialRunner):
            # out-of-process trials need the chip, and a chip belongs to
            # one process: the parent stays off JAX (no HBM pruning — an
            # oversized candidate fails inside its own trial instead)
            if holds_tpu() and any(n.host in _LOCAL_HOSTS for n in nodes):
                raise RuntimeError(
                    "tune_parallel: this process has initialized the TPU "
                    "and holds the chip, so local subprocess trials would "
                    "fail or hang at start-up; call tune_parallel before "
                    "anything touches JAX, or use tune() (in-process)")
            pool = self._candidates()[:self.max_trials]
        else:
            pool = self._pruned_pool()[:self.max_trials]
        rm = ResourceManager(nodes, runner,
                             slots_per_exp=slots_per_exp,
                             max_parallel=max_parallel)
        rm.schedule_experiments([
            {"name": f"trial_{i}", "config": self._trial_config(c), "cand": c}
            for i, c in enumerate(pool)])
        finished = rm.run(early_stop_patience=early_stop_patience)
        best, best_tput = None, -1.0
        by_name = {f"trial_{i}": c for i, c in enumerate(pool)}
        for rec in finished:
            cand = by_name.get(rec["name"])
            self.results.append({"config": cand, "throughput": rec["throughput"],
                                 "host": rec.get("host"),
                                 "error": rec.get("error")})
            if rec["throughput"] is not None and rec["throughput"] > best_tput:
                best, best_tput = cand, rec["throughput"]
        if best is None:
            raise RuntimeError("all autotuning trials failed")
        return {"best": best, "throughput": best_tput,
                "config": self._trial_config(best), "trials": self.results}

    def tune(self) -> Dict[str, Any]:
        """Returns the best candidate and records all results (reference
        Autotuner.tune, autotuner.py:404).

        mode="model": after ``model_seed_trials`` seed runs, a cost model
        fit on the observed throughputs proposes each next candidate
        (reference ModelBasedTuner); grid/random run the pool in order.
        Candidates whose analytical memory floor exceeds device HBM are
        skipped without compiling (reference fast-mode estimators)."""
        pool = self._pruned_pool()

        best, best_tput = None, -1.0
        tried: List[Tuple[Dict[str, Any], float]] = []

        def run_one(cand):
            nonlocal best, best_tput
            tput = self._run_trial(cand)
            self.results.append({"config": cand, "throughput": tput})
            logger.info(f"autotuning: {cand} -> "
                        f"{'FAIL' if tput is None else f'{tput:.0f} tok/s'}")
            if tput is not None:
                tried.append((cand, tput))
                if tput > best_tput:
                    best, best_tput = cand, tput

        if self.mode == "model":
            seeds = min(3, len(pool))
            for cand in pool[:seeds]:
                run_one(cand)
            remaining = pool[seeds:]
            budget = self.max_trials - seeds
            while remaining and budget > 0:
                if tried:
                    preds = self._fit_predict(tried, remaining)
                    nxt = remaining.pop(int(np.argmax(preds)))
                else:
                    # every seed failed: keep probing in pool order until
                    # something works to bootstrap the cost model
                    nxt = remaining.pop(0)
                run_one(nxt)
                budget -= 1
        else:
            for cand in pool:
                run_one(cand)

        if best is None:
            raise RuntimeError("all autotuning trials failed")
        return {"best": best, "throughput": best_tput,
                "config": self._trial_config(best), "trials": self.results}
