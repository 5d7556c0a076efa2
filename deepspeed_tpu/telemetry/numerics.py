"""Numerics observatory: in-graph training-health stats + anomaly sentinel.

The repo traces *where time goes* (goodput ledger) and *where requests
go* (fleet request tracing); this module watches *whether training is
numerically healthy*.  Three parts:

1. **In-graph stat builders** (pure ``jnp``, safe inside ``jit``): tree
   and stacked-``[L]`` per-layer norms / max-abs / nonfinite counts,
   per-leaf nonfinite counts keyed by pytree path, EF-residual norms per
   ``TrainState.comm_errors`` slot, and bit-exact ``uint32`` leaf
   checksums for the cross-rank divergence audit.  The engine carries
   these as EXTRA FUSED STEP OUTPUTS — they live on device until the
   existing ``steps_per_print`` boundary pulls them, so the hot path
   gains zero host syncs and replay recompiles stay 0.

2. **:class:`NumericsLedger`** — the host-side anomaly sentinel.  At
   every boundary it folds the pulled stats into rolling windows and
   runs the detectors (nonfinite / loss-spike / grad-norm-spike /
   overflow-storm / stagnant-loss / divergence).  A firing detector
   counts ``deepspeed_tpu_train_numerics_anomalies_total{kind}``, fires
   ONE flight-recorder dump carrying the full per-layer breakdown
   (which layer went nonfinite first), and records a pending incident
   that the next checkpoint commit stamps into its manifest meta so
   resume-time triage sees it (``checkpoint/saving.py``).

3. **:func:`compare_rank_checksums`** — the host half of the divergence
   audit: given per-rank ``{path: checksum}`` maps (the engine's
   boundary-cadence shard_map audit gathers them; ZeRO 0/1 master
   params must be bit-identical across the data axis) it names the
   FIRST diverging leaf, catching silent collective corruption.

This module is the single owner of the ``deepspeed_tpu_train_numerics_*``
metric family (``analysis/metric_lint.py``).  See docs/OBSERVABILITY.md
"Numerics observatory".
"""

from __future__ import annotations

import collections
import json
import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .registry import MetricsRegistry, get_registry

__all__ = [
    "NumericsLedger", "tree_health", "stacked_health", "leaf_nonfinite",
    "leaf_checksums", "ef_residual_norms", "activation_stats",
    "compare_rank_checksums", "shape_boundary_report",
    "get_numerics_ledger", "set_numerics_ledger",
    "last_numerics_summary", "pending_incident_meta",
]

#: anomaly kinds the sentinel can emit (the {kind} label values)
ANOMALY_KINDS = ("nonfinite", "loss_spike", "grad_spike", "overflow_storm",
                 "stagnant_loss", "divergence")


# ---------------------------------------------------------------- path utils
def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _flat_leaves(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(_path_str(p), leaf) for p, leaf in flat]


# --------------------------------------------------------- in-graph builders
def tree_health(tree: Any, inv_scale=None) -> Dict[str, Any]:
    """Whole-tree health scalars (in-trace): fp32 L2 norm, max-abs and
    nonfinite element count over every leaf.  ``inv_scale`` (e.g.
    ``1 / (gas * loss_scale)``) rescales the magnitude stats so fp16
    loss-scaled gradients report their TRUE magnitudes; nonfinite counts
    are scale-invariant and stay raw."""
    leaves = [jnp.asarray(l) for l in jax.tree_util.tree_leaves(tree)]
    if not leaves:
        z = jnp.float32(0)
        return {"norm": z, "max_abs": z, "nonfinite": jnp.int32(0)}
    f32 = [l.astype(jnp.float32) for l in leaves]
    sumsq = sum(jnp.sum(jnp.square(x)) for x in f32)
    max_abs = jnp.float32(0)
    for x in f32:
        max_abs = jnp.maximum(max_abs, jnp.max(jnp.abs(x)))
    nonfinite = sum(jnp.sum(~jnp.isfinite(x)) for x in f32).astype(jnp.int32)
    norm = jnp.sqrt(sumsq)
    if inv_scale is not None:
        norm = norm * inv_scale
        max_abs = max_abs * inv_scale
    return {"norm": norm, "max_abs": max_abs, "nonfinite": nonfinite}


def stacked_health(subtree: Any, inv_scale=None) -> Optional[Dict[str, Any]]:
    """Per-layer health over a STACKED layer tree (every leaf
    ``[L, ...]`` with a shared leading layer dim, the ``params["layers"]``
    layout the transformer scan runs over): ``[L]`` fp32 norm, max-abs
    and nonfinite count vectors.  Returns None when the tree is empty or
    the leading dims disagree (not a stacked tree — e.g. the MLP test
    fixtures), so callers can gate the per-layer block structurally."""
    leaves = [jnp.asarray(l) for l in jax.tree_util.tree_leaves(subtree)]
    if not leaves or any(l.ndim < 1 for l in leaves):
        return None
    L = leaves[0].shape[0]
    if any(l.shape[0] != L for l in leaves) or L == 0:
        return None
    f32 = [l.astype(jnp.float32).reshape(L, -1) for l in leaves]
    sumsq = sum(jnp.sum(jnp.square(x), axis=1) for x in f32)
    max_abs = jnp.zeros((L,), jnp.float32)
    for x in f32:
        max_abs = jnp.maximum(max_abs, jnp.max(jnp.abs(x), axis=1))
    nonfinite = sum(jnp.sum(~jnp.isfinite(x), axis=1)
                    for x in f32).astype(jnp.int32)
    norm = jnp.sqrt(sumsq)
    if inv_scale is not None:
        norm = norm * inv_scale
        max_abs = max_abs * inv_scale
    return {"norm": norm, "max_abs": max_abs, "nonfinite": nonfinite}


def leaf_nonfinite(tree: Any) -> Dict[str, Any]:
    """Per-leaf nonfinite element counts keyed by pytree path (in-trace).
    This is what lets a dump NAME the offending leaf (``layers/attn/wq``
    or ``layer_1/w``) instead of reporting a global count."""
    return {p: jnp.sum(~jnp.isfinite(jnp.asarray(l).astype(jnp.float32)))
            .astype(jnp.int32) for p, l in _flat_leaves(tree)}


def activation_stats(x: Any) -> Any:
    """``[3]`` fp32 activation-health row for one layer/stage output:
    ``(l2_norm, max_abs, nonfinite_count)``.  Stacked by the transformer
    layer scan into the ``[L, 3]`` side output (``models/transformer.py``)
    and accumulated per stage by the pipe scan (``runtime/pipe``)."""
    f = jnp.asarray(x).astype(jnp.float32)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(f))),
                      jnp.max(jnp.abs(f)),
                      jnp.sum(~jnp.isfinite(f)).astype(jnp.float32)])


def ef_residual_norms(comm_errors: Any) -> Dict[str, Any]:
    """Per-slot L2 norm of the error-feedback residual state (in-trace).
    ``comm_errors`` is the ``TrainState.comm_errors`` dict — slots
    ``overlap`` / ``reduce`` / ``pipe`` as wired.  A residual whose norm
    grows without bound means EF is diverging, not converging."""
    out = {}
    for slot, sub in (comm_errors or {}).items():
        leaves = [jnp.asarray(l).astype(jnp.float32)
                  for l in jax.tree_util.tree_leaves(sub)]
        if not leaves:
            continue
        out[str(slot)] = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                      for x in leaves))
    return out


_UINT_OF_WIDTH = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def leaf_checksums(tree: Any) -> Dict[str, Any]:
    """Bit-exact per-leaf checksums (in-trace): each leaf bitcast to the
    same-width unsigned int and summed mod 2^32.  Integer addition is
    exact and commutative, so the checksum is reduction-order-invariant
    — two ranks holding bit-identical leaves ALWAYS produce equal sums,
    and a single flipped mantissa bit changes the sum."""
    out = {}
    for p, leaf in _flat_leaves(tree):
        x = jnp.asarray(leaf)
        u = _UINT_OF_WIDTH.get(x.dtype.itemsize)
        if u is None:  # exotic width: hash the fp32 cast instead
            x = x.astype(jnp.float32)
            u = jnp.uint32
        bits = jax.lax.bitcast_convert_type(x, u).astype(jnp.uint32)
        out[p] = jnp.sum(bits, dtype=jnp.uint32)
    return out


# ------------------------------------------------------- divergence (host)
def compare_rank_checksums(per_rank: Dict[Any, Dict[str, int]]) -> dict:
    """Host half of the divergence audit: given ``{rank: {path: sum}}``
    maps, name every leaf whose checksum differs across ranks.  Returns
    ``{"ok", "ranks", "first_diverging_leaf", "diverging"}`` — the first
    diverging leaf (lexicographic path order, stable across runs) is
    what the anomaly and the dump report."""
    ranks = sorted(per_rank, key=str)
    if len(ranks) < 2:
        return {"ok": True, "ranks": len(ranks),
                "first_diverging_leaf": None, "diverging": []}
    paths = sorted({p for r in ranks for p in per_rank[r]})
    diverging = []
    for p in paths:
        vals = {int(per_rank[r][p]) for r in ranks if p in per_rank[r]}
        if len(vals) > 1:
            diverging.append(p)
    return {"ok": not diverging, "ranks": len(ranks),
            "first_diverging_leaf": diverging[0] if diverging else None,
            "diverging": diverging}


def shape_boundary_report(host: dict) -> dict:
    """Shape the engine's pulled (host-side) stats tree into the
    sentinel's boundary report: scalars to Python numbers plus the
    'which layer went nonfinite first' attribution — activation stats
    give the forward-order first offender; gradient per-layer counts
    are the fallback attribution.  Pure host-side numpy (the one
    device_get already happened in the engine)."""
    rep = {
        "loss": float(host["loss"]),
        "grad_norm": float(host["grad_norm"]),
        "skipped_steps": int(host["skipped_steps"]),
        "grad_nonfinite": int(host["grad"]["nonfinite"]),
        "grad_norm_unscaled": float(host["grad"]["norm"]),
        "grad_max_abs": float(host["grad"]["max_abs"]),
        "param_norm": float(host["param"]["norm"]),
        "param_max_abs": float(host["param"]["max_abs"]),
        "param_nonfinite": int(host["param"]["nonfinite"]),
        "opt_nonfinite": int(host["opt_nonfinite"]),
    }
    ls = host.get("loss_scale")
    if ls is not None:
        rep["loss_scale"] = float(ls["cur_scale"])
        rep["loss_scale_growth_tracker"] = int(ls["growth_tracker"])
    layers: dict = {}
    first_layer = None
    al = host.get("act_layers")
    if al is not None:
        a = np.asarray(al, np.float64)
        layers["act_norm"] = [float(v) for v in a[:, 0]]
        layers["act_max_abs"] = [float(v) for v in a[:, 1]]
        layers["act_nonfinite"] = [int(v) for v in a[:, 2]]
        bad = np.nonzero(~np.isfinite(a[:, :2]).all(axis=1)
                         | (a[:, 2] > 0))[0]
        if bad.size:
            first_layer = int(bad[0])
    gl = host.get("grad_layers")
    if gl is not None:
        nf = np.asarray(gl["nonfinite"])
        layers["grad_norm"] = [float(v) for v in np.asarray(gl["norm"])]
        layers["grad_max_abs"] = [float(v)
                                  for v in np.asarray(gl["max_abs"])]
        layers["grad_nonfinite"] = [int(v) for v in nf]
        bad = np.nonzero(nf > 0)[0]
        if bad.size and first_layer is None:
            first_layer = int(bad[0])
    pl = host.get("param_layers")
    if pl is not None:
        layers["param_norm"] = [float(v) for v in np.asarray(pl["norm"])]
    if layers:
        rep["layers"] = layers
    if first_layer is not None:
        rep["first_nonfinite_layer"] = first_layer
    leaf_nf = host.get("grad_leaf_nonfinite") or {}
    bad_leaves = sorted(p for p, v in leaf_nf.items() if int(v) > 0)
    if bad_leaves:
        rep["first_nonfinite_leaf"] = bad_leaves[0]
        rep["nonfinite_leaves"] = bad_leaves[:16]
    ef = host.get("ef_residual")
    if ef:
        rep["ef_residual_norm"] = {str(k): float(v)
                                   for k, v in ef.items()}
    efb = host.get("ef_bucket")
    if efb:
        rep["ef_bucket_norm"] = {str(k): float(v)
                                 for k, v in efb.items()}
    return rep


# ----------------------------------------------------------- host sentinel
def _median(vals: Sequence[float]) -> float:
    s = sorted(vals)
    n = len(s)
    if not n:
        return 0.0
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def _json_safe(obj):
    if isinstance(obj, dict):
        return {str(k): _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return repr(obj)  # json.dump(allow_nan=False)-safe
    if isinstance(obj, (int, float, str, bool)) or obj is None:
        return obj
    try:
        f = float(obj)
    except (TypeError, ValueError):
        return str(obj)
    return _json_safe(f) if isinstance(f, float) else f


class NumericsLedger:
    """Anomaly sentinel + numerics accounting (host side, boundary
    cadence only).  The engine pulls the device stats tree at its
    ``steps_per_print`` boundary and feeds :meth:`observe_boundary`;
    everything here is plain Python on already-pulled values."""

    def __init__(self, config=None, registry: Optional[MetricsRegistry] = None):
        self.config = config
        reg = registry or get_registry()
        hist = int(getattr(config, "history", 64) or 64)
        self.min_history = max(2, int(getattr(config, "min_history", 8)))
        self.loss_spike_factor = float(getattr(config, "loss_spike_factor", 3.0))
        self.grad_spike_factor = float(getattr(config, "grad_spike_factor", 10.0))
        self.overflow_storm = int(getattr(config, "overflow_storm", 3))
        self.stagnant_boundaries = int(getattr(config, "stagnant_boundaries", 8))
        self.stagnant_tol = float(getattr(config, "stagnant_tol", 0.0))
        self._loss_hist: collections.deque = collections.deque(maxlen=hist)
        self._gnorm_hist: collections.deque = collections.deque(maxlen=hist)
        self._last_skipped: Optional[int] = None
        self._last_report: Optional[dict] = None
        self._last_anomalies: List[dict] = []
        self._pending_incident: Optional[dict] = None
        self.boundaries = 0
        self.anomaly_counts: Dict[str, int] = {}
        # --- deepspeed_tpu_train_numerics_* family (single owner: this
        # module; analysis/metric_lint.py pins it)
        self._m_anomalies = reg.counter(
            "deepspeed_tpu_train_numerics_anomalies_total",
            "Numerics-sentinel anomaly detections by kind",
            labelnames=("kind",))
        self._m_boundaries = reg.counter(
            "deepspeed_tpu_train_numerics_boundaries_total",
            "Numerics boundary observations (stats pulls)")
        self._m_nonfinite = reg.gauge(
            "deepspeed_tpu_train_numerics_grad_nonfinite_elems",
            "Nonfinite gradient elements at the last numerics boundary")
        self._m_gnorm_median = reg.gauge(
            "deepspeed_tpu_train_numerics_grad_norm_median",
            "Rolling-median global gradient norm (sentinel window)")
        self._m_div_failures = reg.counter(
            "deepspeed_tpu_train_numerics_divergence_failures_total",
            "Cross-data-rank divergence-audit failures")

    # ------------------------------------------------------------ detectors
    def _detect(self, report: dict) -> List[dict]:
        anomalies: List[dict] = []
        loss = report.get("loss")
        gnorm = report.get("grad_norm")
        nonfinite = int(report.get("grad_nonfinite") or 0)
        loss_bad = loss is not None and not math.isfinite(loss)
        if nonfinite > 0 or loss_bad:
            anomalies.append({
                "kind": "nonfinite",
                "nonfinite_elems": nonfinite,
                "loss": _json_safe(loss),
                "first_nonfinite_layer": report.get("first_nonfinite_layer"),
                "first_nonfinite_leaf": report.get("first_nonfinite_leaf"),
            })
        if (loss is not None and math.isfinite(loss)
                and len(self._loss_hist) >= self.min_history):
            med = _median(self._loss_hist)
            if med > 0 and loss > self.loss_spike_factor * med:
                anomalies.append({"kind": "loss_spike", "loss": loss,
                                  "rolling_median": med,
                                  "factor": loss / med})
        if (gnorm is not None and math.isfinite(gnorm)
                and len(self._gnorm_hist) >= self.min_history):
            med = _median(self._gnorm_hist)
            if med > 0 and gnorm > self.grad_spike_factor * med:
                anomalies.append({"kind": "grad_spike", "grad_norm": gnorm,
                                  "rolling_median": med,
                                  "factor": gnorm / med})
        skipped = report.get("skipped_steps")
        if skipped is not None and self._last_skipped is not None:
            delta = int(skipped) - self._last_skipped
            if delta >= max(1, self.overflow_storm):
                anomalies.append({"kind": "overflow_storm",
                                  "skipped_since_last_boundary": delta,
                                  "loss_scale": report.get("loss_scale")})
        if (self.stagnant_boundaries > 0 and loss is not None
                and math.isfinite(loss)):
            recent = list(self._loss_hist)[-(self.stagnant_boundaries - 1):] \
                + [loss]
            if (len(recent) >= self.stagnant_boundaries
                    and max(recent) - min(recent) <= self.stagnant_tol):
                anomalies.append({"kind": "stagnant_loss",
                                  "boundaries": len(recent),
                                  "loss": loss,
                                  "tolerance": self.stagnant_tol})
        div = report.get("divergence")
        if div is not None and not div.get("ok", True):
            self._m_div_failures.inc()
            anomalies.append({
                "kind": "divergence",
                "first_diverging_leaf": div.get("first_diverging_leaf"),
                "diverging": list(div.get("diverging") or [])[:16],
                "ranks": div.get("ranks"),
            })
        return anomalies

    # ------------------------------------------------------------- observe
    def observe_boundary(self, report: dict) -> List[dict]:
        """Fold one boundary report, run the detectors, fire the flight
        dump + metrics on anomaly.  Returns the anomaly list (empty =
        healthy boundary)."""
        self.boundaries += 1
        self._m_boundaries.inc()
        anomalies = self._detect(report)
        loss, gnorm = report.get("loss"), report.get("grad_norm")
        # spikes are judged against the HEALTHY window: fold after
        # detection, and never fold nonfinite values (they would poison
        # every later median)
        if loss is not None and math.isfinite(loss):
            self._loss_hist.append(float(loss))
        if gnorm is not None and math.isfinite(gnorm):
            self._gnorm_hist.append(float(gnorm))
        skipped = report.get("skipped_steps")
        if skipped is not None:
            self._last_skipped = int(skipped)
        self._m_nonfinite.set(float(report.get("grad_nonfinite") or 0))
        if self._gnorm_hist:
            self._m_gnorm_median.set(_median(self._gnorm_hist))
        self._last_report = _json_safe(report)
        self._last_anomalies = _json_safe(anomalies)
        if anomalies:
            for a in anomalies:
                kind = a["kind"]
                self._m_anomalies.inc(kind=kind)
                self.anomaly_counts[kind] = self.anomaly_counts.get(kind, 0) + 1
            self._record_incident(report, anomalies)
            self._fire_dump(report, anomalies)
        return anomalies

    def _record_incident(self, report: dict, anomalies: List[dict]) -> None:
        """Pending incident for the NEXT checkpoint commit: stamped into
        the tag's manifest meta by ``checkpoint/saving.py`` so
        resume-time triage (``resilience/commit.py`` manifest readers)
        sees what went wrong and when."""
        self._pending_incident = _json_safe({
            "step": report.get("step"),
            "kinds": [a["kind"] for a in anomalies],
            "anomalies": anomalies,
        })

    def _fire_dump(self, report: dict, anomalies: List[dict]) -> None:
        """ONE flight dump per anomalous boundary, carrying the full
        per-layer breakdown (the dump's numerics record also rides every
        OTHER dump via :func:`last_numerics_summary`)."""
        try:
            from .flight import get_flight_recorder

            fr = get_flight_recorder()
            if fr is None:
                return
            fr.note("numerics_anomaly", step=report.get("step"),
                    kinds=[a["kind"] for a in anomalies])
            fr.dump(reason=f"numerics:{anomalies[0]['kind']}")
        # dstpu-lint: allow[swallow] the sentinel must never turn an
        # anomaly report into a training crash; the metrics still count
        except Exception:
            pass

    # ------------------------------------------------------------- readout
    def pending_incident(self) -> Optional[dict]:
        return self._pending_incident

    def consume_incident(self) -> Optional[dict]:
        """Pop the pending incident (one incident annotates ONE
        checkpoint tag; a later clean save must not re-stamp it)."""
        inc, self._pending_incident = self._pending_incident, None
        return inc

    def summary(self) -> dict:
        """JSON-safe snapshot for flight dumps and tools."""
        return {
            "boundaries": self.boundaries,
            "anomaly_counts": dict(self.anomaly_counts),
            "grad_norm_median": (_median(self._gnorm_hist)
                                 if self._gnorm_hist else None),
            "loss_median": (_median(self._loss_hist)
                            if self._loss_hist else None),
            "last_report": self._last_report,
            "last_anomalies": self._last_anomalies,
            "pending_incident": self._pending_incident,
        }

    # ------------------------------------------------- checkpoint round-trip
    def state_dict(self) -> dict:
        """Sentinel state for checkpoint client_state: the rolling
        windows and incident bookkeeping survive preemption-resume, so
        a spike right after restore is still judged against the real
        history (and a pre-crash incident is not lost)."""
        return _json_safe({
            "loss_hist": list(self._loss_hist),
            "gnorm_hist": list(self._gnorm_hist),
            "last_skipped": self._last_skipped,
            "boundaries": self.boundaries,
            "anomaly_counts": dict(self.anomaly_counts),
            "pending_incident": self._pending_incident,
        })

    def load_state_dict(self, state: Optional[dict]) -> None:
        if not state:
            return
        self._loss_hist.clear()
        self._loss_hist.extend(float(v) for v in state.get("loss_hist", []))
        self._gnorm_hist.clear()
        self._gnorm_hist.extend(float(v) for v in state.get("gnorm_hist", []))
        ls = state.get("last_skipped")
        self._last_skipped = None if ls is None else int(ls)
        self.boundaries = int(state.get("boundaries", 0))
        self.anomaly_counts = {str(k): int(v) for k, v in
                               (state.get("anomaly_counts") or {}).items()}
        self._pending_incident = state.get("pending_incident")


# ------------------------------------------------------- process default
_LEDGER: Optional[NumericsLedger] = None


def set_numerics_ledger(ledger: Optional[NumericsLedger]) -> None:
    global _LEDGER
    _LEDGER = ledger


def get_numerics_ledger() -> Optional[NumericsLedger]:
    return _LEDGER


def last_numerics_summary() -> Optional[dict]:
    """The numerics record every flight dump carries (same contract as
    ``last_goodput_summary`` / ``last_reqtrace_summary``): None when no
    ledger is live or nothing has been observed yet."""
    if _LEDGER is None or not _LEDGER.boundaries:
        return None
    return _LEDGER.summary()


def pending_incident_meta() -> Optional[dict]:
    """Consume the pending anomaly incident for a checkpoint commit's
    manifest meta (``checkpoint/saving.py``).  None when healthy."""
    if _LEDGER is None:
        return None
    inc = _LEDGER.consume_incident()
    if inc is None:
        return None
    # manifest meta is json.dump'd with default=str; make it round-trip
    return json.loads(json.dumps(inc, default=str))
