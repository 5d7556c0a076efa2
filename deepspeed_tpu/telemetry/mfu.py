"""Model-FLOPs-utilization accounting.

One per-generation TPU peak-FLOPs table (dense bf16, per chip) behind
the telemetry gauges.  Sources: published TPU specs (v4 275T, v5e 197T,
v5p 459T, v6e "Trillium" 918T bf16).

The CPU entry is a nominal 1 TFLOP/s so the test tier's gauges stay
non-zero and clearly-not-a-chip.  A device kind that is not in the table is an error, never a default: an
assumed peak makes every utilization number derived from it fiction.
"""

from __future__ import annotations

from typing import Optional

#: per-chip peak dense-bf16 FLOP/s, keyed by device_kind substring
#: (matched case-insensitively, first hit wins — order specific to
#: generic)
PEAK_BF16_FLOPS = {
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU v4": 275e12,
    "TPU v3": 123e12,
    "TPU v2": 45e12,
    "cpu": 1e12,  # nominal, so CPU runs still report something
}


def peak_flops_for_kind(device_kind: str) -> float:
    """Peak FLOP/s for a device-kind string."""
    kind = str(device_kind).lower()
    for name, peak in PEAK_BF16_FLOPS.items():
        if name.lower() in kind:
            return peak
    raise ValueError(
        f"no peak-FLOPs entry for device kind {device_kind!r}; add its row "
        f"to PEAK_BF16_FLOPS (known: {sorted(PEAK_BF16_FLOPS)})")


def peak_flops_for_device(device=None) -> float:
    """Peak FLOP/s for a jax device (default: the first local device)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return peak_flops_for_kind(getattr(device, "device_kind", "cpu"))


def mfu(model_flops: float, elapsed_s: float, n_chips: int = 1,
        device=None, peak_flops: Optional[float] = None) -> float:
    """Model FLOPs utilization: useful-model FLOPs over what ``n_chips``
    could have done in ``elapsed_s`` at peak.  ``model_flops`` must be
    the MODEL cost (e.g. ``6*N + attn`` per token for training, or the
    XLA cost analysis of the step program), not hardware-counter FLOPs —
    rematerialization must not inflate the number."""
    if elapsed_s <= 0 or n_chips <= 0:
        return 0.0
    peak = peak_flops if peak_flops is not None else peak_flops_for_device(device)
    if peak <= 0:
        return 0.0
    return float(model_flops) / elapsed_s / (n_chips * peak)
