"""Accelerator abstraction.

TPU-native counterpart of the reference's ``DeepSpeedAccelerator`` ABC
(reference: accelerator/abstract_accelerator.py:10) and runtime detection
(accelerator/real_accelerator.py:51).  Every device touch in the framework
goes through ``get_accelerator()``.

The reference exposes ~90 torch-device methods (streams, events, memory
stats, RNG, graph capture, op-builder dispatch).  On TPU under JAX most of
those concepts collapse into XLA's execution model, so the surface here is
the subset that has real meaning — but kept name-compatible where it exists:

- streams/events     → XLA owns scheduling; ``synchronize`` blocks on all
                       outstanding device work (``Stream``/``Event`` are
                       provided as no-op shims so engine code stays uniform).
- memory stats       → ``jax.Device.memory_stats()`` (live HBM numbers).
- RNG                → functional ``jax.random`` keys; the seed API stores
                       the key used to derive per-module streams.
- graph capture      → ``jax.jit`` (always-on); ``device_supports_graphs``
                       is therefore True.
- op builders        → dispatches into ops/op_builder.py (C++ host ops) —
                       same "builder registry keyed by accelerator" shape as
                       the reference's ``create_op_builder`` indirection
                       (op_builder/builder.py:116).

Detection order (mirrors real_accelerator.py:59): explicit ``DS_ACCELERATOR``
env var, else the platform JAX selected (``utils/platform.py``).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional

__all__ = [
    "Accelerator",
    "TPUAccelerator",
    "CPUAccelerator",
    "get_accelerator",
    "set_accelerator",
]


class _NoOpStream:
    """Shim for torch-style stream APIs; XLA schedules asynchronously itself."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def synchronize(self) -> None:
        get_accelerator().synchronize()

    def wait_stream(self, other) -> None:  # noqa: ARG002
        pass


class _NoOpEvent:
    def record(self, stream=None) -> None:  # noqa: ARG002
        pass

    def synchronize(self) -> None:
        get_accelerator().synchronize()

    def wait(self, stream=None) -> None:  # noqa: ARG002
        pass

    def elapsed_time(self, other) -> float:  # noqa: ARG002
        return 0.0


class Accelerator:
    """Base accelerator: the name-compatible subset of the reference ABI."""

    _name = "cpu"
    _communication_backend = "xla"

    # --- identity -------------------------------------------------------
    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def is_available(self) -> bool:
        return len(self._devices()) > 0

    def device_count(self) -> int:
        return len(self._devices())

    def _devices(self) -> List[Any]:
        import jax

        try:
            return [d for d in jax.devices() if d.platform == self._name]
        except RuntimeError:
            return []

    def current_device(self) -> int:
        return 0

    def current_device_name(self) -> str:
        return self.device_name(self.current_device())

    def set_device(self, device_index: int) -> None:  # noqa: ARG002
        # JAX places arrays explicitly via shardings; no thread-local device.
        pass

    # --- execution ------------------------------------------------------
    def synchronize(self, device_index: Optional[int] = None) -> None:
        import jax

        # The analogue of torch.cuda.synchronize(): enqueue a trivial op on
        # each target device's stream and block on it, ordering behind all
        # previously dispatched work on that device.
        devs = self._devices()
        if device_index is not None and devs:
            devs = [devs[device_index]]
        for d in devs:
            jax.device_put(0, d).block_until_ready()

    def Stream(self, *a, **k) -> _NoOpStream:  # noqa: N802, ARG002
        return _NoOpStream()

    def stream(self, stream) -> _NoOpStream:  # noqa: ARG002
        return _NoOpStream()

    def current_stream(self, device_index=None) -> _NoOpStream:  # noqa: ARG002
        return _NoOpStream()

    def default_stream(self, device_index=None) -> _NoOpStream:  # noqa: ARG002
        return _NoOpStream()

    def Event(self, *a, **k) -> _NoOpEvent:  # noqa: N802, ARG002
        return _NoOpEvent()

    # --- graphs (reference: abstract_accelerator.py graph-capture API) --
    def device_supports_graphs(self) -> bool:
        # Everything under jit is a captured/compiled graph on XLA.
        return True

    # --- RNG ------------------------------------------------------------
    def manual_seed(self, seed: int) -> None:
        self._seed = int(seed)

    def initial_seed(self) -> int:
        return getattr(self, "_seed", 0)

    def default_generator(self, device_index: int = 0):  # noqa: ARG002
        import jax

        return jax.random.PRNGKey(self.initial_seed())

    # --- memory ---------------------------------------------------------
    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:
        devs = self._devices()
        if not devs:
            return {}
        d = devs[device_index or 0]
        try:
            return dict(d.memory_stats() or {})
        except Exception:
            return {}

    def aggregate_memory_stats(self) -> Dict[str, int]:
        """Memory stats summed across every addressable device of this
        process — the process-level HBM view the memory ledger
        (telemetry/memory.py) attributes against.  Per-key numeric sum:
        ``bytes_in_use`` and ``bytes_limit`` add naturally; the summed
        per-device peaks are an upper bound on any instant's total (the
        devices need not have peaked together)."""
        out: Dict[str, int] = {}
        for d in self._devices():
            try:
                s = d.memory_stats() or {}
            # dstpu-lint: allow[swallow] a device without stats support just
            # drops out of the aggregate; the others still report
            except Exception:
                continue
            for k, v in s.items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0) + int(v)
        return out

    def memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get("peak_bytes_in_use", 0))

    def reset_peak_memory_stats(self, device_index: Optional[int] = None) -> None:  # noqa: ARG002
        pass  # XLA exposes peak stats read-only

    def total_memory(self, device_index: Optional[int] = None) -> int:
        return int(self.memory_stats(device_index).get("bytes_limit", 0))

    def available_memory(self, device_index: Optional[int] = None) -> int:
        s = self.memory_stats(device_index)
        return max(0, int(s.get("bytes_limit", 0)) - int(s.get("bytes_in_use", 0)))

    def empty_cache(self) -> None:
        pass

    # --- dtype support --------------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def is_fp8_supported(self) -> bool:
        return False

    def supported_dtypes(self) -> List[Any]:
        import jax.numpy as jnp

        out = [jnp.float32, jnp.bfloat16, jnp.float16]
        if self.is_fp8_supported():
            out += [jnp.float8_e4m3fn, jnp.float8_e5m2]
        return out

    # --- comm / ops -----------------------------------------------------
    def communication_backend_name(self) -> str:
        # reference: abstract_accelerator.py:202 — picks nccl/ccl/gloo; here
        # all collectives lower to XLA ops over ICI/DCN.
        return self._communication_backend

    def create_op_builder(self, name: str):
        from ..ops.op_builder import get_builder

        return get_builder(name)

    def get_op_builder(self, name: str):
        from ..ops.op_builder import get_builder

        return type(get_builder(name))

    # --- misc -----------------------------------------------------------
    def range_push(self, msg: str) -> None:
        try:
            import jax.profiler as _p

            ann = _p.TraceAnnotation(msg)
            ann.__enter__()
        except Exception:
            return  # keep push/pop stack aligned: only entered ranges count
        self._ranges = getattr(self, "_ranges", [])
        self._ranges.append(ann)

    def range_pop(self) -> None:
        ranges = getattr(self, "_ranges", [])
        if ranges:
            try:
                ranges.pop().__exit__(None, None, None)
            # dstpu-lint: allow[swallow] best-effort exit of a foreign
            # profiler range; an already-closed range must not raise here
            except Exception:
                pass

    def lazy_call(self, callback) -> None:
        callback()

    def communication_backend_version(self) -> str:
        import jax

        return jax.__version__

    def handles_memory_backpressure(self) -> bool:
        return False

    def visible_devices_envs(self) -> List[str]:
        return ["JAX_PLATFORMS", "TPU_VISIBLE_DEVICES"]


class TPUAccelerator(Accelerator):
    _name = "tpu"
    _communication_backend = "xla:ici"

    def is_fp8_supported(self) -> bool:
        # v5p/v6e native fp8; older gens emulate. Report by device kind.
        devs = self._devices()
        kind = str(getattr(devs[0], "device_kind", "")).lower() if devs else ""
        return any(k in kind for k in ("v5p", "v6", "v7"))

    def device_kind(self) -> str:
        devs = self._devices()
        return str(getattr(devs[0], "device_kind", "tpu")) if devs else "tpu"


class CPUAccelerator(Accelerator):
    """Host-simulation accelerator (the CI mode — the reference's Gloo-on-CPU
    analogue, see SURVEY §4)."""

    _name = "cpu"
    _communication_backend = "xla:host"

    def aggregate_memory_stats(self) -> Dict[str, int]:
        """Virtual CPU devices share one process RSS: summing the
        per-device view would multiply it by the device count."""
        return self.memory_stats()

    def memory_stats(self, device_index: Optional[int] = None) -> Dict[str, int]:  # noqa: ARG002
        import sys

        stats: Dict[str, int] = {}
        try:
            import resource

            peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
            # ru_maxrss is KiB on Linux, bytes on macOS
            stats["peak_bytes_in_use"] = peak if sys.platform == "darwin" else peak * 1024
        # dstpu-lint: allow[swallow] resource-module RSS probe is optional;
        # the stats dict stays partial rather than failing the caller
        except Exception:
            pass
        try:
            with open("/proc/self/statm") as f:
                rss_pages = int(f.read().split()[1])
            stats["bytes_in_use"] = rss_pages * os.sysconf("SC_PAGE_SIZE")
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemTotal:"):
                        stats["bytes_limit"] = int(line.split()[1]) * 1024
                        break
        except Exception:
            stats.setdefault("bytes_in_use", stats.get("peak_bytes_in_use", 0))
        return stats


_lock = threading.Lock()
_accelerator: Optional[Accelerator] = None


def get_accelerator() -> Accelerator:
    """Detect and cache the accelerator (reference: real_accelerator.py:51)."""
    global _accelerator
    if _accelerator is not None:
        return _accelerator
    with _lock:
        if _accelerator is not None:
            return _accelerator
        name = os.environ.get("DS_ACCELERATOR", "").lower()
        if name not in ("", "tpu", "cpu"):
            raise ValueError(f"DS_ACCELERATOR={name!r}: expected tpu or cpu")
        if not name:
            # a backend that fails to initialize propagates: turning it
            # into CPUAccelerator would hide a missing chip
            from ..utils.platform import on_tpu

            name = "tpu" if on_tpu() else "cpu"
        _accelerator = TPUAccelerator() if name == "tpu" else CPUAccelerator()
        return _accelerator


def set_accelerator(acc: Accelerator) -> None:
    global _accelerator
    with _lock:
        _accelerator = acc
