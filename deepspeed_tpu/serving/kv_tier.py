"""Tiered KV cache: host-RAM spill & restore of cold prefix pages.

The device prefix cache (PR 1, ``inference/v2/ragged.py``) turns shared
prompt prefixes into page-table lookups — until the distinct-prefix
working set outgrows the pool slice spared for cached KV and the LRU
starts evicting pages that will be needed again.  This module adds the
hierarchical-memory move the reference framework applies to training
state (ZeRO-Offload/Infinity: host RAM as the second tier): a
:class:`HostKVTier` captures pages on prefix-cache LRU eviction into a
**byte-budgeted host LRU** keyed by the PR 1 content-hash chain keys,
and the engine restores them — CRC-verified, bit-identical — when a
later request's prefix walks past the device hit.

State machine of one cached page::

    device (LRU-parked) --evict+capture--> spilling (ref-pinned)
        --D2H commit--> host (byte-budgeted LRU)
        --prefix walk hits--> restoring (H2D scatter)
        --register+park--> device (LRU-parked)

Contracts:

* **One serialization path** — capture uses ``model_runner.
  paged_gather_pages``'s exact-dtype page layout and stamps
  ``kv_transfer.page_crcs`` (the wire format's checksum rule); restore
  recomputes the CRC and REFUSES mismatches loudly (corrupt page
  dropped, counter bumped, the chain treated as a miss) — device state
  loses nothing on refusal, the engine simply prefills the suffix.
* **Pool dtype** — under ``kv_quant`` the tier stores int8 codes +
  fp32 scales directly (no dequant round trip, ~4x more pages per host
  byte); restore is bit-identical to a never-evicted page.
* **Async, off the hot path** — eviction only *queues* a capture
  (bounded by ``kv_tier.spill_inflight``; the page is pinned via
  refcount so eviction never races a live reader), the D2H copies
  drain in ONE batched gather at the next step boundary, and restores
  for queued-but-not-admitted requests prefetch while the current
  batch decodes.

The engine side (capture hook, drain, restore, prefetch) lives in
``inference/v2/engine_v2.py``; this module owns the host LRU, the
integrity rule, and the ``deepspeed_tpu_serving_kv_tier_*`` metric
family (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import hashlib
import os
import tempfile
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..telemetry import get_registry
from ..telemetry.spans import record_event
from ..utils.logging import logger
from .config import KVTierConfig  # noqa: F401  (re-export: the block's home)
from .kv_transfer import (CorruptBundleError, bundle_from_bytes,
                          bundle_to_bytes, page_crcs, pages_from_bytes,
                          pages_to_bytes)


class NVMeKVTier:
    """File-backed third tier under the host LRU: pages evicted from
    host RAM demote to one DSTPUKV2 page record per file (the wire
    format's exact serialization — :func:`~.kv_transfer.pages_to_bytes`
    — so the on-disk layout, dtype carriage, and per-page CRC rule are
    the SAME as the cross-process wire; the reference framework's
    swap_tensor/AIO tier is the blueprint).  A host miss consults the
    files: read, CRC-verified, promoted back — bit-identical or refused
    loudly.  Byte-budgeted LRU over file sizes; writes are atomic
    (tmp + rename) so a torn write can never be half-read as a page.

    Whole bundles can also sit spilled (:meth:`spill_bundle` /
    :meth:`restore_bundle`, riding ``bundle_to_bytes`` /
    ``bundle_from_bytes``): restore re-bases ``deadline_left_s``
    through the SAME transit clamp as the wire import
    (``kv_transfer.rebase_deadline_left``) — time spent spilled
    consumes the deadline budget, and clock skew never grants it back.
    """

    def __init__(self, config: Optional[KVTierConfig] = None):
        self.config = config or KVTierConfig(enabled=True, nvme_enabled=True)
        self.dir = self.config.nvme_dir or tempfile.mkdtemp(
            prefix="dstpu_kv_nvme_")
        os.makedirs(self.dir, exist_ok=True)
        self._lru: "OrderedDict[Any, Tuple[str, int]]" = OrderedDict()
        self._bytes = 0
        self.spilled_pages = 0
        self.restored_pages = 0
        self.evicted_pages = 0
        self.corrupt_pages = 0
        self.misses = 0
        self._init_metrics()

    def _init_metrics(self) -> None:
        reg = get_registry()
        self._m_spilled = reg.counter(
            "deepspeed_tpu_serving_kv_nvme_spilled_pages_total",
            "pages demoted from the host LRU to NVMe page files "
            "(DSTPUKV2 records, atomic tmp+rename writes)")
        self._m_restored = reg.counter(
            "deepspeed_tpu_serving_kv_nvme_restored_pages_total",
            "NVMe page files promoted back to the host tier "
            "(CRC-verified on read, bit-identical)")
        self._m_bytes = reg.gauge(
            "deepspeed_tpu_serving_kv_nvme_bytes",
            "bytes of KV page files on disk (byte-budgeted LRU)")
        self._m_evicted = reg.counter(
            "deepspeed_tpu_serving_kv_nvme_evicted_pages_total",
            "page files unlinked from the NVMe LRU to hold the byte "
            "budget (the tier's floor: past it, pages are recomputed)")
        self._m_corrupt = reg.counter(
            "deepspeed_tpu_serving_kv_nvme_corrupt_pages_total",
            "page files refusing restore on CRC mismatch or torn read "
            "(file unlinked; the walk treats the page as a miss)")
        self._m_miss = reg.counter(
            "deepspeed_tpu_serving_kv_nvme_misses_total",
            "restore walks that consulted the NVMe tier for a page it "
            "does not hold")
        self._m_hit_rate = reg.gauge(
            "deepspeed_tpu_serving_kv_nvme_hit_rate",
            "cumulative NVMe promotes / (promotes + NVMe misses)")

    def _publish(self) -> None:
        self._m_bytes.set(self._bytes)
        looked = self.restored_pages + self.misses
        if looked:
            self._m_hit_rate.set(self.restored_pages / looked)

    @staticmethod
    def _key_name(key: Any) -> str:
        if isinstance(key, bytes):
            return key.hex()
        return hashlib.sha256(repr(key).encode()).hexdigest()

    def _path(self, key: Any) -> str:
        return os.path.join(self.dir, self._key_name(key) + ".kvpage")

    def _write_atomic(self, path: str, blob: bytes) -> None:
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)

    @property
    def nvme_bytes(self) -> int:
        return self._bytes

    @property
    def nvme_pages(self) -> int:
        return len(self._lru)

    @property
    def hit_rate(self) -> float:
        looked = self.restored_pages + self.misses
        return self.restored_pages / looked if looked else 0.0

    def has(self, key: Any) -> bool:
        return key in self._lru

    def put(self, key: Any, arrays: Dict[str, np.ndarray]) -> bool:
        """Demote one page to disk (DSTPUKV2 record, atomic write),
        then unlink oldest files past the byte budget.  Returns False —
        nothing written — when the single record exceeds the whole
        budget."""
        blob = pages_to_bytes(arrays, {"tier": "nvme",
                                       "key": self._key_name(key)})
        if len(blob) > self.config.nvme_bytes:
            logger.warning(
                f"kv_nvme: one page record ({len(blob)} B) exceeds the "
                f"NVMe byte budget ({self.config.nvme_bytes} B); dropped")
            return False
        path = self._path(key)
        old = self._lru.pop(key, None)
        if old is not None:
            self._bytes -= old[1]
        self._write_atomic(path, blob)
        self._lru[key] = (path, len(blob))
        self._bytes += len(blob)
        self.spilled_pages += 1
        self._m_spilled.inc()
        while self._bytes > self.config.nvme_bytes:
            _, (p, nb) = self._lru.popitem(last=False)
            self._unlink(p)
            self._bytes -= nb
            self.evicted_pages += 1
            self._m_evicted.inc()
        self._publish()
        record_event("kv_nvme_demote", cat="serve",
                     nvme_pages=self.nvme_pages, nvme_bytes=self._bytes)
        return True

    def get(self, key: Any) -> Optional[Dict[str, np.ndarray]]:
        """CRC-verified read for promotion: the page's arrays
        (bit-identical to what was demoted) or None — on a genuine
        miss (counted), or LOUDLY on a corrupt/torn file, which is
        unlinked so the walk treats the page as a miss (refusal loses
        nothing; the device recomputes the suffix)."""
        entry = self._lru.get(key)
        if entry is None:
            self.misses += 1
            self._m_miss.inc()
            self._publish()
            return None
        path, nb = entry
        try:
            with open(path, "rb") as f:
                blob = f.read()
            arrays, _header = pages_from_bytes(blob)
        except (OSError, CorruptBundleError) as e:
            self._lru.pop(key, None)
            self._bytes -= nb
            self.corrupt_pages += 1
            self._m_corrupt.inc()
            self._unlink(path)
            self._publish()
            logger.error(
                f"kv_nvme: REFUSING promote of page {self._key_name(key)[:16]}"
                f"…: {e}; file dropped — the device recomputes the suffix, "
                "nothing is lost")
            return None
        self._lru.move_to_end(key)
        self.restored_pages += 1
        self._m_restored.inc()
        self._publish()
        record_event("kv_nvme_promote", cat="serve",
                     nvme_pages=self.nvme_pages)
        return arrays

    def pop(self, key: Any) -> None:
        """Drop one entry (promotion to host moved ownership up-tier)."""
        entry = self._lru.pop(key, None)
        if entry is not None:
            self._bytes -= entry[1]
            self._unlink(entry[0])
            self._publish()

    @staticmethod
    def _unlink(path: str) -> None:
        try:
            os.unlink(path)
        except OSError:
            pass

    # -- whole-bundle spill (sequence-level, not page-level) -----------------
    def spill_bundle(self, bundle: Any) -> str:
        """Park a whole exported sequence on disk (``bundle_to_bytes``
        — the serializer stamps ``sent_unix``/``deadline_left_s``, so
        the spilled record carries its SLO identity)."""
        path = os.path.join(self.dir, f"seq_{bundle.uid}.kvbundle")
        self._write_atomic(path, bundle_to_bytes(bundle))
        return path

    def restore_bundle(self, path: str) -> Any:
        """Re-hydrate a spilled sequence.  ``bundle_from_bytes`` runs
        the full wire-import integrity pass (per-page CRCs) AND re-bases
        ``deadline_left_s`` through ``rebase_deadline_left`` — the time
        the bundle sat spilled consumes its deadline budget exactly as
        wire transit would (a page that sat on NVMe gets no free
        deadline).  Raises :class:`CorruptBundleError` naming the page
        on a torn or bit-flipped file."""
        with open(path, "rb") as f:
            return bundle_from_bytes(f.read())

    def stats(self) -> Dict[str, float]:
        return {"nvme_spilled_pages": self.spilled_pages,
                "nvme_restored_pages": self.restored_pages,
                "nvme_pages": self.nvme_pages,
                "nvme_bytes": self._bytes,
                "nvme_evictions": self.evicted_pages,
                "nvme_corrupt_pages": self.corrupt_pages,
                "nvme_misses": self.misses,
                "nvme_hit_rate": self.hit_rate}


class HostKVTier:
    """Byte-budgeted host LRU of spilled KV pages, keyed by the prefix
    cache's content-hash chain keys.

    One entry per page: ``{leaf: np.ndarray[L, 1, page_size, KVH, D]}``
    in the pool's exact dtype (the ``paged_gather_pages`` layout) plus
    the capture-time CRC32.  Pure host state — safe to consult from the
    admission path; the only device work (gather/scatter) stays in the
    engine."""

    def __init__(self, config: Optional[KVTierConfig] = None):
        self.config = config or KVTierConfig(enabled=True)
        self._lru: "OrderedDict[Any, Tuple[Dict[str, np.ndarray], int, int]]" \
            = OrderedDict()  # key -> (arrays, crc, nbytes); oldest first
        self._bytes = 0
        # cumulative counters (mirrored onto the registry family below;
        # these stay the per-tier source of truth for stats())
        self.spilled_pages = 0
        self.restored_pages = 0
        self.host_evictions = 0
        self.corrupt_pages = 0
        self.dropped_spills = 0
        self.hits = 0    # pages served from the host tier (on restore)
        self.misses = 0  # restore walks that ended on a page not held
        #: optional NVMe third tier: host-LRU evictions demote to page
        #: files instead of being dropped, and a host miss consults the
        #: files (promote-on-hit) before declaring a true miss
        self.nvme: Optional[NVMeKVTier] = (
            NVMeKVTier(self.config) if self.config.nvme_enabled else None)
        self._init_metrics()

    # -- telemetry -----------------------------------------------------------
    def _init_metrics(self) -> None:
        reg = get_registry()
        self._m_spilled = reg.counter(
            "deepspeed_tpu_serving_kv_tier_spilled_pages_total",
            "prefix-cache pages captured into the host tier on LRU "
            "eviction (D2H commit counted, not queueing)")
        self._m_restored = reg.counter(
            "deepspeed_tpu_serving_kv_tier_restored_pages_total",
            "host-tier pages restored into the device pool (H2D, "
            "CRC-verified bit-identical)")
        self._m_host_bytes = reg.gauge(
            "deepspeed_tpu_serving_kv_tier_host_bytes",
            "host RAM held by spilled KV pages (byte-budgeted LRU)")
        self._m_hit_rate = reg.gauge(
            "deepspeed_tpu_serving_kv_tier_hit_rate",
            "cumulative restored pages / (restored + restore walks that "
            "missed)")
        self._m_restore_h = reg.histogram(
            "deepspeed_tpu_serving_kv_tier_restore_seconds",
            "one batched host->device restore (H2D scatter + CRC "
            "verification) wall time")
        self._m_host_evict = reg.counter(
            "deepspeed_tpu_serving_kv_tier_host_evicted_pages_total",
            "spilled pages dropped from the host LRU to hold the byte "
            "budget")
        self._m_corrupt = reg.counter(
            "deepspeed_tpu_serving_kv_tier_corrupt_pages_total",
            "host-tier pages refusing restore on CRC mismatch (entry "
            "dropped; the device treats the page as a miss)")
        self._m_dropped = reg.counter(
            "deepspeed_tpu_serving_kv_tier_dropped_spills_total",
            "evictions whose spill was refused: the bounded in-flight "
            "queue was full, or a single page exceeded the whole host "
            "byte budget (the device never blocks on the tier either "
            "way)")

    def _publish(self) -> None:
        self._m_host_bytes.set(self._bytes)
        looked = self.restored_pages + self.misses
        if looked:
            self._m_hit_rate.set(self.restored_pages / looked)

    # -- the host LRU --------------------------------------------------------
    @property
    def host_bytes(self) -> int:
        return self._bytes

    @property
    def host_pages(self) -> int:
        return len(self._lru)

    @property
    def hit_rate(self) -> float:
        looked = self.restored_pages + self.misses
        return self.restored_pages / looked if looked else 0.0

    def has(self, key: Any) -> bool:
        """Membership without touching recency — the prefix walk's
        cheap consult (``PrefixCache.host_extend``).  Consults the NVMe
        tier too (dict membership, no file I/O): a demoted page is
        still a tier hit, it just costs a disk read at restore."""
        if key in self._lru:
            return True
        return self.nvme is not None and self.nvme.has(key)

    def insert(self, key: Any, arrays: Dict[str, np.ndarray],
               crc: int) -> bool:
        """Commit one captured page (the D2H copy already happened —
        ``arrays`` are host arrays in the pool's exact dtype).  Inserts
        at the MRU end, then evicts oldest entries past the byte
        budget.  Returns False — nothing stored — when the single page
        exceeds the whole budget."""
        nbytes = sum(a.nbytes for a in arrays.values())
        if nbytes > self.config.host_bytes:
            self.dropped_spills += 1
            self._m_dropped.inc()
            logger.warning(
                f"kv_tier: one page ({nbytes} B) exceeds the host byte "
                f"budget ({self.config.host_bytes} B); not spilled")
            return False
        old = self._lru.pop(key, None)
        if old is not None:
            self._bytes -= old[2]
        self._lru[key] = (arrays, int(crc) & 0xFFFFFFFF, nbytes)
        self._bytes += nbytes
        self.spilled_pages += 1
        self._m_spilled.inc()
        while self._bytes > self.config.host_bytes:
            k, (arrs, _, nb) = self._lru.popitem(last=False)
            self._bytes -= nb
            self.host_evictions += 1
            self._m_host_evict.inc()
            if self.nvme is not None:
                # demote instead of drop: the page's next stop is a
                # DSTPUKV2 file record, not recomputation
                self.nvme.put(k, arrs)
        self._publish()
        return True

    def get(self, key: Any) -> Optional[Dict[str, np.ndarray]]:
        """CRC-verified fetch for restore: returns the page's arrays
        (recency refreshed) or None — on a genuine miss, or LOUDLY on a
        CRC mismatch, where the corrupt entry is dropped so the walk
        treats the page as a miss and the device prefills the suffix
        instead (refusal loses nothing).  A host miss consults the NVMe
        tier (CRC-verified file read) and promotes a hit back into the
        host LRU — ownership moves up-tier, the file is dropped."""
        entry = self._lru.get(key)
        if entry is None:
            if self.nvme is None:
                return None
            arrays = self.nvme.get(key)
            if arrays is None:
                return None
            # promote: the page re-enters the host LRU at the MRU end
            # with its freshly verified CRC (pages_from_bytes already
            # refused any mismatch), and the file goes away
            crc = page_crcs(arrays, sorted(arrays))[0]
            self.nvme.pop(key)
            nbytes = sum(a.nbytes for a in arrays.values())
            if nbytes <= self.config.host_bytes:
                self._lru[key] = (arrays, crc, nbytes)
                self._bytes += nbytes
                while self._bytes > self.config.host_bytes:
                    k, (arrs, _, nb) = self._lru.popitem(last=False)
                    self._bytes -= nb
                    self.host_evictions += 1
                    self._m_host_evict.inc()
                    if k != key:  # never demote the page being served
                        self.nvme.put(k, arrs)
                self._publish()
            return arrays
        arrays, crc, _nbytes = entry
        got = page_crcs(arrays, sorted(arrays))[0]
        if got != crc:
            self._drop_corrupt(key, crc, got)
            return None
        self._lru.move_to_end(key)
        return arrays

    def _drop_corrupt(self, key: Any, want: int, got: int) -> None:
        _arrays, _crc, nb = self._lru.pop(key)
        self._bytes -= nb
        self.corrupt_pages += 1
        self._m_corrupt.inc()
        self._publish()
        kh = key.hex()[:16] if isinstance(key, bytes) else str(key)
        logger.error(
            f"kv_tier: REFUSING restore of page {kh}…: CRC32 {got:#010x} "
            f"!= captured {want:#010x} (host-RAM bit flip or torn copy); "
            "entry dropped — the device recomputes the suffix, nothing "
            "is lost")

    # -- accounting hooks (the engine calls these; trace events live
    # here so the kv_tier_* event names have a single owner) -----------------
    def note_capture_dropped(self, n: int = 1) -> None:
        """The in-flight spill queue was full: ``n`` evictions were not
        captured (pages recycled as before the tier existed)."""
        self.dropped_spills += n
        self._m_dropped.inc(n)

    def note_spill(self, pages: int, wall_s: float) -> None:
        """One drained spill batch committed ``pages`` D2H copies."""
        record_event("kv_tier_spill", cat="serve", pages=pages,
                     host_pages=self.host_pages, host_bytes=self._bytes,
                     wall_s=round(wall_s, 6))

    def note_restore(self, pages: int, wall_s: float) -> None:
        """One restore batch moved ``pages`` pages H2D."""
        self.restored_pages += pages
        self.hits += pages
        self._m_restored.inc(pages)
        self._m_restore_h.observe(wall_s)
        self._publish()
        record_event("kv_tier_restore", cat="serve", pages=pages,
                     host_pages=self.host_pages, wall_s=round(wall_s, 6))

    def note_miss(self) -> None:
        """A restore walk needed a page the tier does not hold."""
        self.misses += 1
        self._publish()

    def stats(self) -> Dict[str, float]:
        """Cumulative tier counters (tests/unit/test_kv_tier.py and
        tools/fleet_drill.py check these)."""
        out = {"spilled_pages": self.spilled_pages,
               "restored_pages": self.restored_pages,
               "host_pages": self.host_pages,
               "host_bytes": self._bytes,
               "host_evictions": self.host_evictions,
               "corrupt_pages": self.corrupt_pages,
               "dropped_spills": self.dropped_spills,
               "hit_rate": self.hit_rate}
        if self.nvme is not None:
            out.update(self.nvme.stats())
        return out


def page_slices(arrays: Dict[str, np.ndarray], j: int
                ) -> Dict[str, np.ndarray]:
    """Page ``j``'s own copy out of a ``paged_gather_pages`` batch:
    ``[L, 1, page_size, KVH, D]`` per leaf.  Copies — an entry must own
    its memory, not keep the whole gathered batch alive as a view."""
    return {name: np.ascontiguousarray(a[:, j:j + 1])
            for name, a in arrays.items()}


def batch_page_crcs(arrays: Dict[str, np.ndarray]) -> List[int]:
    """Per-page CRC32s of a gathered batch — literally the wire
    format's :func:`~.kv_transfer.page_crcs` (one serialization path)."""
    return page_crcs(arrays, sorted(arrays))


__all__ = ["HostKVTier", "NVMeKVTier", "KVTierConfig", "page_slices",
           "batch_page_crcs"]
