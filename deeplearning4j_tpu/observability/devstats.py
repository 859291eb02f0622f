"""Device-side cost accounting, sampled OFF the serving hot path.

Two accounts the adaptive policies (ROADMAP items 2-3) need before
they can size anything:

- **Device memory** — per-device allocator stats from
  ``Device.memory_stats()`` (TPU/GPU backends; ``None`` on CPU, where
  the view degrades to the live-array census) plus a
  ``jax.live_arrays()`` census (count + bytes). Both are read at
  COLLECTION time (a ``/snapshot`` or ``/metrics`` render), never from
  the decode loop — reading allocator counters syncs nothing, but it is
  still work the hot path must not pay.

- **KV-cache bytes** — exact per-engine accounting from the decoder's
  ACTUAL cache leaves (slots × heads × T_max × Dh × itemsize summed
  over attention layers and k/v), not a formula that can drift from the
  allocation. Sharded caches report global bytes, per-host
  (addressable) bytes, and the shard count, so a (data, tp) mesh's
  dominant allocation is attributable per chip — the number the paged
  KV cache (ROADMAP item 2) must fit under.

Everything here is host-side observation: nothing dispatches device
work, nothing runs under jit (graftlint GL015 rejects devstats calls in
traced code), and every probe degrades to a partial snapshot instead of
failing the endpoint.
"""

from __future__ import annotations

import threading
import weakref
from typing import Dict, List, Optional

from .metrics import MetricsRegistry, default_registry


def _leaf_arrays(tree) -> List:
    import jax
    return [x for x in jax.tree_util.tree_leaves(tree)
            if hasattr(x, "dtype") and hasattr(x, "shape")]


def device_memory_snapshot() -> dict:
    """Per-device allocator stats + the live-array census. Guarded
    end-to-end: a backend without ``memory_stats`` (CPU) reports
    ``memory_stats: None`` per device and the census still stands."""
    import jax
    devices = []
    for d in jax.local_devices():
        row = {"id": int(d.id), "platform": str(d.platform),
               "kind": str(getattr(d, "device_kind", "?"))}
        try:
            ms = d.memory_stats()
        except Exception:   # noqa: BLE001 — a probe must not 500 the view
            ms = None
        if ms:
            row["memory_stats"] = {
                k: int(v) for k, v in ms.items()
                if isinstance(v, (int, float)) and k in (
                    "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
                    "largest_alloc_size", "pool_bytes")}
        else:
            row["memory_stats"] = None
        devices.append(row)
    try:
        live = jax.live_arrays()
        census = {"count": len(live),
                  "bytes": int(sum(int(a.nbytes) for a in live))}
    except Exception:   # noqa: BLE001
        census = {"count": None, "bytes": None}
    return {"devices": devices, "live_arrays": census}


def kv_cache_stats(engine) -> dict:
    """Exact KV-cache byte accounting from the engine's live cache
    leaves. ``bytes`` is the global logical allocation; on a sharded
    cache ``addressable_bytes`` is this host's share and ``shards`` the
    device count one layer's k tensor spans."""
    caches = getattr(engine, "_caches", None)
    if not caches:
        return {"bytes": 0, "layers": 0}
    leaves = _leaf_arrays(caches)
    total = sum(int(x.size) * int(x.dtype.itemsize) for x in leaves)
    addressable = 0
    shards = 1
    for x in leaves:
        try:
            sh = x.addressable_shards
            addressable += sum(int(s.data.size) * int(x.dtype.itemsize)
                               for s in sh)
            shards = max(shards, len(x.sharding.device_set))
        except Exception:   # noqa: BLE001 — plain arrays: fully local
            addressable += int(x.size) * int(x.dtype.itemsize)
    first = leaves[0]
    out = {
        "bytes": total,
        "addressable_bytes": addressable,
        "shards": shards,
        "layers": len(caches),
        "slot_shape": list(first.shape),   # [S, H/g, T_max, g·Dh]
        "dtype": str(first.dtype),
        "bytes_per_slot": total // max(1, int(first.shape[0])),
        # g: heads sharing one 128-lane row of the slab (1 = unpacked,
        # and always for a paged pool) — the engine's own account
        "heads_per_row": int(getattr(engine, "kv_heads_per_row", 1)),
        # a latent (compressed-KV) slab: bytes a cached token takes over
        # all its layers; 0 for per-head k/v
        "latent_bytes_per_token": int(getattr(
            engine, "latent_cache_bytes_per_token", 0)),
    }
    # paged engine (ISSUE 12): slot_shape is the POOL shape
    # [P, H, page_size, Dh] and bytes_per_slot is bytes per PAGE; the
    # page-granular account (free/used/cached/shared, mapped pages,
    # refcount'd share ratio, internal fragmentation) rides alongside —
    # pool bytes are FIXED by construction, which is exactly what makes
    # concurrency-at-fixed-memory a devstats-verifiable claim
    try:
        fn = getattr(engine, "kv_page_stats", None)
        pages = fn() if fn is not None else None
    except Exception:   # noqa: BLE001 — a probe must not 500 the view
        pages = None
    if pages is not None:
        out["paged"] = True
        out["pages"] = pages
        used = pages.get("used", 0)
        out["pages"]["share_ratio"] = round(
            pages.get("shared", 0) / used, 4) if used else 0.0
    mesh = getattr(engine, "mesh", None)
    if mesh is not None:
        from ..parallel.mesh import mesh_tag
        out["mesh"] = mesh_tag(mesh)
    return out


class DeviceStats:
    """Aggregating view: engines attach once; ``snapshot()`` assembles
    device memory + per-engine KV bytes on demand.

    Registry integration: ``devstats_live_array_bytes`` /
    ``devstats_live_arrays`` gauges (collection-time callbacks) and a
    ``devstats_kv_cache_bytes{engine=...}`` and
    ``devstats_kv_heads_per_row{engine=...}`` /
    ``devstats_latent_cache_bytes_per_token{engine=...}`` gauges per attached
    engine — all weakref'd, so a retired engine reads 0 instead of being
    pinned (with its device caches) by the registry."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self._registry = registry if registry is not None \
            else default_registry()
        self._lock = threading.Lock()
        self._engines: Dict[str, weakref.ref] = {}
        reg = self._registry
        self._g_kv = reg.gauge("devstats_kv_cache_bytes",
                               "KV-cache bytes allocated (global)",
                               ("engine",))
        self._g_rows = reg.gauge("devstats_kv_heads_per_row",
                                 "heads sharing one 128-lane row of the "
                                 "slab KV cache (1 = unpacked)",
                                 ("engine",))
        self._g_latent = reg.gauge(
            "devstats_latent_cache_bytes_per_token",
            "bytes one cached token takes over all latent-attention "
            "layers (0 = per-head k/v cache)", ("engine",))
        reg.gauge("devstats_live_arrays",
                  "jax.live_arrays() count").set_function(
            _live_count)
        reg.gauge("devstats_live_array_bytes",
                  "jax.live_arrays() total bytes").set_function(
            _live_bytes)

    def attach_engine(self, name: str, engine) -> "DeviceStats":
        wref = weakref.ref(engine)
        with self._lock:
            self._engines[str(name)] = wref
        self._g_kv.labels(str(name)).set_function(
            lambda: (lambda e: 0 if e is None else
                     kv_cache_stats(e).get("bytes", 0))(wref()))
        # the engine's own label: no walk over the cache leaves
        self._g_rows.labels(str(name)).set_function(
            lambda: int(getattr(wref(), "kv_heads_per_row", 0)))
        self._g_latent.labels(str(name)).set_function(
            lambda: int(getattr(wref(), "latent_cache_bytes_per_token", 0)))
        return self

    def snapshot(self) -> dict:
        out = device_memory_snapshot()
        kv = {}
        with self._lock:
            engines = dict(self._engines)
        for name, wref in sorted(engines.items()):
            eng = wref()
            if eng is None:
                continue
            try:
                kv[name] = kv_cache_stats(eng)
            except Exception as e:   # noqa: BLE001 — degrade per engine
                kv[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
        out["kv_cache"] = kv
        # attention calls traced in this process by the plan each took
        # (nn.helpers.note_attention_plan): how many engaged the packed
        # 128-lane flash tile
        from ..nn.helpers import attention_plan_counts
        out["attention_plans"] = attention_plan_counts()
        return out


def _live_count() -> int:
    import jax
    try:
        return len(jax.live_arrays())
    except Exception:   # noqa: BLE001
        return 0


def _live_bytes() -> int:
    import jax
    try:
        return int(sum(int(a.nbytes) for a in jax.live_arrays()))
    except Exception:   # noqa: BLE001
        return 0
