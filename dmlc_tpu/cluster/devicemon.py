"""Device-plane telemetry: compile census, HBM accounting, live MFU.

The observability plane (docs/OBSERVABILITY.md §1-§7) sees every RPC, span,
and cost lane — but nothing below the Python line. This module is the
device-plane counterpart, owned by each node (``ClusterNode._build``) and
exported through the SAME registry/scrape/scrape-tree fabric, so the leader
learns about compiles, HBM pressure, and achieved FLOP/s the same way it
learns about queue depths:

- **Compile census** — every jit construction site the repo owns
  (``parallel/inference.py``, ``generate/engine.py``,
  ``ops/device_resize.py``) wraps its jitted callable in ``CensusedJit``,
  which detects a compile by tracing-cache growth around each dispatch and
  records it in the process-global ``CENSUS`` under a stable program label.
  ``jax.monitoring`` duration listeners (where available) add backend
  compile-seconds. A label that compiles again AFTER its warmup window is a
  *steady-state recompile* — the runtime counterpart to analyzer rule A6
  (docs/ANALYZE.md) — and lands a ``recompile_steady_state`` flight event.
- **HBM accounting** — ``device.memory_stats()`` polled into
  ``hbm_bytes_in_use`` / ``hbm_peak_bytes`` / ``hbm_limit_bytes`` gauges
  (graceful ``None`` on CPU/sim backends that have no stats), plus analytic
  resident bytes per loaded model (weights pytree + KV page pools) so
  headroom is attributable, with an ``hbm_high_watermark`` flight event at
  the alert fraction.
- **Live MFU** — each dispatch/gen-step reports (items, device-seconds);
  with the registry's analytic ``flops_per_item`` that becomes achieved
  FLOP/s against the ``DEVICE_PEAKS`` roofline of this host's
  ``device_kind``, exported as per-model ``mfu_<model>`` gauges (``None``
  on a device kind the table does not list — a CPU has no roofline here)
  and folded into CostProfiler lanes.

The census is process-global (jax compiles are process-global); co-hosted
nodes in the localcluster harness therefore share one census, exactly like
they share the process-global tracer.
"""

from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import TYPE_CHECKING, Any, Callable

if TYPE_CHECKING:
    from dmlc_tpu.cluster.flight import FlightRecorder
    from dmlc_tpu.utils.metrics import Metrics, Registry

log = logging.getLogger(__name__)

# Published per-chip peaks, keyed by jax ``device_kind`` — the ONE table
# every utilization figure in the repo (these gauges, bench.py) divides by.
# A kind that is not listed has no roofline: ``peak_flops()`` and the
# ``mfu_*`` gauges read None rather than score against somebody else's
# chip. Override per-node with config.devicemon_peak_flops.
DEVICE_PEAKS: dict[str, dict[str, float]] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s. jax reports the chip as "TPU v5 lite".
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
}


def device_identity() -> dict[str, Any] | None:
    """``{"platform", "device_kind", "count"}`` of this process's local
    devices as jax reports them, or None while jax is not loaded or the
    backend cannot be read. Never raises, and never the import that loads
    jax (node.py's autodetect rule); once jax is loaded this does
    initialize the backend, like ``memory_stats``."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        devices = jax.local_devices()
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
        }
    except Exception:  # noqa: BLE001 - telemetry degrades to None, never raises
        log.debug("device introspection failed", exc_info=True)
        return None


def pytree_nbytes(tree: Any) -> int:
    """Total bytes of every array leaf in a pytree (0 for a None tree).
    Works on jax arrays, numpy arrays, and ShapeDtypeStructs alike — any
    leaf without ``nbytes`` counts 0 rather than raising."""
    if tree is None:
        return 0
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


class CompileCensus:
    """Process-global compile ledger: per-program-label compile counts and
    seconds, with steady-state-recompile detection.

    A label's first compile opens its *warmup window* (``warmup_s``).
    Compiles inside the window are expected (cold start, shape discovery);
    a compile AFTER the window means a steady-state program lost its cache
    entry or saw a new shape — the condition analyzer rule A6 hunts
    statically, observed live. Subscribed callbacks (each node's
    DeviceMonitor) fire on that edge."""

    def __init__(self, clock: Callable[[], float] = time.monotonic) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self.warmup_s = 60.0
        self._labels: dict[str, dict[str, float]] = {}
        # jax.monitoring event -> [count, seconds]; backend compile phases
        # observed through the duration listener, kept apart from our own
        # labeled sites (they overlap: one labeled compile spans several
        # backend events).
        self._jax_events: dict[str, list[float]] = {}
        self._callbacks: list[Callable[[str, int], None]] = []

    def subscribe(self, callback: Callable[[str, int], None]) -> None:
        with self._lock:
            if callback not in self._callbacks:
                self._callbacks.append(callback)

    def unsubscribe(self, callback: Callable[[str, int], None]) -> None:
        with self._lock:
            if callback in self._callbacks:
                self._callbacks.remove(callback)

    def record(self, label: str, seconds: float = 0.0) -> bool:
        """Count one compile under ``label``; returns True when it landed
        after the label's warmup window (a steady-state recompile). The
        seconds are the dispatch wall that triggered the compile —
        trace + lower + backend compile dominate that wall, so it is the
        honest per-label cost figure available without jax internals."""
        now = self._clock()
        with self._lock:
            ent = self._labels.get(label)
            if ent is None:
                ent = {
                    "compiles": 0.0, "seconds": 0.0,
                    "first": now, "steady_recompiles": 0.0,
                }
                self._labels[label] = ent
            ent["compiles"] += 1.0
            ent["seconds"] += max(0.0, float(seconds))
            steady = (now - ent["first"]) > self.warmup_s
            if steady:
                ent["steady_recompiles"] += 1.0
            count = int(ent["compiles"])
            callbacks = list(self._callbacks)
        if steady:
            for cb in callbacks:
                try:
                    cb(label, count)
                except Exception:  # noqa: BLE001 - telemetry must not break dispatch
                    log.exception("steady-recompile callback failed for %s", label)
        return steady

    def note_jax_event(self, event: str, seconds: float) -> None:
        """Fold one jax.monitoring duration event (backend compile phases)."""
        with self._lock:
            ent = self._jax_events.setdefault(event, [0.0, 0.0])
            ent[0] += 1.0
            ent[1] += max(0.0, float(seconds))

    # ---- reads ----------------------------------------------------------

    def compiles(self) -> int:
        with self._lock:
            return int(sum(e["compiles"] for e in self._labels.values()))

    def compile_seconds(self) -> float:
        with self._lock:
            return float(sum(e["seconds"] for e in self._labels.values()))

    def steady_recompiles(self) -> int:
        with self._lock:
            return int(sum(e["steady_recompiles"] for e in self._labels.values()))

    def snapshot(self) -> dict[str, Any]:
        """Wire/report form: per-label census + raw jax.monitoring rollup."""
        with self._lock:
            labels = {
                label: {
                    "compiles": int(e["compiles"]),
                    "seconds": round(e["seconds"], 6),
                    "steady_recompiles": int(e["steady_recompiles"]),
                }
                for label, e in sorted(self._labels.items())
            }
            events = {
                ev: {"count": int(c), "seconds": round(s, 6)}
                for ev, (c, s) in sorted(self._jax_events.items())
            }
        return {"labels": labels, "jax_events": events, "warmup_s": self.warmup_s}

    def reset(self) -> None:
        """Tests only: drop every label and event."""
        with self._lock:
            self._labels.clear()
            self._jax_events.clear()


CENSUS = CompileCensus()

_JAX_HOOKED = False
_HOOK_LOCK = threading.Lock()


def hook_jax_monitoring() -> bool:
    """Register the (one, idempotent) jax.monitoring duration listener that
    feeds backend compile phases into ``CENSUS``. Returns False when jax or
    its monitoring API is unavailable — the census still works from the
    ``CensusedJit`` wrappers alone."""
    global _JAX_HOOKED
    with _HOOK_LOCK:
        if _JAX_HOOKED:
            return True
        import sys

        if "jax" not in sys.modules:
            # Never the import that loads jax (node.py's autodetect rule):
            # the caller retries on its poll cadence and the hook lands
            # once an engine has paid the import.
            return False
        try:
            from jax import monitoring as jax_monitoring
        except Exception:  # noqa: BLE001 - jax-less environments degrade gracefully
            return False

        def _on_duration(event: str, duration_secs: float, **kw: Any) -> None:
            if "/compile/" in event or "compilation_cache" in event:
                CENSUS.note_jax_event(event, duration_secs)

        try:
            jax_monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:  # noqa: BLE001
            return False
        _JAX_HOOKED = True
        return True


class CensusedJit:
    """Transparent census wrapper for one jitted callable.

    Detects a compile by tracing-cache growth (``_cache_size``) around each
    dispatch and records it under ``label``. Every other attribute
    (``lower``, ``_cache_size``, ...) passes through, so engines keep using
    the wrapped object exactly as before (``jit_cache_sizes``, bench's
    ``lower().compile().cost_analysis()``). A backend whose jit object has
    no ``_cache_size`` degrades to counting nothing — never raising."""

    def __init__(self, label: str, fn: Any, census: CompileCensus | None = None) -> None:
        # _fn is set FIRST: __getattr__ delegates to it.
        self._fn = fn
        self._label = label
        self._census = census if census is not None else CENSUS

    def cache_entries(self) -> int:
        try:
            return int(self._fn._cache_size())
        except Exception:  # noqa: BLE001 - census is best-effort
            return -1

    def __call__(self, *args: Any, **kw: Any) -> Any:
        before = self.cache_entries()
        # dmlc-lint: disable=D1 -- measuring REAL compile wall is the point: this wraps live jit dispatch (never run under the sim fabric), and the census it feeds is injected-clock for everything the simulator does exercise
        t0 = time.perf_counter()
        out = self._fn(*args, **kw)
        if before >= 0 and self.cache_entries() > before:
            # dmlc-lint: disable=D1 -- closes the real compile-wall measurement opened at t0 above
            self._census.record(self._label, time.perf_counter() - t0)
        return out

    def __getattr__(self, name: str) -> Any:
        return getattr(self._fn, name)


class DeviceMonitor:
    """One node's device-plane telemetry: registry gauges + flight events.

    Never raises from a gauge read or a poll — on CPU/sim backends with no
    ``memory_stats`` the hbm gauges read None, which the registry snapshot
    carries and the fleet merge drops (docs/OBSERVABILITY.md §2)."""

    def __init__(
        self,
        registry: Registry | None,
        *,
        flight: FlightRecorder | None = None,
        metrics: Metrics | None = None,
        profiler: Any = None,
        member: str = "",
        clock: Callable[[], float] = time.monotonic,
        warmup_s: float = 60.0,
        hbm_alert_fraction: float = 0.9,
        peak_flops: float = 0.0,
        mfu_window_s: float = 60.0,
        census: CompileCensus | None = None,
    ) -> None:
        self.registry = registry
        self.flight = flight
        self.metrics = metrics
        self.profiler = profiler
        self.member = member
        self.clock = clock
        self.hbm_alert_fraction = float(hbm_alert_fraction)
        self.mfu_window_s = float(mfu_window_s)
        self._peak_override = float(peak_flops)
        self.census = census if census is not None else CENSUS
        self.census.warmup_s = float(warmup_s)
        hook_jax_monitoring()
        self.census.subscribe(self._on_steady_recompile)
        self._lock = threading.Lock()
        # model -> deque[(t, flops, device_seconds)] inside mfu_window_s
        self._work: dict[str, deque[tuple[float, float, float]]] = {}
        self._flops_per_item: dict[str, float | None] = {}
        self._residents: dict[str, Callable[[], int | None]] = {}
        self._hbm_peak_seen = 0.0
        self._hbm_alerted = False
        if registry is not None:
            registry.gauge("hbm_bytes_in_use", lambda: self._hbm_value("bytes_in_use"))
            registry.gauge("hbm_peak_bytes", lambda: self._hbm_value("peak_bytes_in_use"))
            registry.gauge("hbm_limit_bytes", lambda: self._hbm_value("bytes_limit"))
            registry.gauge("jit_compiles", self.census.compiles)
            registry.gauge("jit_compile_seconds", self.census.compile_seconds)
            registry.gauge("jit_steady_recompiles", self.census.steady_recompiles)
            registry.gauge("device_peak_flops", self.peak_flops)

    def close(self) -> None:
        self.census.unsubscribe(self._on_steady_recompile)

    # ---- compile census -------------------------------------------------

    def _on_steady_recompile(self, label: str, compiles: int) -> None:
        if self.metrics is not None:
            self.metrics.inc("recompile_steady_state")
        if self.flight is not None:
            self.flight.note(
                "recompile_steady_state", program=label, compiles=compiles,
                warmup_s=self.census.warmup_s,
            )

    # ---- HBM accounting -------------------------------------------------

    def memory_stats(self) -> dict[str, Any] | None:
        """``device.memory_stats()`` for the first local device, or None on
        backends without memory introspection (CPU/sim). Never raises, and
        never the import that loads (or the call that initializes) jax —
        stats appear once an engine has built."""
        import sys

        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            device = jax.local_devices()[0]
            stats_fn = getattr(device, "memory_stats", None)
            if stats_fn is None:
                return None
            stats = stats_fn()
            return dict(stats) if stats else None
        except Exception:  # noqa: BLE001 - telemetry degrades to None, never raises
            return None

    def _hbm_value(self, key: str) -> float | None:
        stats = self.memory_stats()
        if stats is None:
            return None
        value = stats.get(key)
        if value is None and key == "peak_bytes_in_use":
            # PJRT spellings vary; fall back to our own polled watermark.
            with self._lock:
                return self._hbm_peak_seen if self._hbm_peak_seen > 0 else None
        return float(value) if value is not None else None

    def headroom_bytes(self) -> float | None:
        """limit - in_use, or None when the backend reports no stats."""
        stats = self.memory_stats()
        if stats is None:
            return None
        limit, used = stats.get("bytes_limit"), stats.get("bytes_in_use")
        if limit is None or used is None:
            return None
        return float(limit) - float(used)

    def poll(self) -> None:
        """One watermark/alert pass (the node runs this on its devicemon
        cadence). Tracks the high watermark and stamps an
        ``hbm_high_watermark`` flight event on the alert-fraction edge.
        Also retries the jax.monitoring hook, which is deferred until an
        engine has paid the jax import."""
        hook_jax_monitoring()
        stats = self.memory_stats()
        if stats is None:
            return
        used = float(stats.get("bytes_in_use") or 0.0)
        limit = float(stats.get("bytes_limit") or 0.0)
        peak = float(stats.get("peak_bytes_in_use") or used)
        with self._lock:
            self._hbm_peak_seen = max(self._hbm_peak_seen, used, peak)
            fraction = (used / limit) if limit > 0 else 0.0
            fire = fraction >= self.hbm_alert_fraction and not self._hbm_alerted
            if fire:
                self._hbm_alerted = True
            elif fraction < self.hbm_alert_fraction * 0.9:
                self._hbm_alerted = False  # hysteresis: re-arm well below the edge
        if fire:
            if self.metrics is not None:
                self.metrics.inc("hbm_high_watermark")
            if self.flight is not None:
                self.flight.note(
                    "hbm_high_watermark", bytes_in_use=int(used),
                    bytes_limit=int(limit), fraction=round(fraction, 4),
                    threshold=self.hbm_alert_fraction,
                )

    def register_model(
        self, model: str, resident_bytes: Callable[[], int | None] | None = None
    ) -> None:
        """Register one servable model: a ``resident_bytes_<model>`` gauge
        (analytic weights + KV bytes, None until the lazy engine builds)
        and its ``mfu_<model>`` gauge."""
        if resident_bytes is not None:
            self._residents[model] = resident_bytes
            if self.registry is not None:
                self.registry.gauge(
                    f"resident_bytes_{model}",
                    lambda m=model: self._resident_value(m),
                )
        if self.registry is not None:
            self.registry.gauge(f"mfu_{model}", lambda m=model: self.mfu(m))

    def _resident_value(self, model: str) -> float | None:
        fn = self._residents.get(model)
        if fn is None:
            return None
        value = fn()
        return float(value) if value is not None else None

    def resident_bytes_total(self) -> int:
        """Sum of every registered model's known resident bytes."""
        total = 0
        for model in list(self._residents):
            value = self._resident_value(model)
            if value is not None:
                total += int(value)
        return total

    # ---- live MFU -------------------------------------------------------

    def peak_flops(self) -> float | None:
        """The roofline this node scores against: the configured override,
        else the ``DEVICE_PEAKS`` row of the local ``device_kind``. None
        while jax is not loaded, when the backend cannot be read, or for a
        kind the table does not list (every CPU)."""
        if self._peak_override > 0:
            return self._peak_override
        ident = device_identity()
        if ident is None:
            return None
        row = DEVICE_PEAKS.get(ident["device_kind"])
        return row["flops_bf16"] if row is not None else None

    def _item_flops(self, model: str) -> float | None:
        if model not in self._flops_per_item:
            value: float | None = None
            try:
                from dmlc_tpu.models.registry import get_model

                value = get_model(model).flops_per_item()
            except Exception:  # noqa: BLE001 - unknown/unregistered models just skip MFU
                value = None
            self._flops_per_item[model] = value
        return self._flops_per_item[model]

    def device_work(self, model: str, items: int, seconds: float) -> None:
        """One device execution's accounting: ``items`` units (images or
        generated tokens) took ``seconds`` of device wall. This is the
        callback the engines call per dispatch/gen-step; it feeds the MFU
        window and the per-model CostProfiler compute lane."""
        if items <= 0 or seconds <= 0:
            return
        flops = self._item_flops(model)
        now = self.clock()
        if flops is not None:
            with self._lock:
                window = self._work.setdefault(model, deque())
                window.append((now, float(items) * flops, float(seconds)))
                horizon = now - self.mfu_window_s
                while window and window[0][0] < horizon:
                    window.popleft()
        if self.profiler is not None:
            try:
                self.profiler.record(model, self.member, "device", seconds, count=items)
            except Exception:  # noqa: BLE001 - telemetry must not break dispatch
                log.debug("profiler device-lane record failed", exc_info=True)

    def mfu(self, model: str) -> float | None:
        """Model FLOP/s Utilization over the sliding window: achieved
        FLOP/s during device execution divided by the device roofline.
        None until the model has reported work, for a model with no
        analytic flops_per_item, or on a device with no roofline."""
        now = self.clock()
        with self._lock:
            window = self._work.get(model)
            if not window:
                return None
            horizon = now - self.mfu_window_s
            while window and window[0][0] < horizon:
                window.popleft()
            flops = sum(f for _, f, _ in window)
            seconds = sum(s for _, _, s in window)
        if seconds <= 0:
            return None
        peak = self.peak_flops()
        if peak is None:
            return None
        return (flops / seconds) / peak

    # ---- reporting ------------------------------------------------------

    def summary(self) -> dict[str, Any]:
        """One node's device section (bench/CLI form): census, HBM, MFU."""
        stats = self.memory_stats()
        with self._lock:
            models = sorted(set(self._work) | set(self._residents))
        mfu = {m: self.mfu(m) for m in models}
        residents = {m: self._resident_value(m) for m in sorted(self._residents)}
        return {
            "platform_peak_flops": self.peak_flops(),
            "hbm": {
                "bytes_in_use": stats.get("bytes_in_use") if stats else None,
                "peak_bytes_in_use": (
                    stats.get("peak_bytes_in_use") if stats else None
                ),
                "bytes_limit": stats.get("bytes_limit") if stats else None,
            },
            "resident_bytes": residents,
            "mfu": {m: v for m, v in mfu.items() if v is not None},
            "census": self.census.snapshot(),
        }


__all__ = [
    "CENSUS",
    "CensusedJit",
    "CompileCensus",
    "DEVICE_PEAKS",
    "DeviceMonitor",
    "device_identity",
    "hook_jax_monitoring",
    "pytree_nbytes",
]
