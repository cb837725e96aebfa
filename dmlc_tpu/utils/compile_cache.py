"""Persistent XLA compilation cache — ONE switch shared by every entry
point (ClusterNode, chip_smoke.py, bench.py, __graft_entry__.py,
tests/conftest.py).

Where the cache lives is the caller's environment's decision, not this
module's: if ``JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it
into ``jax.config.jax_compilation_cache_dir`` and ``enable()`` leaves that
value alone (the directory is part of the cache key, so a path this module
rewrote would never hit what the environment's owner put there). Only when
the variable is unset does ``enable()`` pick ``<repo>/.jax_cache``
(gitignored) — a fixed path, never a temp dir, pid or timestamp.

Under that default root, CPU-selected runs are additionally scoped by a
machine fingerprint: XLA:CPU persists ahead-of-time *machine-code*
artifacts keyed only by HLO, so a cache written on one host feeds binaries
compiled for a different CPU feature set to the loader on another (the repo
directory travels between machines). That is at best a wall of
``cpu_aot_loader.cc`` machine-feature-mismatch errors and at worst silent
deopts — so virtual-CPU runs (the multichip dryrun, the hermetic test mesh)
each land in ``.jax_cache/cpu-<fingerprint>`` instead of the shared root.
"""

from __future__ import annotations

import hashlib
import os
import platform as _platform
import sys
import threading
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parents[2]

# Persistent-cache observability (docs/OBSERVABILITY.md §8): jax announces
# hits/misses through jax.monitoring events; ``enable()`` registers ONE
# listener folding them here, and writes are inferred from cache-directory
# growth since enable() (jax emits no write event). ``export_metrics``
# exposes the lot as registry gauges so the silent cache becomes a scraped
# fleet signal.
_counts_lock = threading.Lock()
_COUNTS = {"hits": 0, "misses": 0, "requests": 0}
_ENABLED = False
_BASELINE_ENTRIES = 0


def _on_cache_event(event: str, **kw) -> None:
    """jax.monitoring event listener (also driven directly by the unit
    test): counts persistent-cache hit/miss/request events."""
    if "/jax/compilation_cache/" not in event:
        return
    with _counts_lock:
        if event.endswith("cache_hits"):
            _COUNTS["hits"] += 1
        elif event.endswith("cache_misses"):
            _COUNTS["misses"] += 1
        elif event.endswith("compile_requests_use_cache"):
            _COUNTS["requests"] += 1


def cache_dir() -> str | None:
    """The directory jax's persistent cache is using, read from
    ``jax.config`` — the one place both the environment variable and
    ``enable()``'s default land. None until jax is loaded or a directory is
    set. Never the import that loads jax (node.py's autodetect rule)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    return jax.config.jax_compilation_cache_dir or None


def _count_entries(root: str | None) -> int:
    if root is None:
        return 0
    try:
        # One directory read, no stat per entry: this runs inside every
        # metrics scrape, beside threads that want the interpreter.
        with os.scandir(root) as entries:
            return sum(1 for entry in entries if entry.is_file())
    except OSError:
        return 0


def counters() -> dict:
    """Hit/miss/request counts since process start, plus writes (entries
    added to the cache dir since ``enable()``) and the current entry
    count. All zeros until ``enable()`` has installed the listener."""
    with _counts_lock:
        out = dict(_COUNTS)
    entries = _count_entries(cache_dir())
    out["entries"] = entries
    out["writes"] = max(0, entries - _BASELINE_ENTRIES)
    return out


def export_metrics(registry) -> None:
    """Register the cache counters as gauges on a metrics Registry
    (utils/metrics.py): ``jax_cache_hits`` / ``jax_cache_misses`` /
    ``jax_cache_writes`` / ``jax_cache_entries``. Gauges read live, so one
    registration at node build covers the process lifetime."""
    registry.gauge("jax_cache_hits", lambda: _COUNTS["hits"])  # no directory read for these two
    registry.gauge("jax_cache_misses", lambda: _COUNTS["misses"])
    registry.gauge("jax_cache_writes", lambda: counters()["writes"])
    registry.gauge("jax_cache_entries", lambda: counters()["entries"])


def machine_fingerprint() -> str:
    """Stable id for this host's CPU code-generation surface: ISA flags and
    model, the inputs XLA:CPU's AOT specializes machine code against."""
    parts = [_platform.machine(), _platform.processor()]
    # One line PER KEY (cores are uniform; the first package suffices):
    # 'model name' alone is not discriminating — hypervisors report generic
    # model strings while masking different feature sets, and the flags are
    # what AOT code generation actually keys on.
    wanted = {"flags", "model name", "Features"}
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in wanted:
                    wanted.remove(key)
                    parts.append(line.strip())
                    if not wanted:
                        break
    except OSError:
        pass
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:12]


def _cpu_platform_selected() -> bool:
    """True when jax will SELECT the cpu backend — i.e. cpu is the first
    entry of the platform priority list. Membership is not enough: a
    ``tpu,cpu`` list selects the TPU with cpu as the host-side helper, and
    scoping that run's TPU entries per-host would miss the shared cache."""
    import jax

    cfg = getattr(jax.config, "jax_platforms", None) or os.environ.get(
        "JAX_PLATFORMS", ""
    )
    if cfg:
        return cfg.split(",")[0].strip().lower() == "cpu"
    # Nothing configured: jax auto-selects. Asking the backend initializes
    # it, which is fine here — enable() callers are about to compile anyway.
    return jax.default_backend() == "cpu"


def enable() -> None:
    """Turn the persistent cache on for this process. Idempotent: the first
    call decides the directory and installs the listener; later calls
    (every ClusterNode of a localcluster, a test after conftest) are
    no-ops."""
    global _ENABLED, _BASELINE_ENTRIES
    with _counts_lock:
        if _ENABLED:
            return
        _ENABLED = True
    import jax
    from jax import monitoring

    cpu = _cpu_platform_selected()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        root = _REPO_ROOT / ".jax_cache"
        if cpu:
            root = root / f"cpu-{machine_fingerprint()}"
        jax.config.update("jax_compilation_cache_dir", str(root))
    _BASELINE_ENTRIES = _count_entries(cache_dir())
    monitoring.register_event_listener(_on_cache_event)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)
    # Persist XLA's internal (autotuning etc.) caches too, not just final
    # executables — without these a "warm" hit still re-runs part of the
    # compile pipeline. NOT on CPU: there the internal cache stores AOT
    # machine-code kernels whose loader error-logs a feature-set comparison
    # on every hit (XLA stamps tuning pseudo-features like
    # +prefer-no-scatter that never appear in the detected host set), and
    # virtual-CPU compiles are cheap anyway.
    jax.config.update("jax_persistent_cache_enable_xla_caches", "none" if cpu else "all")
