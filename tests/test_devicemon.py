"""Device-plane telemetry (cluster/devicemon.py, docs/OBSERVABILITY.md §8).

Unit coverage for the compile census (warmup windows, steady-state
recompile detection, jax.monitoring rollup), the ``CensusedJit`` wrapper,
graceful degradation on CPU backends (None gauges, never a raise), the
MFU window math, the persistent-cache counters, and the fleet integration:
a real 3-node localcluster whose scrape carries the devicemon gauges, and
a seeded steady-state recompile landing its ``recompile_steady_state``
flight event through a real ``jax.jit`` recompile.
"""

import pytest

from dmlc_tpu.cluster.devicemon import (
    CENSUS,
    CensusedJit,
    CompileCensus,
    DEVICE_PEAKS,
    DeviceMonitor,
    pytree_nbytes,
)
from dmlc_tpu.cluster.flight import FlightRecorder
from dmlc_tpu.utils.metrics import Counters, Registry, merge_mergeable_snapshots


class VClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


class TestCompileCensus:
    def test_compiles_inside_warmup_are_not_steady(self):
        clock = VClock()
        census = CompileCensus(clock)
        census.warmup_s = 10.0
        assert census.record("prog") is False
        clock.t = 5.0
        assert census.record("prog") is False
        assert census.compiles() == 2
        assert census.steady_recompiles() == 0

    def test_compile_after_warmup_is_steady_and_fires_callbacks(self):
        clock = VClock()
        census = CompileCensus(clock)
        census.warmup_s = 10.0
        fired = []
        census.subscribe(lambda label, count: fired.append((label, count)))
        census.record("prog")
        clock.t = 11.0
        assert census.record("prog") is True
        assert census.steady_recompiles() == 1
        assert fired == [("prog", 2)]

    def test_warmup_windows_are_per_label(self):
        clock = VClock()
        census = CompileCensus(clock)
        census.warmup_s = 10.0
        census.record("old")
        clock.t = 11.0
        # "young" opens its OWN window at t=11: not steady at t=15.
        census.record("young")
        clock.t = 15.0
        assert census.record("young") is False
        assert census.record("old") is True

    def test_unsubscribe_stops_callbacks(self):
        clock = VClock()
        census = CompileCensus(clock)
        census.warmup_s = 0.0
        fired = []
        cb = lambda label, count: fired.append(label)  # noqa: E731
        census.subscribe(cb)
        census.record("prog")
        clock.t = 1.0
        census.record("prog")
        assert fired == ["prog"]
        census.unsubscribe(cb)
        clock.t = 2.0
        census.record("prog")
        assert fired == ["prog"]

    def test_callback_errors_never_break_record(self):
        clock = VClock()
        census = CompileCensus(clock)
        census.warmup_s = 0.0
        census.subscribe(lambda label, count: 1 / 0)
        census.record("prog")
        clock.t = 1.0
        assert census.record("prog") is True  # did not raise

    def test_snapshot_shape_and_jax_event_rollup(self):
        clock = VClock()
        census = CompileCensus(clock)
        census.warmup_s = 7.0
        census.record("prog", seconds=1.5)
        census.record("prog", seconds=0.5)
        census.note_jax_event("/jax/compile/backend_compile", 0.25)
        census.note_jax_event("/jax/compile/backend_compile", 0.75)
        snap = census.snapshot()
        assert snap["warmup_s"] == 7.0
        assert snap["labels"]["prog"] == {
            "compiles": 2, "seconds": 2.0, "steady_recompiles": 0,
        }
        assert snap["jax_events"]["/jax/compile/backend_compile"] == {
            "count": 2, "seconds": 1.0,
        }
        assert census.compile_seconds() == pytest.approx(2.0)


class FakeJit:
    """Stand-in for a jax jit object: a tracing cache size plus arbitrary
    attributes the wrapper must pass through."""

    def __init__(self):
        self.entries = 0
        self.cost_hint = "passthrough-ok"

    def _cache_size(self):
        return self.entries

    def __call__(self, x, grow=False):
        if grow:
            self.entries += 1
        return x * 2


class TestCensusedJit:
    def test_records_only_on_cache_growth(self):
        census = CompileCensus(VClock())
        fn = CensusedJit("prog", FakeJit(), census=census)
        assert fn(3, grow=True) == 6
        assert fn(4) == 8  # cache stable: no compile recorded
        assert fn(5, grow=True) == 10
        assert census.compiles() == 2
        assert census.snapshot()["labels"]["prog"]["compiles"] == 2

    def test_attribute_passthrough(self):
        fn = CensusedJit("prog", FakeJit(), census=CompileCensus(VClock()))
        assert fn.cost_hint == "passthrough-ok"
        assert fn.cache_entries() == 0

    def test_backend_without_cache_size_degrades_to_counting_nothing(self):
        census = CompileCensus(VClock())
        fn = CensusedJit("prog", lambda x: x + 1, census=census)
        assert fn.cache_entries() == -1
        assert fn(41) == 42  # still dispatches
        assert census.compiles() == 0


class TestGracefulCpu:
    """ISSUE 15 satellite (c): on CPU/sim backends the monitor reports
    None gauges, never raises, and the fleet merge drops the Nones."""

    def test_hbm_gauges_read_none_on_cpu(self):
        registry = Registry()
        mon = DeviceMonitor(registry, census=CompileCensus(VClock()))
        try:
            gauges = registry.snapshot()["gauges"]
            # Present (the contract: graceful degradation, not absence) ...
            for key in ("hbm_bytes_in_use", "hbm_peak_bytes", "hbm_limit_bytes"):
                assert key in gauges
                # ... and None: the CPU PJRT client has no memory_stats.
                assert gauges[key] is None
            # The census gauges still read real numbers ...
            assert gauges["jit_compiles"] == 0.0
            # ... but a CPU is not in the device_kind-keyed peak table, so
            # it has no roofline: None like the hbm gauges, never a default.
            assert "cpu" not in DEVICE_PEAKS
            assert gauges["device_peak_flops"] is None
        finally:
            mon.close()

    def test_broken_device_introspection_never_raises(self, monkeypatch):
        import jax

        monkeypatch.setattr(
            jax, "local_devices", lambda: (_ for _ in ()).throw(RuntimeError("boom"))
        )
        registry = Registry()
        mon = DeviceMonitor(registry, census=CompileCensus(VClock()))
        try:
            assert mon.memory_stats() is None
            assert mon.headroom_bytes() is None
            mon.poll()  # watermark pass on a broken backend: silent no-op
            assert registry.snapshot()["gauges"]["hbm_bytes_in_use"] is None
        finally:
            mon.close()

    def test_fleet_merge_drops_none_gauges(self):
        registry = Registry()
        mon = DeviceMonitor(registry, census=CompileCensus(VClock()))
        try:
            cpu_snap = registry.snapshot(mergeable=True)
        finally:
            mon.close()
        tpu_snap = {
            "counters": {}, "latency": {},
            "gauges": {"hbm_bytes_in_use": 2.0e9, "jit_compiles": 3.0},
        }
        merged = merge_mergeable_snapshots([cpu_snap, tpu_snap])
        # The CPU member's None did not poison (or zero) the TPU number.
        assert merged["gauges"]["hbm_bytes_in_use"] == 2.0e9
        assert merged["gauges"]["jit_compiles"] == 3.0

    def test_summary_never_raises_without_stats(self):
        mon = DeviceMonitor(None, census=CompileCensus(VClock()))
        try:
            summary = mon.summary()
            assert summary["hbm"]["bytes_in_use"] is None
            assert summary["platform_peak_flops"] is None
        finally:
            mon.close()


class TestSteadyRecompileSeeded:
    """ISSUE 15 satellite (d): seed a genuine steady-state recompile
    through a real ``jax.jit`` and assert the flight alert fires."""

    def test_real_jit_recompile_lands_flight_event(self):
        import jax
        import jax.numpy as jnp

        census = CompileCensus()  # real clock; warmup_s=0 makes t>first steady
        flight = FlightRecorder()
        metrics = Counters()
        mon = DeviceMonitor(
            None, flight=flight, metrics=metrics, warmup_s=0.0, census=census,
        )
        try:
            fn = CensusedJit("test/steady", jax.jit(lambda x: x * 2), census=census)
            fn(jnp.ones((2,), jnp.float32))   # first compile opens the window
            fn(jnp.ones((3,), jnp.float32))   # new shape AFTER warmup: steady
            assert census.compiles() == 2
            assert census.steady_recompiles() >= 1
            events = [
                e for e in flight.events() if e["kind"] == "recompile_steady_state"
            ]
            assert events, flight.events()
            assert events[0]["program"] == "test/steady"
            assert events[0]["compiles"] == 2
            assert metrics.get("recompile_steady_state") >= 1
        finally:
            mon.close()


class TestMfuWindow:
    def _monitor(self, clock):
        mon = DeviceMonitor(
            None, clock=clock, peak_flops=100.0, mfu_window_s=60.0,
            census=CompileCensus(clock),
        )
        mon._flops_per_item["fake"] = 10.0
        return mon

    def test_mfu_is_achieved_over_peak(self):
        clock = VClock()
        mon = self._monitor(clock)
        try:
            # 5 items * 10 flops in 1 device-second = 50 FLOP/s vs peak 100.
            mon.device_work("fake", 5, 1.0)
            assert mon.mfu("fake") == pytest.approx(0.5)
            mon.device_work("fake", 5, 1.0)  # same rate: ratio unchanged
            assert mon.mfu("fake") == pytest.approx(0.5)
        finally:
            mon.close()

    def test_no_roofline_without_a_table_row(self, monkeypatch):
        """The peak table is keyed by device_kind with no default row: on
        the CPU mesh the same work scores None, on a listed kind it scores
        against that row, and the override still wins."""
        from dmlc_tpu.cluster import devicemon

        clock = VClock()
        mon = DeviceMonitor(None, clock=clock, census=CompileCensus(clock))
        mon._flops_per_item["fake"] = 10.0
        try:
            mon.device_work("fake", 5, 1.0)
            assert mon.peak_flops() is None
            assert mon.mfu("fake") is None
            monkeypatch.setitem(
                devicemon.DEVICE_PEAKS, "cpu",
                {"flops_bf16": 200.0, "hbm_bytes_per_s": 1.0},
            )
            assert mon.peak_flops() == 200.0
            assert mon.mfu("fake") == pytest.approx(0.25)
        finally:
            mon.close()

    def test_window_expiry_returns_none(self):
        clock = VClock()
        mon = self._monitor(clock)
        try:
            mon.device_work("fake", 5, 1.0)
            clock.t = 61.0
            assert mon.mfu("fake") is None
        finally:
            mon.close()

    def test_unknown_model_skips_mfu_but_feeds_profiler(self):
        records = []

        class Profiler:
            def record(self, model, member, lane, seconds, count=1):
                records.append((model, member, lane, seconds, count))

        clock = VClock()
        mon = DeviceMonitor(
            None, profiler=Profiler(), member="m0", clock=clock,
            peak_flops=100.0, census=CompileCensus(clock),
        )
        try:
            mon.device_work("no_such_model_zzz", 4, 0.5)
            assert mon.mfu("no_such_model_zzz") is None
            assert records == [("no_such_model_zzz", "m0", "device", 0.5, 4)]
        finally:
            mon.close()

    def test_zero_items_or_seconds_ignored(self):
        clock = VClock()
        mon = self._monitor(clock)
        try:
            mon.device_work("fake", 0, 1.0)
            mon.device_work("fake", 5, 0.0)
            assert mon.mfu("fake") is None
        finally:
            mon.close()

    def test_register_model_exports_resident_and_mfu_gauges(self):
        clock = VClock()
        registry = Registry()
        mon = DeviceMonitor(
            registry, clock=clock, peak_flops=100.0, census=CompileCensus(clock),
        )
        mon._flops_per_item["fake"] = 10.0
        try:
            resident = {"value": None}
            mon.register_model("fake", resident_bytes=lambda: resident["value"])
            gauges = registry.snapshot()["gauges"]
            assert gauges["resident_bytes_fake"] is None  # lazy engine unbuilt
            assert gauges["mfu_fake"] is None
            resident["value"] = 12345
            mon.device_work("fake", 10, 1.0)
            gauges = registry.snapshot()["gauges"]
            assert gauges["resident_bytes_fake"] == 12345.0
            assert gauges["mfu_fake"] == pytest.approx(1.0)
            assert mon.resident_bytes_total() == 12345
        finally:
            mon.close()


class TestPytreeNbytes:
    def test_counts_array_leaves(self):
        import numpy as np

        tree = {"w": np.zeros((4, 4), np.float32), "b": np.zeros((4,), np.float32)}
        assert pytree_nbytes(tree) == 4 * 4 * 4 + 4 * 4

    def test_none_and_arrayless_leaves_count_zero(self):
        assert pytree_nbytes(None) == 0
        assert pytree_nbytes({"hp": "adam", "steps": 7}) == 0


class TestCompileCacheCounters:
    """ISSUE 15 satellite (a): persistent-cache hit/miss/write counters
    through the metrics registry."""

    def _fresh(self, monkeypatch, tmp_path, baseline=0):
        from dmlc_tpu.utils import compile_cache as cc

        monkeypatch.setattr(cc, "_COUNTS", {"hits": 0, "misses": 0, "requests": 0})
        monkeypatch.setattr(cc, "cache_dir", lambda: str(tmp_path))
        monkeypatch.setattr(cc, "_BASELINE_ENTRIES", baseline)
        return cc

    def test_listener_counts_cache_events(self, monkeypatch, tmp_path):
        cc = self._fresh(monkeypatch, tmp_path)
        cc._on_cache_event("/jax/compilation_cache/cache_hits")
        cc._on_cache_event("/jax/compilation_cache/cache_hits")
        cc._on_cache_event("/jax/compilation_cache/cache_misses")
        cc._on_cache_event("/jax/compilation_cache/compile_requests_use_cache")
        cc._on_cache_event("/jax/unrelated/event")  # ignored
        counts = cc.counters()
        assert counts["hits"] == 2
        assert counts["misses"] == 1
        assert counts["requests"] == 1

    def test_writes_are_entry_growth_since_enable(self, monkeypatch, tmp_path):
        cc = self._fresh(monkeypatch, tmp_path, baseline=1)
        (tmp_path / "a.bin").write_bytes(b"x")
        (tmp_path / "b.bin").write_bytes(b"y")
        (tmp_path / "c.bin").write_bytes(b"z")
        counts = cc.counters()
        assert counts["entries"] == 3
        assert counts["writes"] == 2  # grew from the baseline of 1

    def test_writes_never_negative(self, monkeypatch, tmp_path):
        cc = self._fresh(monkeypatch, tmp_path, baseline=5)
        assert cc.counters()["writes"] == 0

    def test_export_metrics_registers_live_gauges(self, monkeypatch, tmp_path):
        cc = self._fresh(monkeypatch, tmp_path)
        registry = Registry()
        cc.export_metrics(registry)
        cc._on_cache_event("/jax/compilation_cache/cache_hits")
        (tmp_path / "entry.bin").write_bytes(b"x")
        gauges = registry.snapshot()["gauges"]
        assert gauges["jax_cache_hits"] == 1.0
        assert gauges["jax_cache_misses"] == 0.0
        assert gauges["jax_cache_writes"] == 1.0
        assert gauges["jax_cache_entries"] == 1.0


class TestCompileCachePlacement:
    """The cache directory is the environment's to place: with
    JAX_COMPILATION_CACHE_DIR set, enable() touches nothing and jax writes
    there; unset, it is <repo>/.jax_cache[/cpu-<fingerprint>]."""

    SCRIPT = (
        "import jax, jax.numpy as jnp\n"
        "from dmlc_tpu.utils import compile_cache as cc\n"
        "cc.enable()\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones((4,))).block_until_ready()\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "print(cc.cache_dir())\n"
        "print(cc.counters()['writes'])\n"
    )

    def _run(self, env_overrides):
        import os
        import subprocess
        import sys

        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu", **env_overrides)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, "-c", self.SCRIPT], env=env, cwd=repo,
            capture_output=True, text=True, timeout=120, check=True,
        )
        return proc.stdout.strip().splitlines()[-3:]

    def test_environment_variable_places_the_cache(self, tmp_path):
        where = tmp_path / "placed"
        configured, reported, writes = self._run(
            {"JAX_COMPILATION_CACHE_DIR": str(where)}
        )
        assert configured == reported == str(where)  # nothing appended
        assert int(writes) >= 1
        assert any(where.iterdir())

    def test_default_is_the_repo_cache_scoped_per_cpu(self):
        from dmlc_tpu.utils import compile_cache as cc

        configured, reported, _ = self._run({})
        want = cc._REPO_ROOT / ".jax_cache" / f"cpu-{cc.machine_fingerprint()}"
        assert configured == reported == str(want)

    def test_a_metrics_scrape_reads_the_directory_twice_and_stats_nothing(
            self, monkeypatch, tmp_path):
        """The four gauges run inside every ``obs.metrics`` scrape, beside the
        decode thread: a stat per entry (2,800 system calls a scrape at 700
        entries) held a 2 s scrape over its budget on the chip's host."""
        import os
        from pathlib import Path

        from dmlc_tpu.utils import compile_cache as cc
        from dmlc_tpu.utils.metrics import Registry

        for i in range(5):
            (tmp_path / f"entry{i}").write_bytes(b"x")
        (tmp_path / "a_directory").mkdir()
        monkeypatch.setattr(cc, "cache_dir", lambda: str(tmp_path))
        reads = []
        real_scandir = os.scandir
        monkeypatch.setattr(cc.os, "scandir", lambda p: (reads.append(p), real_scandir(p))[1])

        def no_stat(self, *a, **kw):
            raise AssertionError(f"a stat of {self}")

        monkeypatch.setattr(Path, "stat", no_stat)
        registry = Registry()
        cc.export_metrics(registry)
        gauges = registry.snapshot()["gauges"]
        assert gauges["jax_cache_entries"] == 5
        assert {"jax_cache_hits", "jax_cache_misses", "jax_cache_writes"} <= set(gauges)
        assert len(reads) == 2  # entries and writes; hits and misses read no directory

    def test_cluster_node_build_enables_it(self, monkeypatch, tmp_path):
        from dmlc_tpu.cluster.node import ClusterNode
        from dmlc_tpu.utils import compile_cache as cc
        from dmlc_tpu.utils.config import ClusterConfig

        calls = []
        monkeypatch.setattr(cc, "enable", lambda: calls.append(1))
        node = ClusterNode(
            ClusterConfig(
                host="127.0.0.1", gossip_port=0, leader_port=0, member_port=0,
                storage_dir=str(tmp_path / "storage"), job_models=[],
            ),
            backends={},
        )
        try:
            assert calls == [1]
        finally:
            node.stop()


class TestFleetScrape:
    """ISSUE 15 satellite (d): a real 3-node localcluster's fleet scrape
    carries the devicemon gauges after a predict."""

    def test_fleet_scrape_carries_device_gauges(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from dmlc_tpu.cli import Cli
        from dmlc_tpu.cluster.localcluster import (
            start_local_cluster,
            stop_local_cluster,
            wait_until,
        )

        nodes = start_local_cluster(tmp_path, n_nodes=3)
        try:
            leader = nodes[0]
            wait_until(
                lambda: leader.tracker.current == leader.self_leader_addr,
                msg="tracker converged on the promoted leader",
            )
            leader.predict()
            wait_until(
                lambda: all(j.done for j in leader.scheduler.jobs.values()),
                msg="predict jobs complete",
            )
            # One real censused compile: the census is process-global (like
            # the tracer), so every co-hosted member's jit_compiles gauge
            # reflects it — exactly what a one-node-per-host fleet reports.
            CensusedJit("test/fleet_scrape", jax.jit(lambda x: x + 1))(
                jnp.ones((2,), jnp.float32)
            )
            assert CENSUS.compiles() > 0

            def scraped():
                good = []
                for addr, reply in leader.fleet_metrics.items():
                    gauges = (reply.get("metrics") or {}).get("gauges", {})
                    if (
                        "hbm_bytes_in_use" in gauges
                        and "hbm_limit_bytes" in gauges
                        and any(k.startswith("mfu_") for k in gauges)
                        and (gauges.get("jit_compiles") or 0) > 0
                    ):
                        good.append(addr)
                return good

            wait_until(
                lambda: len(scraped()) >= 1,
                timeout=30.0,
                msg="devicemon gauges in the leader's fleet scrape",
            )
            # The CLI device verb renders the fleet table from any member.
            table = Cli(nodes[1]).run_command("device")
            assert "hbm used/limit" in table
            assert "compiles" in table
            for node in nodes:
                assert node.self_member_addr in table
        finally:
            stop_local_cluster(nodes)
