"""Critical-path attribution + drift sentinel (docs/OBSERVABILITY.md §9).

Pins the ISSUE-20 acceptance math:

- a hand-built DAG with overlapped children charges only the max-lane
  chain — stage shares sum to ~1.0 of wall time, never more;
- gang fan-out charges the slowest rank;
- the backwards-walk attribution matches a brute-force longest-path
  reference (elementary intervals x latest-ending-active-child) on
  randomized seeded DAGs;
- orphan subtrees degrade gracefully (charged under a virtual root,
  never crashing or double-counting);
- the analyzer charges each trace once fleet-wide (root ownership), and
  the fleet fold + sentinel name a drifting member within
  ``confirm_windows`` ticks across chaos seeds 0/1000/2000.
"""

from __future__ import annotations

import math
import random

import pytest

from dmlc_tpu.cluster.critpath import (
    GAP_STAGE,
    CritPathAnalyzer,
    FleetCritPath,
    Span,
    breakdown,
    critical_path,
    spans_from_perfetto,
    spans_from_wire,
    stage_of,
)
from dmlc_tpu.cluster.sentinel import DriftSentinel


def mk(name, start, end, span_id, parent=None, trace="t1", lane=None,
       model=None):
    return Span(name=name, start=float(start), end=float(end),
                span_id=span_id, parent_id=parent, trace_id=trace,
                lane=lane, model=model)


def charged_by_span(path):
    out: dict[str, float] = {}
    for span, sec in path.charges:
        out[span.span_id] = out.get(span.span_id, 0.0) + sec
    return out


# ---------------------------------------------------------------------------
# Extraction math
# ---------------------------------------------------------------------------


class TestCriticalPath:
    def test_overlapped_children_charge_max_lane_only(self):
        # root [0,10]; A [1,6] and B [2,9] overlap: B (later-ending)
        # shadows A on [2,6]; A is charged only its uncovered head [1,2].
        spans = [
            mk("rpc/job.predict", 0, 10, "r", model="m"),
            mk("scheduler/dispatch", 1, 6, "a", parent="r", lane="n1"),
            mk("scheduler/dispatch", 2, 9, "b", parent="r", lane="n2"),
        ]
        path = critical_path(spans)
        got = charged_by_span(path)
        assert got == pytest.approx({"r": 1 + 1, "a": 1, "b": 7})
        assert path.total_s == pytest.approx(10.0)  # exact wall partition
        shares = sum(got.values()) / 10.0
        assert shares == pytest.approx(1.0)

    def test_gang_fanout_charges_slowest_rank(self):
        # Four gang ranks fan out at t=1; the slowest ([1,9]) is the
        # blocking chain — the three faster ranks finish in its shadow
        # and charge nothing.
        spans = [mk("rpc/job.predict", 0, 10, "r", model="m")]
        ends = [4, 5, 9, 3]
        for i, e in enumerate(ends):
            spans.append(mk("rpc/job.decode_gang", 1, e, f"g{i}",
                            parent="r", lane=f"rank{i}"))
        path = critical_path(spans)
        got = charged_by_span(path)
        assert got["g2"] == pytest.approx(8.0)  # slowest rank [1,9]
        assert all(f"g{i}" not in got for i in (0, 1, 3))
        assert got["r"] == pytest.approx(2.0)  # [0,1] + [9,10]
        assert path.total_s == pytest.approx(10.0)

    def test_nested_pipeline_charges_blocking_chain(self):
        # dispatch [1,5] with decode child [2,4]; compute [4,9] pipelined
        # after: each inner span charges only its unshadowed self-time.
        spans = [
            mk("rpc/job.predict", 0, 10, "r", model="m"),
            mk("scheduler/dispatch", 1, 5, "d", parent="r", lane="n1"),
            mk("host/decode", 2, 4, "dec", parent="d", lane="n1"),
            mk("device/forward", 4, 9, "fwd", parent="r", lane="n1"),
        ]
        got = charged_by_span(critical_path(spans))
        # forward (ends later) claims [4,9]; dispatch keeps [1,4], inside
        # which decode claims [2,4] and dispatch self-time [1,2]; the
        # root's own gaps are [0,1] and [9,10]. Wall partitions exactly.
        assert got == pytest.approx({"r": 2, "fwd": 5, "d": 1, "dec": 2})
        assert sum(got.values()) == pytest.approx(10.0)

    def test_engine_holding_thread_children_leave_the_handler_no_self_time(self):
        # A member's job.predict as the feeding-thread spans tile it
        # (docs/OBSERVABILITY.md §1): the wait at the engine lock, then
        # engine/run tiled by its leaves; host/decode runs on the stage
        # pool under engine/run, concurrent with the thread's decode_wait.
        spans = [
            mk("rpc/job.predict", 0, 10, "r", model="m", lane="n1"),
            mk("engine/lock_wait", 0, 6, "w", parent="r", lane="n1"),
            mk("engine/run", 6, 10, "run", parent="r", lane="n1"),
            mk("engine/resolve_paths", 6, 6.5, "res", parent="run", lane="n1"),
            mk("ingest/decode_wait", 6.5, 8, "dw", parent="run", lane="n1"),
            mk("host/decode", 6.5, 7.9, "dec", parent="run", lane="n1"),
            mk("ingest/stage", 8, 9, "st", parent="run", lane="n1"),
            mk("device/sync_wait", 9, 10, "sync", parent="run", lane="n1"),
        ]
        path = critical_path(spans)
        got = charged_by_span(path)
        # The thread's own wait blocks the tail the pool's decode overlaps:
        # decode is concurrent shadow, the handler and engine/run keep nothing.
        assert got == pytest.approx(
            {"w": 6, "res": 0.5, "dw": 1.5, "st": 1, "sync": 1})
        assert path.total_s == pytest.approx(10.0)
        assert stage_of("engine/lock_wait") == "engine/lock_wait"  # its own label

    def test_child_overhanging_parent_is_clamped(self):
        # A child recorded past its parent's end (clock skew / late flush)
        # must not push shares past 1.0.
        spans = [
            mk("rpc/job.predict", 0, 10, "r", model="m"),
            mk("host/decode", 8, 14, "c", parent="r", lane="n1"),
        ]
        path = critical_path(spans)
        got = charged_by_span(path)
        assert got == pytest.approx({"r": 8, "c": 2})
        assert path.total_s == pytest.approx(10.0)

    def test_multiple_roots_hull_and_gap(self):
        # Two parentless spans: hull [0,10], uncovered middle [4,6] is
        # virtual-root gap time.
        spans = [
            mk("a", 0, 4, "a", lane="n1", model="m"),
            mk("b", 6, 10, "b", lane="n2"),
        ]
        path = critical_path(spans)
        got = charged_by_span(path)
        assert got["a"] == pytest.approx(4.0)
        assert got["b"] == pytest.approx(4.0)
        gap = [sec for s, sec in path.charges if s.name == GAP_STAGE]
        assert sum(gap) == pytest.approx(2.0)
        assert path.total_s == pytest.approx(10.0)

    def test_orphans_charge_under_virtual_root_without_double_count(self):
        # An orphan subtree (parent id never arrived) rides next to the
        # true root: overlap with the covered chain stays shadowed, only
        # the orphan's overhang is charged — shares never exceed 1.0.
        spans = [
            mk("rpc/job.predict", 0, 8, "r", model="m", lane="n1"),
            mk("scheduler/dispatch", 1, 7, "d", parent="r", lane="n1"),
            # orphan: parent "ghost" was dropped by the sampling budget
            mk("host/decode", 2, 9, "o", parent="ghost", lane="n2"),
            mk("gen/step", 3, 5, "os", parent="o", lane="n2"),
        ]
        path = critical_path(spans)
        assert path.orphans == 1
        got = charged_by_span(path)
        # Hull [0,9]: orphan "o" ends last -> claims [2,9] minus its own
        # child's chain; true chain covers [0,2].
        assert path.total_s == pytest.approx(9.0)
        assert sum(got.values()) == pytest.approx(9.0)
        assert got["o"] == pytest.approx((3 - 2) + (9 - 5))
        assert got["os"] == pytest.approx(2.0)

    def test_cycle_guard_terminates(self):
        # A pure 2-cycle has no top-level span: dropped as malformed, not
        # an infinite walk.
        cycle = [
            mk("x", 0, 5, "a", parent="b", model="m"),
            mk("y", 1, 4, "b", parent="a"),
        ]
        assert critical_path(cycle) is None
        # A cycle island next to a real root never hangs the walk either;
        # the rooted chain is charged normally.
        path = critical_path(
            [mk("rpc/job.predict", 0, 10, "r", model="m"), *cycle])
        assert path is not None
        assert path.total_s == pytest.approx(10.0)
        assert sum(charged_by_span(path).values()) == pytest.approx(10.0)

    def test_self_parent_treated_as_root(self):
        path = critical_path([mk("x", 0, 5, "a", parent="a", model="m")])
        assert path.total_s == pytest.approx(5.0)

    def test_empty_and_zero_width(self):
        assert critical_path([]) is None
        assert critical_path([mk("x", 3, 3, "a")]) is None

    def test_model_inheritance_nearest_ancestor(self):
        spans = [
            mk("rpc/job.predict", 0, 10, "r", model="mA"),
            mk("scheduler/dispatch", 1, 9, "d", parent="r"),
            mk("host/decode", 2, 8, "c", parent="d", model="mB"),
            mk("gen/step", 3, 7, "g", parent="c"),
        ]
        path = critical_path(spans)
        assert path.model == "mA"
        by_id = {s.span_id: s.model for s, _ in path.charges}
        assert by_id["d"] == "mA"
        assert by_id["g"] == "mB"


# ---------------------------------------------------------------------------
# Brute-force reference on randomized seeded DAGs
# ---------------------------------------------------------------------------


def _reference_charges(spans: list[Span]) -> dict[str, float]:
    """Forward characterization of the blocking critical path: at each
    instant the charged span is found by descending from the root,
    repeatedly stepping into the latest-ending child active then (ties:
    larger start, then span id). Exact via elementary intervals."""
    by_id = {s.span_id: s for s in spans}
    children: dict[str, list[Span]] = {}
    tops: list[Span] = []
    for s in spans:
        if s.parent_id and s.parent_id != s.span_id and s.parent_id in by_id:
            children.setdefault(s.parent_id, []).append(s)
        else:
            tops.append(s)
    if len(tops) == 1:
        root = tops[0]
    else:
        root = Span(name=GAP_STAGE, start=min(s.start for s in tops),
                    end=max(s.end for s in tops), span_id="(vroot)",
                    parent_id=None, trace_id="t", lane=None, model=None)
        children["(vroot)"] = tops
    points = sorted({p for s in [root, *spans]
                     for p in (s.start, s.end)
                     if root.start <= p <= root.end} | {root.start, root.end})
    out: dict[str, float] = {}
    for lo, hi in zip(points, points[1:]):
        if hi <= lo:
            continue
        u = (lo + hi) / 2.0
        cur = root
        while True:
            active = [c for c in children.get(cur.span_id, ())
                      if c.start <= u < c.end]
            if not active:
                break
            cur = max(active, key=lambda c: (c.end, c.start, c.span_id))
        out[cur.span_id] = out.get(cur.span_id, 0.0) + (hi - lo)
    return out


def _random_tree(rng: random.Random) -> list[Span]:
    spans: list[Span] = []
    counter = [0]

    def grow(parent_id, lo, hi, depth):
        n = rng.randint(0, 3 if depth < 3 else 0)
        for _ in range(n):
            counter[0] += 1
            sid = f"s{counter[0]}"
            a = rng.uniform(lo - 0.5, hi)
            b = a + rng.uniform(0.0, (hi - lo) * rng.uniform(0.2, 1.2))
            if b <= a:
                continue
            spans.append(Span(
                name=rng.choice(["scheduler/dispatch", "host/decode",
                                 "device/forward", "gen/step"]),
                start=round(a, 3), end=round(b, 3), span_id=sid,
                parent_id=parent_id, trace_id="t",
                lane=rng.choice(["n1", "n2", "n3", None]), model=None))
            grow(sid, a, b, depth + 1)

    root = Span(name="rpc/job.predict", start=0.0,
                end=round(rng.uniform(5.0, 20.0), 3), span_id="root",
                parent_id=None, trace_id="t", lane="n1", model="m")
    spans.append(root)
    grow("root", root.start, root.end, 0)
    return spans


@pytest.mark.parametrize("seed", [0, 1000, 2000, 7, 42, 1337])
def test_matches_bruteforce_reference_on_random_dags(seed):
    rng = random.Random(seed)
    for _ in range(25):
        spans = _random_tree(rng)
        path = critical_path(spans)
        ref = _reference_charges(spans)
        got = charged_by_span(path)
        root = spans[0]
        assert path.total_s == pytest.approx(root.end - root.start, abs=1e-9)
        assert sum(got.values()) <= path.total_s + 1e-9  # never > wall
        for sid in set(ref) | set(got):
            assert got.get(sid, 0.0) == pytest.approx(
                ref.get(sid, 0.0), abs=1e-9), (seed, sid, spans)


# ---------------------------------------------------------------------------
# Normalization + one-shot breakdown
# ---------------------------------------------------------------------------


class TestNormalize:
    def test_wire_roundtrip_and_breakdown_shares(self):
        events = [
            {"name": "rpc/job.predict", "start": 0.0, "dur": 10.0,
             "trace": "t1", "span": "r", "parent": None, "lane": "n1",
             "attrs": {"model": "m"}},
            {"name": "scheduler/dispatch", "start": 1.0, "dur": 6.0,
             "trace": "t1", "span": "d", "parent": "r", "lane": "n1",
             "attrs": {"job": "m"}},
            {"name": "host/decode", "start": 2.0, "dur": 4.0,
             "trace": "t1", "span": "c", "parent": "d", "lane": "n2",
             "attrs": {}},
            {"name": "junk-no-ids", "start": 0.0, "dur": 1.0},
        ]
        traces = spans_from_wire(events)
        assert set(traces) == {"t1"}
        bd = breakdown(traces)
        body = bd["m"]
        assert body["requests"] == 1
        assert body["max_lanes"] == 2
        assert sum(ln["share"] for ln in body["lanes"]) == pytest.approx(1.0)
        assert body["total_s"] == pytest.approx(10.0)
        stages = {ln["stage"] for ln in body["lanes"]}
        assert stage_of("host/decode") in stages
        assert stage_of("scheduler/dispatch") in stages

    def test_perfetto_units_are_microseconds(self):
        doc = {"traceEvents": [
            {"ph": "X", "name": "rpc/job.predict", "ts": 0, "dur": 2_000_000,
             "args": {"trace": "t", "span": "r", "model": "m"}},
            {"ph": "M", "name": "meta"},
        ]}
        traces = spans_from_perfetto(doc)
        (span,) = traces["t"]
        assert span.end == pytest.approx(2.0)
        assert span.model == "m"


# ---------------------------------------------------------------------------
# Rolling analyzer: ownership, windows, snapshot
# ---------------------------------------------------------------------------


def _request_events(trace, model, root_lane="n1", t0=0.0, decode_s=2.0,
                    dispatch_member="n2"):
    """A plausible predict request: root -> dispatch -> decode."""
    total = 1.0 + decode_s + 1.0
    return [
        {"name": "host/decode", "start": t0 + 1.5, "dur": decode_s,
         "trace": trace, "span": f"{trace}.c", "parent": f"{trace}.d",
         "lane": dispatch_member, "attrs": {}},
        {"name": "scheduler/dispatch", "start": t0 + 1.0,
         "dur": decode_s + 1.0, "trace": trace, "span": f"{trace}.d",
         "parent": f"{trace}.r", "lane": root_lane,
         "attrs": {"job": model, "member": dispatch_member}},
        {"name": "rpc/job.predict", "start": t0, "dur": total,
         "trace": trace, "span": f"{trace}.r", "parent": None,
         "lane": root_lane, "attrs": {"model": model}},
    ]


class TestAnalyzer:
    def test_charges_once_and_shares_sum_to_one(self):
        clk = [100.0]
        an = CritPathAnalyzer(window_s=10.0, clock=lambda: clk[0])
        for i in range(5):
            an.ingest(_request_events(f"t{i}", "m"))
        snap = an.snapshot()
        body = snap["models"]["m"]
        assert body["requests"] == 5
        assert sum(ln["share"] for ln in body["lanes"]) == pytest.approx(1.0)
        assert snap["counters"]["traces"] == 5
        # Late spans for an already-charged trace are counted, not folded.
        an.ingest(_request_events("t0", "m"))
        snap2 = an.snapshot()
        assert snap2["models"]["m"]["requests"] == 5
        assert snap2["counters"]["late_spans"] == 3

    def test_root_ownership_partition(self):
        clk = [0.0]
        events = _request_events("tx", "m", root_lane="leader")
        owner = CritPathAnalyzer(clock=lambda: clk[0])
        other = CritPathAnalyzer(clock=lambda: clk[0])
        assert owner.ingest(events, own_lane="leader") == 1
        assert other.ingest(events, own_lane="member2") == 0
        # Unlaned roots are claimed only by the claimer (the leader).
        unlaned = _request_events("ty", "m", root_lane=None)
        assert other.ingest(unlaned, own_lane="member2") == 0
        assert owner.ingest(unlaned, own_lane="leader",
                            claim_unlaned=True) == 1

    def test_unrooted_trace_never_charged_and_bounded(self):
        clk = [0.0]
        an = CritPathAnalyzer(clock=lambda: clk[0])
        an.MAX_PENDING = 4
        for i in range(8):  # orphan-only fragments of remote traces
            an.ingest([{"name": "host/decode", "start": 1.0, "dur": 1.0,
                        "trace": f"frag{i}", "span": f"f{i}",
                        "parent": "remote-root", "lane": "n1",
                        "attrs": {}}], own_lane="n1")
        snap = an.snapshot()
        assert snap["models"] == {}
        assert snap["counters"]["unrooted_evicted"] >= 4

    def test_windows_decay_out(self):
        clk = [0.0]
        an = CritPathAnalyzer(window_s=10.0, windows=4,
                              clock=lambda: clk[0])
        an.ingest(_request_events("t1", "m"))
        assert "m" in an.snapshot()["models"]
        clk[0] += 10.0 * 5  # beyond the window horizon
        assert an.snapshot()["models"] == {}

    def test_snapshot_is_jsonable(self):
        import json
        an = CritPathAnalyzer(clock=lambda: 0.0)
        an.ingest(_request_events("t1", "m"))
        json.dumps(an.snapshot())


class TestFleetFold:
    def test_fold_and_culprit(self):
        clk = [0.0]
        fleet = FleetCritPath()
        for member, decode_s in (("n1", 0.5), ("n2", 6.0)):
            an = CritPathAnalyzer(clock=lambda: clk[0])
            for i in range(4):
                an.ingest(_request_events(
                    f"{member}.t{i}", "m", root_lane=member,
                    dispatch_member=member, decode_s=decode_s))
            fleet.fold(member, an.snapshot())
        table = fleet.table()
        assert table["members_reporting"] == 2
        body = table["models"]["m"]
        assert body["requests"] == 8
        assert sum(ln["share"] for ln in body["lanes"]) == pytest.approx(1.0)
        culprit = fleet.culprit("m")
        assert culprit is not None
        assert culprit["stage"] == "decode"
        assert culprit["member"] == "n2"
        assert 0.0 < culprit["critpath_share"] <= 1.0
        assert fleet.culprit("missing") is None
        fleet.forget("n2")
        assert fleet.table()["members_reporting"] == 1


# ---------------------------------------------------------------------------
# Drift sentinel
# ---------------------------------------------------------------------------


def _table(q_samples: dict[tuple[str, str, str], list[float]]):
    models: dict = {}
    for (model, stage, member), samples in q_samples.items():
        body = models.setdefault(model, {"requests": 0, "total_s": 0.0,
                                         "lanes": []})
        body["lanes"].append({
            "stage": stage, "member": member,
            "crit_s": sum(samples), "share": 0.5, "n": len(samples),
            "recent_n": len(samples), "samples": list(samples),
            "p50": 0.0, "p99": 0.0,
        })
    return {"models": models}


class TestSentinel:
    def _mk(self, **kw):
        events: list[tuple[str, dict]] = []
        forces: list[float] = []
        replans: list[str] = []
        s = DriftSentinel(
            min_samples=5, confirm_windows=3, drift_factor=2.0,
            clear_factor=1.3,
            flight_note=lambda kind, **f: events.append((kind, f)),
            force_sample=forces.append,
            request_replan=replans.append, **kw)
        return s, events, forces, replans

    @pytest.mark.parametrize("seed", [0, 1000, 2000])
    def test_drift_alert_within_confirm_windows(self, seed):
        rng = random.Random(seed)
        s, events, forces, replans = self._mk()
        key = ("m", "decode", "n2")
        healthy = lambda: [rng.uniform(0.9, 1.1) for _ in range(10)]
        for _ in range(6):  # learn the baseline
            s.tick(_table({key: healthy()}))
        assert s.alerting() == []
        slow = lambda: [rng.uniform(4.5, 5.5) for _ in range(10)]  # 5x
        ticks_to_alert = 0
        for i in range(5):
            fired = s.tick(_table({key: slow()}))
            if fired:
                ticks_to_alert = i + 1
                break
        assert ticks_to_alert == 3  # exactly confirm_windows
        assert s.alerting() == [key]
        (desc,) = [f for k, f in events if k == "latency_drift"]
        assert (desc["model"], desc["stage"], desc["member"]) == key
        assert desc["factor"] > 2.0
        assert forces == [s.force_sample_s]
        assert any(k == "drift_force_sample" for k, _ in events)
        # Localized to one member -> replan requested.
        assert replans == ["latency_drift:m:decode:n2"]
        assert any(k == "drift_replan_request" for k, _ in events)

    def test_min_samples_floor(self):
        s, events, *_ = self._mk()
        key = ("m", "decode", "n2")
        for _ in range(4):
            s.tick(_table({key: [1.0, 1.0, 1.0]}))  # n=3 < 5: never judged
        for _ in range(6):
            s.tick(_table({key: [100.0] * 3}))
        assert s.alerting() == []
        assert events == []

    def test_baseline_frozen_during_drift_and_hysteresis_clear(self):
        s, events, _, _ = self._mk()
        key = ("m", "decode", "n2")
        for _ in range(4):
            s.tick(_table({key: [1.0] * 8}))
        base = s.status()["lanes"][0]["baseline_s"]
        for _ in range(3):
            s.tick(_table({key: [5.0] * 8}))
        st = s.status()["lanes"][0]
        assert st["alert"] is True
        assert st["baseline_s"] == pytest.approx(base)  # frozen, no launder
        # One healthy tick does not clear (hysteresis)...
        s.tick(_table({key: [1.0] * 8}))
        assert s.alerting() == [key]
        # ...confirm_windows healthy ticks do.
        for _ in range(2):
            s.tick(_table({key: [1.0] * 8}))
        assert s.alerting() == []
        assert any(k == "latency_drift_clear" for k, _ in events)

    def test_fleetwide_drift_does_not_replan(self):
        s, _, _, replans = self._mk()
        keys = [("m", "decode", f"n{i}") for i in range(3)]
        for _ in range(4):
            s.tick(_table({k: [1.0] * 8 for k in keys}))
        for _ in range(4):
            s.tick(_table({k: [5.0] * 8 for k in keys}))
        assert len(s.alerting()) == 3  # all three members drifted
        assert replans == []  # not placement-fixable: no replan

    def test_config_validation(self):
        with pytest.raises(ValueError):
            DriftSentinel(clear_factor=3.0, drift_factor=2.0)
        with pytest.raises(ValueError):
            DriftSentinel(baseline_decay=1.5)

    def test_status_jsonable(self):
        import json
        s, *_ = self._mk()
        s.tick(_table({("m", "decode", "n1"): [1.0] * 8}))
        json.dumps(s.status())


class TestDriftSoak:
    """The ISSUE-20 acceptance soak: the pinned drift scenario (sim
    fabric, virtual clock, 5x decode slowdown on exactly one member at
    half-replay) must produce — reproducibly across the chaos-seed
    matrix — a sentinel alert naming (model, decode, that member) within
    3 fast windows, the next fast-burn alert carrying the same culprit,
    a forced-sampling window, and a placement replan request, all read
    back from the flight recorder."""

    @pytest.mark.parametrize("seed", [0, 1000, 2000])
    def test_drift_detected_and_attributed(self, seed):
        from dmlc_tpu.loadgen import (
            DRIFT_DETECT_FAST_WINDOWS,
            DRIFT_FAST_WINDOW_S,
            DRIFT_MEMBER_INDEX,
            DRIFT_SCRAPE_INTERVAL_S,
            DRIFT_STAGE,
            drift_sentinel_harness,
            validate_slo_cert,
        )
        from tools.slo_cert import critpath_failures

        harness = drift_sentinel_harness(4, seed)
        cert = harness.run()
        assert validate_slo_cert(cert) == []
        # The exact verdicts CI's drift leg gates on (tools/slo_cert.py
        # --critpath) must hold for the pytest matrix too.
        assert critpath_failures(cert) == []

        member = harness.member_addrs[DRIFT_MEMBER_INDEX]
        events = harness.flight.to_wire()["events"]

        # 1. Injection recorded, then the sentinel names the culprit.
        (injected,) = [e for e in events if e["kind"] == "drift_injected"]
        assert injected["member"] == member
        assert injected["stage"] == DRIFT_STAGE
        drifts = [e for e in events if e["kind"] == "latency_drift"]
        assert drifts, "sentinel never alerted"
        first = drifts[0]
        assert (first["model"], first["stage"], first["member"]) == (
            "resnet50", DRIFT_STAGE, member)
        assert first["factor"] > harness.sentinel.drift_factor

        # 2. Within 3 fast windows of the injection.
        bound_s = DRIFT_DETECT_FAST_WINDOWS * DRIFT_FAST_WINDOW_S
        assert first["t"] - injected["t"] <= bound_s + DRIFT_SCRAPE_INTERVAL_S

        # 3. The next fast-burn alert carries the same culprit.
        burns_after = [e for e in events if e["kind"] == "slo_fast_burn"
                       and e["t"] >= first["t"]]
        assert burns_after, "no burn alert after the drift alert"
        assert burns_after[0]["culprit_member"] == member
        assert burns_after[0]["culprit_stage"] == DRIFT_STAGE
        assert 0.0 < burns_after[0]["critpath_share"] <= 1.0

        # 4. Forced sampling opened, replan requested, both recorded.
        assert any(e["kind"] == "drift_force_sample" and e["member"] == member
                   for e in events)
        (replan,) = [e for e in events if e["kind"] == "drift_replan_request"]
        assert replan["reason"] == f"latency_drift:resnet50:{DRIFT_STAGE}:{member}"
        assert harness.replan_requests == [replan["reason"]]

        # 5. The folded table blames the slowed member's decode lane above
        # every other lane, and shares sum to exactly 1.
        body = cert["critpath"]["table"]["models"]["resnet50"]
        top = body["lanes"][0]
        assert (top["stage"], top["member"]) == (DRIFT_STAGE, member)
        assert sum(ln["share"] for ln in body["lanes"]) == pytest.approx(1.0)
