"""Fleet-wide distributed tracing: propagation, merge, and the acceptance pin.

Two layers:

- Sim-fabric tests (deterministic, no sockets): the ``t`` frame field
  carries ``(trace_id, span_id)`` across hops, handlers' ``rpc/<method>``
  spans parent correctly through nested calls, typed failures
  (DeadlineExceeded/Overloaded) still record spans and leak no ambient
  context, and DISABLED tracing adds no ``t`` field at all (zero frame
  bytes).

- The localcluster acceptance test (ISSUE 5): a real predict run over
  TCP with tracing enabled yields ONE merged Chrome/Perfetto trace in
  which leader-dispatch, member-predict, and SDFS-pull spans from >= 3
  distinct nodes share a single trace_id with correct parent edges and
  clock-aligned, non-negative child offsets.
"""

from __future__ import annotations

import json

import pytest

from dmlc_tpu.cluster import observe, tracectx
from dmlc_tpu.cluster.localcluster import (
    make_synsets,
    start_local_cluster,
    stop_local_cluster,
    wait_until,
)
from dmlc_tpu.cluster.rpc import (
    DeadlineExceeded,
    Overloaded,
    SimRpcNetwork,
)
from dmlc_tpu.cluster.sdfs import placement_order
from dmlc_tpu.utils import tracing
from dmlc_tpu.utils.tracing import traced_methods, tracer


@pytest.fixture(autouse=True)
def fresh_tracer():
    """Every test starts from a clean, enabled-off global tracer and ends
    without leaking enablement into the rest of the suite."""
    tracer.reset()
    tracer.enabled = False
    yield
    tracer.enabled = False
    tracer.reset()


def spans_by_name() -> dict:
    return {e["name"]: e for e in tracer.events_wire()}


# ---------------------------------------------------------------------------
# Sim-fabric propagation
# ---------------------------------------------------------------------------


def make_chain(net: SimRpcNetwork):
    """leader -> member -> storage, each hop a traced RPC service."""
    net.serve("storage", traced_methods({
        "sdfs.fetch": lambda p: {"data": b"x"},
    }))

    def predict(p):
        net.client("member").call("storage", "sdfs.fetch", {}, timeout=5.0)
        return {"predictions": [0]}

    net.serve("member", traced_methods({"job.predict": predict}))

    def dispatch(p):
        return net.client("leader").call("member", "job.predict", {}, timeout=5.0)

    net.serve("leader", traced_methods({"job.start": dispatch}))


def test_nested_hops_share_one_trace_with_parent_links():
    net = SimRpcNetwork()
    make_chain(net)
    tracer.enabled = True
    with tracer.span("client/predict"):
        net.client("cli").call("leader", "job.start", {}, timeout=10.0)
    spans = spans_by_name()
    assert set(spans) == {
        "client/predict", "rpc/job.start", "rpc/job.predict", "rpc/sdfs.fetch"
    }
    trace_ids = {e["trace"] for e in spans.values()}
    assert len(trace_ids) == 1
    # Parent edges mirror the call tree exactly.
    assert spans["client/predict"]["parent"] is None
    assert spans["rpc/job.start"]["parent"] == spans["client/predict"]["span"]
    assert spans["rpc/job.predict"]["parent"] == spans["rpc/job.start"]["span"]
    assert spans["rpc/sdfs.fetch"]["parent"] == spans["rpc/job.predict"]["span"]
    # Lanes: each hop attributed to the node that served it.
    assert spans["rpc/job.start"]["lane"] == "leader"
    assert spans["rpc/job.predict"]["lane"] == "member"
    assert spans["rpc/sdfs.fetch"]["lane"] == "storage"


def test_member_predict_children_and_pool_work_stay_in_the_callers_trace(tmp_path):
    """Below rpc/job.predict on a real EngineBackend (docs/OBSERVABILITY.md
    §1, feeding threads): the wait for the ahead slot, the ahead work, the
    wait at the engine lock and engine/run are the handler's children, and
    the decodes handed to the stage pool keep the caller's trace, the span
    they were started under as parent (engine/ahead: a two-batch shard
    starts both before the lock), and the member's lane — they used to be
    the roots of traces of their own, with no lane."""
    from dmlc_tpu.scheduler.worker import EngineBackend, PredictWorker
    from dmlc_tpu.utils import corpus
    import tiny_model  # noqa: F401  (registers "tinynet")

    data_dir, _ = corpus.generate(tmp_path, n_classes=16, images_per_class=1, size=32)
    synsets = sorted(d.name for d in data_dir.iterdir())
    backend = EngineBackend("tinynet", data_dir, batch_size=8)
    backend.warmup()
    net = SimRpcNetwork()
    net.serve("member", PredictWorker({"tinynet": backend}).methods())
    tracer.enabled = True
    with tracer.span("scheduler/dispatch"):
        reply = net.client("leader").call(
            "member", "job.predict", {"model": "tinynet", "synsets": synsets}, timeout=60.0)
    assert len(reply["predictions"]) == len(synsets)
    events = tracer.events_wire()
    assert len({e["trace"] for e in events}) == 1
    one = {e["name"]: e for e in events}
    rpc, ahead, run = one["rpc/job.predict"], one["engine/ahead"], one["engine/run"]
    assert rpc["parent"] == one["scheduler/dispatch"]["span"]
    for child in ("engine/ahead_wait", "engine/ahead", "engine/lock_wait", "engine/run"):
        assert one[child]["parent"] == rpc["span"], child
    for leaf in ("engine/resolve_paths", "engine/ahead_submit"):
        assert one[leaf]["parent"] == ahead["span"] and one[leaf]["lane"] == "member", leaf
    for leaf in ("ingest/decode_wait", "ingest/stage", "ingest/dispatch", "device/sync_wait",
                 "ingest/collect", "engine/collect"):
        assert one[leaf]["parent"] == run["span"] and one[leaf]["lane"] == "member", leaf
    assert run["attrs"]["ahead"] == 2
    decodes = [e for e in events if e["name"] == "host/decode"]
    assert len(decodes) == 2  # 16 images, batches of 8: the stream path
    for d in decodes:
        assert d["parent"] == ahead["span"] and d["lane"] == "member" and d["tid"] != run["tid"]


def test_every_frame_carries_the_same_trace_id():
    net = SimRpcNetwork()
    make_chain(net)
    tracer.enabled = True
    with tracer.span("root"):
        net.client("cli").call("leader", "job.start", {}, timeout=10.0)
    assert len(net.frames) == 3
    tids = {f["t"][0] for f in net.frames}
    assert len(tids) == 1
    # Each hop's `t` names the CALLER's span (the remote parent), so the
    # three frames carry three different span ids under one trace.
    sids = {f["t"][1] for f in net.frames}
    assert len(sids) == 3


def test_disabled_tracing_adds_zero_frame_bytes():
    net = SimRpcNetwork()
    make_chain(net)
    assert not tracer.enabled
    net.client("cli").call("leader", "job.start", {}, timeout=10.0)
    assert net.frames, "sanity: frames recorded"
    assert all("t" not in f for f in net.frames)
    assert tracer.events_wire() == []


def test_typed_errors_still_record_spans_and_leak_no_context():
    net = SimRpcNetwork()

    def overloaded(p):
        raise Overloaded("queue full", retry_after_s=0.1)

    def expired(p):
        raise DeadlineExceeded("budget exhausted")

    net.serve("m", traced_methods({"x.shed": overloaded, "x.late": expired}))
    tracer.enabled = True
    with tracer.span("root"):
        with pytest.raises(Overloaded):
            net.client("c").call("m", "x.shed", {}, timeout=5.0)
        with pytest.raises(DeadlineExceeded):
            net.client("c").call("m", "x.late", {}, timeout=5.0)
    assert tracectx.current() is None, "ambient context leaked past the spans"
    spans = spans_by_name()
    root = spans["root"]
    for name in ("rpc/x.shed", "rpc/x.late"):
        assert spans[name]["trace"] == root["trace"]
        assert spans[name]["parent"] == root["span"]


def test_expired_budget_rejected_before_handler_keeps_context_clean():
    net = SimRpcNetwork()
    net.serve("m", traced_methods({"x.go": lambda p: {}}))
    net.set_latency("c", "m", 10.0)  # transit eats the whole budget
    tracer.enabled = True
    with tracer.span("root"):
        with pytest.raises(Exception):
            net.client("c").call("m", "x.go", {}, timeout=1.0)
    assert tracectx.current() is None
    assert "rpc/x.go" not in spans_by_name()  # the method never ran


# ---------------------------------------------------------------------------
# Clock alignment + merge (pure functions, scripted offsets)
# ---------------------------------------------------------------------------


def test_merge_aligns_clocks_and_clamps_residual_skew():
    # Node B's tracer clock runs 5.0s AHEAD of the collector's; its span is
    # a child of A's span. Aligned, the child starts 10ms after the parent.
    per_node = {
        "a:1": {
            "offset": 0.0, "rtt": 0.001,
            "dump": {"events": [{
                "name": "parent", "start": 1.000, "dur": 0.100, "tid": 1,
                "trace": "t1", "span": "s1", "parent": None, "lane": "a:1",
                "attrs": {},
            }], "dropped": 0},
        },
        "b:2": {
            "offset": 5.0, "rtt": 0.001,
            "dump": {"events": [{
                "name": "child", "start": 6.010, "dur": 0.050, "tid": 2,
                "trace": "t1", "span": "s2", "parent": "s1", "lane": "b:2",
                "attrs": {},
            }, {
                # Residual skew artifact: aligned start would precede the
                # parent by 2ms — must be clamped to the parent's start.
                "name": "skewed", "start": 5.998, "dur": 0.010, "tid": 2,
                "trace": "t1", "span": "s3", "parent": "s1", "lane": "b:2",
                "attrs": {},
            }], "dropped": 0},
        },
    }
    doc = observe.merge_fleet_trace(per_node)
    events = {e["name"]: e for e in doc["traceEvents"] if e.get("ph") == "X"}
    meta = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert {m["args"]["name"] for m in meta} == {"a:1", "b:2"}
    assert events["parent"]["pid"] != events["child"]["pid"]
    assert events["child"]["ts"] == pytest.approx(
        events["parent"]["ts"] + 10_000, abs=1.0
    )
    assert events["skewed"]["ts"] == pytest.approx(events["parent"]["ts"])
    assert doc["otherData"]["skew_clamped_children"] == 1


def test_measure_clock_offset_midpoint():
    net = SimRpcNetwork()
    remote_now = 100.0
    net.serve("n", traced_methods({"obs.clock": lambda p: {"now": remote_now}}))
    # Local virtual clock advances 0.2s per call (scripted link latency
    # charges transit on both the request and nothing on reply — midpoint
    # still lands between t0 and t1).
    net.set_latency("c", "n", 0.2)
    client = net.client("c")
    offset, rtt = observe.measure_clock_offset(
        client, "n", local_now=net.clock, samples=3
    )
    assert rtt == pytest.approx(0.2)
    # t0 = now, t1 = now + 0.2 per probe; remote stays 100.
    assert offset == pytest.approx(remote_now - (net.now - 0.2 + net.now) / 2, abs=0.5)


# ---------------------------------------------------------------------------
# Acceptance: localcluster predict -> one merged >=3-node trace
# ---------------------------------------------------------------------------


def test_fleet_trace_three_nodes_one_trace(tmp_path):
    """ISSUE 5 acceptance: leader-dispatch, member-predict, and SDFS-pull
    spans from >= 3 distinct nodes share a single trace_id with correct
    parent edges and non-negative child offsets, in a merged trace that
    loads as Chrome/Perfetto JSON."""
    nodes: list = []
    blob_name = {"name": None}

    def make_backends(i: int):
        def predict(synsets):
            # Every shard pulls the published blob THROUGH SDFS: leader
            # directory lookup + member-to-member fetch, all under the
            # ambient trace of the rpc/job.predict span.
            nodes[i].sdfs.get_bytes(blob_name["name"])
            return [int(s[1:]) for s in synsets]

        return {"resnet18": predict}

    synsets = make_synsets(tmp_path / "synsets.txt", 24)
    nodes.extend(start_local_cluster(
        tmp_path, 3,
        backends=make_backends,
        synset_path=synsets,
        job_models=["resnet18"],
        replication_factor=2,
        dispatch_shard_size=4,
    ))
    try:
        leader = nodes[0]
        members = sorted(leader.active_member_addrs())
        assert len(members) == 3
        # Choose a blob whose hash placement starts AWAY from the leader's
        # member store: its replicas then live on the two non-leader nodes,
        # so a shard predicted by the node that fetches from the OTHER
        # replica holder touches three distinct lanes in one trace.
        leader_member = leader.self_member_addr
        name = next(
            f"corpus/blob{i}" for i in range(256)
            if placement_order(f"corpus/blob{i}", members)[-1] == leader_member
        )
        blob_name["name"] = name
        reply = nodes[1].sdfs.put_bytes(b"fixture-bytes" * 64, name)
        assert leader_member not in reply["replicas"]

        # The probe loops need a tick to agree on who leads before
        # `predict` can land (a deferring standby refuses it).
        wait_until(
            lambda: leader.tracker.current == leader.self_leader_addr,
            msg="tracker converged on the promoted leader",
        )
        tracing.enable()
        tracer.reset()
        leader.predict()
        wait_until(
            lambda: all(
                r["finished"] >= r["total"]
                for r in leader.jobs_report().values()
            ),
            timeout=60.0,
            msg="all shards finished",
        )

        out = tmp_path / "fleet_trace.json"
        doc = observe.export_fleet_trace(leader.rpc, members, out)
        tracing.disable()

        # The artifact is valid Perfetto/Chrome JSON.
        loaded = json.loads(out.read_text())
        events = [e for e in loaded["traceEvents"] if e.get("ph") == "X"]
        meta = [e for e in loaded["traceEvents"] if e.get("ph") == "M"]
        assert {m["args"]["name"] for m in meta} == set(members)
        assert doc["otherData"]["nodes"].keys() == set(members)

        # Index spans by trace.
        by_trace: dict[str, list[dict]] = {}
        for e in events:
            t = e["args"].get("trace")
            if t:
                by_trace.setdefault(t, []).append(e)

        def names(evs):
            return {e["name"] for e in evs}

        # THE acceptance trace: dispatch + predict + SDFS pull, >= 3 pids.
        best = None
        for t, evs in by_trace.items():
            pids = {e["pid"] for e in evs}
            if (
                len(pids) >= 3
                and "scheduler/dispatch" in names(evs)
                and "rpc/job.predict" in names(evs)
                and {"sdfs/pull", "rpc/sdfs.fetch_meta"} & names(evs)
            ):
                best = evs
                break
        assert best is not None, (
            "no trace spanned 3 nodes with dispatch+predict+pull; traces: "
            + str({t: sorted(names(evs)) for t, evs in by_trace.items()})
        )

        # Parent edges are correct within the merged trace.
        spans = {e["args"]["span"]: e for e in best}
        dispatch = next(e for e in best if e["name"] == "scheduler/dispatch")
        predict = next(e for e in best if e["name"] == "rpc/job.predict")
        assert dispatch["args"].get("parent") is None  # trace root
        assert predict["args"]["parent"] == dispatch["args"]["span"]
        pulls = [e for e in best if e["name"] == "sdfs/pull"]
        assert pulls and all(
            p["args"]["parent"] in spans for p in pulls
        )
        # Clock-aligned, non-negative child offsets: no child starts before
        # its parent anywhere in the merged document.
        all_spans = {
            e["args"]["span"]: e for e in events if e["args"].get("span")
        }
        violations = [
            (e["name"], e["ts"] - all_spans[e["args"]["parent"]]["ts"])
            for e in events
            if e["args"].get("parent") in all_spans
            and e["ts"] < all_spans[e["args"]["parent"]]["ts"]
        ]
        assert not violations, violations
    finally:
        tracing.disable()
        stop_local_cluster(nodes)


def test_fleet_metrics_scrape_and_prometheus(tmp_path):
    """The leader's probe-cadence scrape surfaces every member's counters
    through obs.fleet, and the Prometheus rendering labels them by node."""
    nodes = start_local_cluster(
        tmp_path, 3, synset_path=make_synsets(tmp_path / "s.txt", 8),
        job_models=["resnet18"],
    )
    try:
        leader = nodes[0]
        members = set(leader.active_member_addrs())
        wait_until(
            lambda: set(leader.fleet_metrics) == members,
            timeout=30.0,
            msg="leader scraped every member",
        )
        reply = nodes[1].rpc.call(leader.self_leader_addr, "obs.fleet", {}, timeout=5.0)
        assert set(reply["fleet"]) == members
        for addr, snap in reply["fleet"].items():
            assert "counters" in snap["metrics"]
            assert "gauges" in snap["metrics"]
        text = nodes[1].rpc.call(
            leader.self_leader_addr, "obs.fleet_prom", {}, timeout=5.0
        )["text"]
        for addr in members:
            assert f'node="{addr}"' in text
    finally:
        stop_local_cluster(nodes)
