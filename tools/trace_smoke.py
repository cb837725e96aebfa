"""CI trace smoke (tools/ci_check.sh): prove the fleet tracing pipeline
end to end on a real localcluster.

Starts a 3-node localcluster on loopback (real TCP + gossip), enables
tracing, runs the predict workload to completion, drives one ``generate``
request through the continuous-batching worker, collects the merged fleet
trace through the obs.* RPC surface (clock alignment included), and
asserts the committed contract:

- the merged artifact loads as Chrome/Perfetto trace-event JSON,
- spans from >= 2 distinct node lanes (pids) share one trace_id,
- no child span starts before its parent after alignment,
- the generate request produced ``gen/step`` spans PARENTED into its
  ``rpc/job.generate`` trace (docs/GENERATE.md's tracing contract), each
  with its ``gen/step_sync`` child, and a ``gen/wait`` beside every
  ``gen/prefill`` (the feeding-thread spans, docs/OBSERVABILITY.md §1),
- the leader's fleet scrape surfaces the device-plane gauges
  (docs/OBSERVABILITY.md §8): compile census with real compiles counted,
  per-model ``mfu_*`` gauges, and the ``hbm_*`` keys (None-valued on CPU,
  but PRESENT — graceful degradation, not absence),
- the same merged trace yields a critical-path breakdown
  (docs/OBSERVABILITY.md §9): a non-empty path crossing >= 2 node lanes,
  stage shares partitioning the charged time (sum ~1.0), and the one
  DELIBERATELY SLOWED member surfacing as the top critical-path
  contributor — the attribution names the real bottleneck, not just a
  stage histogram.

Exit 0 on success; nonzero with a diagnostic otherwise.
"""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

try:
    import _bootstrap  # noqa: F401  (repo-root sys.path for standalone runs)
except ImportError:
    pass  # invoked as a module from the repo root


SLOW_NODE = 2        # non-leader member with a deliberately slow backend
SLOW_SECONDS = 0.25  # per shard — dwarfs every healthy sub-ms span


def main() -> int:
    import time

    from dmlc_tpu.cluster import observe
    from dmlc_tpu.cluster.localcluster import (
        echo_backend,
        make_synsets,
        start_local_cluster,
        stop_local_cluster,
        wait_until,
    )
    from dmlc_tpu.utils import tracing

    def slow_echo(synsets):
        time.sleep(SLOW_SECONDS)
        return echo_backend(synsets)

    tmp = Path(tempfile.mkdtemp(prefix="trace_smoke_"))
    nodes = start_local_cluster(
        tmp, 3,
        synset_path=make_synsets(tmp / "synsets.txt", 24),
        job_models=["resnet18"],
        dispatch_shard_size=4,
        generate_models=["lm_small"],
        gen_page_size=8,
        gen_num_pages=64,
        gen_max_prefill=16,
        eager_load=False,  # the one lm_small engine builds on first use
        backends=lambda i: {
            "resnet18": slow_echo if i == SLOW_NODE else echo_backend
        },
    )
    try:
        leader = nodes[0]
        wait_until(
            lambda: leader.tracker.current == leader.self_leader_addr,
            msg="tracker converged on the promoted leader",
        )
        tracing.enable()
        tracing.tracer.reset()
        leader.predict()
        wait_until(
            lambda: all(
                r["finished"] >= r["total"] for r in leader.jobs_report().values()
            ),
            timeout=60.0,
            msg="workload finished",
        )
        # One generation through the continuous-batching worker: its
        # gen/step spans must land in the fleet trace, parented under the
        # request's rpc/job.generate span.
        gen_reply = leader.generate("lm_small", [1, 2, 3], max_new_tokens=4)
        assert len(gen_reply["tokens"]) == 4, gen_reply

        # Survivable-generation contract (docs/GENERATE.md §Migration): a
        # ROUTED generate drained off its member mid-stream must keep ONE
        # trace id across the migration — gen/* spans from two distinct
        # member lanes parented into the leader's rpc/job.generate trace.
        router = leader.genrouter
        assert router is not None, "promoted leader has no session router"
        mig_reply = leader.rpc.call(
            leader.tracker.current, "job.generate",
            {"model": "lm_small", "prompt": [4, 5], "max_new_tokens": 48,
             "seed": 11},
            timeout=30.0,
        )
        mig_gen_id = mig_reply["gen_id"]
        mig_tokens: list[int] = []
        mig_acked = 0

        def _poll_once() -> dict:
            nonlocal mig_acked
            r = leader.rpc.call(
                leader.tracker.current, "job.generate_poll",
                {"gen_id": mig_gen_id, "ack": mig_acked}, timeout=30.0,
            )
            for seq, chunk in sorted(r.get("chunks", [])):
                if seq <= mig_acked:
                    continue
                mig_acked = seq
                mig_tokens.extend(int(t) for t in chunk)
            return r

        wait_until(
            lambda: bool(_poll_once() and mig_tokens),
            timeout=60.0, msg="first routed token before the drain",
        )
        placed = next(s["member"] for s in router.sessions_table()
                      if s["id"] == mig_gen_id)
        router.drain(placed, deadline_s=0.0, reason="trace_smoke")
        wait_until(
            lambda: (router.tick() or True) and any(
                s["id"] == mig_gen_id and s["migrations"] >= 1
                for s in router.sessions_table()
            ),
            timeout=30.0, msg="drained session migrated",
        )
        wait_until(
            lambda: bool((r := _poll_once()).get("done")
                         and not r.get("chunks")),
            timeout=60.0, msg="migrated stream finished",
        )
        assert len(mig_tokens) == 48, (
            f"{len(mig_tokens)} tokens across the migration (want exactly "
            "48: a shortfall is a lost token, an excess a duplicate)"
        )
        mig_wire = router._sessions[mig_gen_id].trace
        mig_trace = mig_wire[0] if mig_wire else None
        router.undrain(placed)

        out = tmp / "fleet_trace.json"
        observe.export_fleet_trace(
            leader.rpc, sorted(leader.active_member_addrs()), out
        )
        # Live cost profiles (docs/OBSERVABILITY.md §5): the completed
        # workload must have grown dispatch lanes for >= 2 members in the
        # leader's profiler, served over the obs.profile verb.
        profile = leader.rpc.call(
            leader.self_member_addr, "obs.profile", {}, timeout=5.0
        )
        profile_members = {
            member
            for lanes in profile.get("profiles", {}).values()
            for member in lanes
        }

        # Device-plane telemetry (docs/OBSERVABILITY.md §8): the completed
        # predict compiled real programs, so the next fleet scrape must
        # carry the devicemon gauges for every member — compile census with
        # compiles counted, an mfu_* gauge per registered model, and the
        # hbm_* keys (None on CPU backends, but present).
        def _device_members() -> list[str]:
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

        n_members = len(leader.active_member_addrs())
        wait_until(
            lambda: len(_device_members()) >= n_members,
            timeout=30.0,
            msg="devicemon gauges in the fleet scrape for every member",
        )
        device_members = _device_members()
        slow_addr = nodes[SLOW_NODE].self_member_addr
    finally:
        tracing.disable()
        stop_local_cluster(nodes)

    if len(profile_members) < 2:
        print(
            "trace smoke FAILED: obs.profile grew lanes for "
            f"{sorted(profile_members)} (need >= 2 members); the dispatch "
            "path is not feeding the cost profiler",
            file=sys.stderr,
        )
        return 1

    doc = json.loads(out.read_text())  # must load as Perfetto JSON
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    by_trace: dict[str, list[dict]] = {}
    for e in events:
        t = e["args"].get("trace")
        if t:
            by_trace.setdefault(t, []).append(e)
    multi_node = {
        t: evs for t, evs in by_trace.items() if len({e["pid"] for e in evs}) >= 2
    }
    if not multi_node:
        print(
            "trace smoke FAILED: no trace crossed >= 2 node lanes; traces: "
            + str({t: sorted({e['name'] for e in evs}) for t, evs in by_trace.items()}),
            file=sys.stderr,
        )
        return 1
    starts = {e["args"]["span"]: e["ts"] for e in events if e["args"].get("span")}
    bad = [
        (e["name"], e["ts"] - starts[e["args"]["parent"]])
        for e in events
        if e["args"].get("parent") in starts and e["ts"] < starts[e["args"]["parent"]]
    ]
    if bad:
        print(f"trace smoke FAILED: children before parents: {bad}", file=sys.stderr)
        return 1
    # Generation contract: the generate request produced gen/step spans,
    # and every one is PARENTED (carries a parent edge) inside the same
    # trace as an rpc/job.generate span.
    gen_steps = [e for e in events if e["name"] == "gen/step"]
    gen_rpc_traces = {
        e["args"].get("trace") for e in events if e["name"] == "rpc/job.generate"
    }
    if not gen_steps:
        print("trace smoke FAILED: no gen/step spans recorded", file=sys.stderr)
        return 1
    orphans = [
        e for e in gen_steps
        if not e["args"].get("parent") or e["args"].get("trace") not in gen_rpc_traces
    ]
    if orphans:
        print(
            f"trace smoke FAILED: {len(orphans)}/{len(gen_steps)} gen/step "
            "span(s) not parented into a rpc/job.generate trace",
            file=sys.stderr,
        )
        return 1
    # Feeding-thread contract (docs/OBSERVABILITY.md §1): the blocking read
    # of every step is a gen/step_sync CHILD of the gen/step that read it
    # (a busy period's first turn dispatches a step and reads none: no
    # ``slots``, no child), every prefill run has its gen/prefill_sync, and
    # every generate trace that reached a prefill shows how long the request
    # waited for it (gen/wait).
    for child, parent in (("gen/step_sync", "gen/step"),
                          ("gen/prefill_sync", "gen/prefill")):
        parents = [e for e in events if e["name"] == parent]
        kids: dict[str, int] = {}
        for e in events:
            if e["name"] == child:  # the engine's warm-up syncs too, under no step
                kids[e["args"].get("parent")] = kids.get(e["args"].get("parent"), 0) + 1
        childless = [e for e in parents if kids.get(e["args"]["span"], 0)
                     != (parent == "gen/prefill" or "slots" in e["args"])]
        if not parents or childless:
            print(
                f"trace smoke FAILED: of {len(parents)} {parent} span(s) "
                f"{len(childless)} lack their one {child} child",
                file=sys.stderr,
            )
            return 1
    prefill_traces = {e["args"].get("trace") for e in events
                      if e["name"] == "gen/prefill"}
    wait_traces = {e["args"].get("trace") for e in events
                   if e["name"] == "gen/wait"}
    if not prefill_traces or prefill_traces - wait_traces:
        print(
            "trace smoke FAILED: gen/prefill without a gen/wait in the same "
            f"trace: {sorted(t for t in prefill_traces - wait_traces if t)}",
            file=sys.stderr,
        )
        return 1
    # Migration contract: the drained generate's trace must hold gen/*
    # spans from >= 2 member lanes AND its rpc/job.generate root — one
    # trace id surviving the mid-stream move between members.
    mig_events = [e for e in events if e["args"].get("trace") == mig_trace]
    mig_gen_pids = {e["pid"] for e in mig_events
                    if e["name"].startswith("gen/")}
    mig_has_root = any(e["name"] == "rpc/job.generate" for e in mig_events)
    if mig_trace is None or len(mig_gen_pids) < 2 or not mig_has_root:
        print(
            "trace smoke FAILED: migrated generate's trace "
            f"{mig_trace!r} has gen/* spans on {len(mig_gen_pids)} member "
            f"lane(s) (want >= 2) with rpc/job.generate root "
            f"present={mig_has_root} — the migration forked or dropped "
            "the trace",
            file=sys.stderr,
        )
        return 1
    # Critical-path contract (docs/OBSERVABILITY.md §9): the merged trace
    # must yield a non-empty blocking path for the predict workload that
    # crossed >= 2 node lanes, with lane shares PARTITIONING the charged
    # time — and the deliberately slowed member must surface as the top
    # contributor, because attribution that cannot find a 250ms-per-shard
    # fault planted on one member is not attribution.
    from dmlc_tpu.cluster.critpath import breakdown, spans_from_perfetto

    crit = breakdown(spans_from_perfetto(doc))
    entry = crit.get("resnet18")
    if not entry or not entry.get("lanes"):
        print(
            "trace smoke FAILED: no critical-path breakdown for resnet18; "
            f"models seen: {sorted(crit)}",
            file=sys.stderr,
        )
        return 1
    if entry["max_lanes"] < 2:
        print(
            "trace smoke FAILED: critical path never crossed >= 2 node "
            f"lanes (max_lanes={entry['max_lanes']}); the dispatch->member "
            "chain is not represented in the charged path",
            file=sys.stderr,
        )
        return 1
    share_sum = sum(float(ln["share"]) for ln in entry["lanes"])
    if abs(share_sum - 1.0) > 1e-6:
        print(
            f"trace smoke FAILED: lane shares sum to {share_sum!r}, not "
            "~1.0 — the charges no longer partition the requests' wall "
            f"time; lanes: {entry['lanes']}",
            file=sys.stderr,
        )
        return 1
    top = entry["lanes"][0]
    if top["member"] != slow_addr:
        print(
            "trace smoke FAILED: top critical-path lane is "
            f"{top['stage']}@{top['member']} ({top['share'] * 100:.1f}%), "
            f"but the deliberately slowed member is {slow_addr} "
            f"(+{SLOW_SECONDS}s/shard); lanes: {entry['lanes']}",
            file=sys.stderr,
        )
        return 1
    print(
        f"trace smoke OK: {len(events)} spans, {len(by_trace)} traces, "
        f"{len(multi_node)} crossing >= 2 nodes, "
        f"{len(gen_steps)} parented gen/step span(s), "
        f"migrated generate across {len(mig_gen_pids)} member lanes "
        "on one trace, "
        f"profile lanes for {len(profile_members)} members, "
        f"device-plane gauges for {len(device_members)} members, "
        f"critical path names slowed member {slow_addr} "
        f"({top['stage']} {top['share'] * 100:.1f}% of {share_sum:.2f})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
