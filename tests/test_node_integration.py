"""End-to-end: a real 3-node cluster on localhost (UDP gossip, TCP RPC,
maintenance threads), driven through the CLI command surface — the whole
stack the reference only ever exercised by hand on 10 VMs.

Fake inference backends keep this hermetic (no JAX); the real EngineBackend
path is covered by bench.py on hardware.
"""

import pytest

from dmlc_tpu.cli import Cli
from dmlc_tpu.cluster.localcluster import (
    start_local_cluster,
    stop_local_cluster,
    wait_until,
)


@pytest.fixture
def cluster3(tmp_path):
    """3 real nodes on 127.0.0.1 via the shared harness (echo backends,
    joined + converged + first leader promoted)."""
    nodes = start_local_cluster(tmp_path, n_nodes=3)
    yield nodes
    stop_local_cluster(nodes)


def test_full_stack_through_cli(cluster3, tmp_path):
    nodes = cluster3
    cli = Cli(nodes[1])  # drive from a non-leader node

    # membership verbs
    out = cli.run_command("lm")
    assert out.count("active") == 3
    assert nodes[1].gossip.address in cli.run_command("list_self")

    # SDFS verbs through the CLI
    src = tmp_path / "w.bin"
    src.write_bytes(b"weights-bytes-v1")
    out = cli.run_command(f"put {src} models/resnet18")
    assert "1" in out
    dst = tmp_path / "out.bin"
    out = cli.run_command(f"get models/resnet18 {dst}")
    assert "v1" in out
    assert dst.read_bytes() == b"weights-bytes-v1"

    src.write_bytes(b"weights-bytes-v2")
    cli.run_command(f"put {src} models/resnet18")
    merged = tmp_path / "merged.bin"
    out = cli.run_command(f"gv models/resnet18 2 {merged}")
    assert "[2, 1]" in out
    assert b"== Version 2 ==" in merged.read_bytes()

    out = cli.run_command("ls models/resnet18")
    assert "models/resnet18" in out

    # train: broadcast the weights to every member, visible in local stores
    cli.run_command("train")
    wait_until(
        lambda: "models/resnet18" in Cli(nodes[2]).run_command("store"),
        msg="train broadcast reaches node2's store",
    )

    # predict + jobs: both jobs run to completion with 100% accuracy
    out = cli.run_command("predict")
    assert "resnet18" in out and "alexnet" in out
    leader = nodes[0]
    wait_until(
        lambda: all(j.done for j in leader.scheduler.jobs.values()),
        msg="jobs complete",
    )
    out = cli.run_command("jobs")
    assert "40/40 finished" in out
    assert "accuracy 100.00%" in out
    assert "p99" in out

    out = cli.run_command("assign")
    assert "resnet18" in out

    # trace verb: toggle, record through a traced path, summarize, export.
    # finally-guarded: the tracer is process-global, and a failed assertion
    # must not leave tracing on (or spans behind) for later tests.
    from dmlc_tpu.utils.tracing import tracer

    try:
        assert "enabled" in cli.run_command("trace on")
        cli.run_command(f"get models/resnet18 {tmp_path / 'traced.bin'}")
        trace_path = tmp_path / "trace.json"
        cli.run_command("trace summary")  # must not crash, spans optional here
        assert "wrote Chrome trace" in cli.run_command(f"trace export {trace_path}")
        assert trace_path.exists() and "traceEvents" in trace_path.read_text()
        assert "disabled" in cli.run_command("trace off")
    finally:
        tracer.enabled = False
        tracer.reset()

    # error surfaces, not crashes
    assert "error" in cli.run_command("get no/such/file /tmp/x")
    assert "unknown command" in cli.run_command("frobnicate")
    assert "usage" in cli.run_command("put onlyonearg")


@pytest.mark.parametrize("line", ["export resnet18", "export-bundle resnet18 /tmp/b"])
def test_retired_verbs_are_unknown_commands(line):
    """The exported-executable path went in PR 29; its verbs answer as any
    unknown verb does (no node is touched: the dispatcher falls through)."""
    verb = line.split()[0]
    assert Cli(node=None).run_command(line) == f"unknown command {verb!r} (try: help)"


def test_help_lists_no_retired_verb():
    first_words = {ln.split()[0] for ln in Cli(node=None).run_command("help").splitlines() if ln.strip()}
    assert {"predict", "train", "mesh-join"} <= first_words
    assert not {"export", "export-bundle"} & first_words


@pytest.mark.parametrize("fault", ["rpc_not_found", "compiler"])
def test_a_warmup_that_raises_stops_the_node(tmp_path, fault):
    """No backend's warm-up failure is tolerated, whatever its class or
    message (until PR 29 one backend's "not in SDFS" was)."""
    from dmlc_tpu.cluster.rpc import RpcError

    error = {
        "rpc_not_found": RpcError("executables/resnet18 not in SDFS"),
        "compiler": RuntimeError("Mosaic failed to compile"),
    }[fault]

    class Backend:
        def __call__(self, synsets):
            return [0 for _ in synsets]

        def warmup(self):
            raise error

    with pytest.raises(type(error), match=str(error)):
        start_local_cluster(
            tmp_path, n_nodes=1, backends={"resnet18": Backend(), "alexnet": Backend()}
        )


def test_authenticated_cluster_end_to_end(tmp_path):
    """A fleet sharing auth_key converges, replicates, and serves jobs with
    every gossip datagram and RPC frame HMAC-tagged — and an unkeyed caller
    cannot reach the leader's methods."""
    import pytest

    from dmlc_tpu.cluster.rpc import RpcUnreachable, TcpRpc

    nodes = start_local_cluster(tmp_path, n_nodes=3, auth_key="fleet-secret")
    try:
        cli = Cli(nodes[1])
        assert cli.run_command("lm").count("active") == 3

        src = tmp_path / "w.bin"
        src.write_bytes(b"keyed-bytes")
        cli.run_command(f"put {src} models/keyed")
        dst = tmp_path / "out.bin"
        cli.run_command(f"get models/keyed {dst}")
        assert dst.read_bytes() == b"keyed-bytes"

        # The whole point: reaching the port without the key gets silence.
        leader = nodes[0].self_leader_addr
        with pytest.raises(RpcUnreachable):
            TcpRpc().call(leader, "sdfs.delete", {"name": "models/keyed"}, timeout=2.0)
    finally:
        stop_local_cluster(nodes)


def test_status_verb_shows_shed_requests(cluster3):
    """The CLI `status` verb surfaces the overload counters — and a request
    shed at a member's admission gate is visible there (docs/OVERLOAD.md)."""
    from dmlc_tpu.cluster.rpc import Overloaded

    nodes = cluster3
    member = nodes[2]
    cli = Cli(member)

    # Baseline: the verb renders the gates and no sheds yet.
    out = cli.run_command("status")
    assert "predict gate" in out and "transfer gate" in out
    assert f"node {member.self_member_addr}" in out

    # Saturate the member's predict gate, then drive one RPC through the
    # REAL member server: it must shed typed, fast — and be counted.
    holders = [member.predict_gate.admit() for _ in range(member.predict_gate.capacity)]
    for h in holders:
        h.__enter__()
    try:
        with pytest.raises(Overloaded):
            nodes[0].rpc.call(
                member.self_member_addr,
                "job.predict",
                {"model": "resnet18", "synsets": ["n00000001"]},
                timeout=5.0,
            )
    finally:
        for h in holders:
            h.__exit__(None, None, None)

    out = cli.run_command("status")
    assert "shed=1" in out, out
    assert "shed_predict=1" in out, out
    # The member's own counter registry saw it too (same numbers the
    # leader-side status aggregates read).
    assert member.metrics.get("shed") == 1


def test_tenants_verb_renders_quota_plane(tmp_path):
    """The CLI `tenants` verb (and `status`) surface the tenant plane on a
    real cluster: declared priorities/shares, live gate occupancy and debt,
    typed over-quota sheds, and the autoscaler's targets (docs/OPERATIONS.md
    §Tenants and the autoscaler)."""
    from dmlc_tpu.cluster import tenant as tenant_mod
    from dmlc_tpu.cluster.rpc import Overloaded

    nodes = start_local_cluster(
        tmp_path, n_nodes=2,
        tenants={"acme": {"priority": "low", "share": 0.25}},
        autoscaler_enabled=True,
    )
    try:
        member = nodes[1]
        cli = Cli(member)
        gate = member.predict_gate
        quota = gate.ledger.quota("acme")
        holders = []
        with tenant_mod.bind("acme"):
            for _ in range(quota):
                ctx = gate.admit()
                ctx.__enter__()
                holders.append(ctx)
            # One past the share: typed over_quota, visible in both verbs.
            with pytest.raises(Overloaded) as ei:
                gate.admit().__enter__()
            assert ei.value.quota == "over_quota"
        try:
            out = cli.run_command("tenants")
            assert "acme" in out and "low" in out, out
            assert f"{quota}/{quota}" in out, out  # occupancy at quota
            assert "over-quota sheds" in out, out
            assert "autoscaler targets" in out, out
            status = cli.run_command("status")
            assert "tenant acme:" in status, status
            assert "over_quota_sheds=1" in status, status
            assert "autoscaler:" in status, status
        finally:
            for h in holders:
                h.__exit__(None, None, None)
        # The leader renders the same plane from its own seat.
        assert "acme" in Cli(nodes[0]).run_command("tenants")
    finally:
        stop_local_cluster(nodes)


def test_leader_failover_resumes_jobs(cluster3, tmp_path):
    nodes = cluster3
    leader, standby, member = nodes
    cli = Cli(member)

    cli.run_command("predict")
    wait_until(
        lambda: any(j.finished > 0 for j in leader.scheduler.jobs.values()),
        msg="first shards complete",
    )
    # Standby mirrors progress before the crash.
    wait_until(
        lambda: any(j.finished > 0 for j in standby.scheduler.jobs.values()),
        msg="standby state sync",
    )
    leader.stop()

    wait_until(lambda: standby.standby.is_leader, msg="standby promotion")
    wait_until(
        lambda: all(j.done for j in standby.scheduler.jobs.values()),
        msg="jobs finish under the new leader",
    )
    # The member-side tracker now points at the standby, so CLI verbs work.
    wait_until(
        lambda: member.tracker.current == standby.self_leader_addr,
        msg="tracker advance",
    )
    out = cli.run_command("jobs")
    assert "40/40 finished" in out
    assert "accuracy 100.00%" in out


def test_critpath_verb_renders_fleet_attribution(cluster3):
    """The CLI `critpath` verb surfaces the leader's folded critical-path
    table (docs/OBSERVABILITY.md section 9): after traced predict traffic,
    (stage x member) lanes render with charged seconds and shares, and the
    `slo` verb grows the culprit column alongside its burn columns."""
    from dmlc_tpu.utils.tracing import tracer

    nodes = cluster3
    leader = nodes[0]
    cli = Cli(nodes[1])
    try:
        tracer.enabled = True
        cli.run_command("predict")
        wait_until(
            lambda: all(j.done for j in leader.scheduler.jobs.values()),
            msg="jobs complete",
        )
        # Charge the process tracer's spans and fold them leader-side the
        # same way the scrape cycle does — without waiting for its cadence.
        assert leader.critpath is not None
        leader.critpath.ingest_tracer(tracer, own_lane=None)
        leader.fleet_critpath.fold("local", leader.critpath.snapshot())

        out = cli.run_command("critpath")
        lines = out.splitlines()
        assert "model" in lines[0] and "share" in lines[0], out
        assert len(lines) >= 2, out
        # --top bounds lanes per model; unknown models and extra args are
        # clean misses, not crashes.
        top = cli.run_command("critpath --top 1")
        assert len(top.splitlines()) <= len(lines)
        assert "no critical-path lanes" in cli.run_command("critpath nope")
        assert "usage:" in cli.run_command("critpath a b")
        # The slo verb still renders (culprit column rides along when
        # objectives exist; this fleet declares none).
        assert cli.run_command("slo")
    finally:
        tracer.enabled = False
        tracer.reset()
