"""Partition-rule engine + gang-sharded serving (ISSUE 17, docs/SHARDING.md).

Three layers, cheapest first:

- pure rule mechanics on synthetic pytrees: first-match-wins, strict mode,
  dead/unmatched auditing, spec clamping at meshes the rules were not
  written for, mesh-shape planning, minimal gang width;
- compiled-program parity: lm_wide's rule-sharded predict on 3- and
  8-device meshes is TOKEN-IDENTICAL to the unsharded mesh-of-1 reference
  (the numeric contract every gang result rests on);
- the acceptance path end-to-end: real LmBackend members on the sim
  fabric, HBM gauges too small for lm_wide solo, and truth labels computed
  by THIS process's reference program — so ``job.accuracy == 1.0`` is
  literal token identity through advisor gang formation, gang dispatch,
  and per-rank sharded execution.

The 8-device virtual CPU mesh comes from conftest.py.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dmlc_tpu.models.registry import get_model
from dmlc_tpu.parallel import sharding as sl
from dmlc_tpu.parallel.mesh import make_mesh


# ---------------------------------------------------------------------------
# Rule mechanics (no device work)
# ---------------------------------------------------------------------------


TREE = {
    "params": {
        "attn": {
            "query": {"kernel": np.zeros((8, 16)), "bias": np.zeros((16,))},
            "out": {"kernel": np.zeros((16, 8)), "bias": np.zeros((8,))},
        },
        "scale": np.zeros(()),  # scalar: always P() regardless of rules
    }
}

RULES = (
    (r"query/kernel$", P(None, "tp")),
    (r"query/bias$", P("tp")),
    (r"out/kernel$", P("tp", None)),
    (r".*", P()),
)


class TestMatchPartitionRules:
    def test_first_match_wins_and_scalars_replicate(self):
        specs = sl.match_partition_rules(RULES, TREE)
        attn = specs["params"]["attn"]
        assert attn["query"]["kernel"] == P(None, "tp")
        assert attn["query"]["bias"] == P("tp")
        assert attn["out"]["kernel"] == P("tp", None)
        assert attn["out"]["bias"] == P()  # catch-all
        assert specs["params"]["scale"] == P()

    def test_strict_mode_raises_on_unmatched(self):
        with pytest.raises(ValueError, match="attn/out/kernel"):
            sl.match_partition_rules(((r"bias$", P("tp")),), TREE)

    def test_validate_rules_names_dead_and_unmatched(self):
        report = sl.validate_rules(
            ((r"nothing_matches_this$", P("tp")), (r"kernel$", P())), TREE
        )
        assert not report.ok
        assert report.dead_rules == ("nothing_matches_this$",)
        assert any("bias" in path for path in report.unmatched)

    def test_healthy_table_reports_ok(self):
        report = sl.validate_rules(RULES, TREE)
        assert report.ok and report.dead_rules == () and report.unmatched == ()

    def test_registry_tables_are_healthy_for_served_models(self):
        # The dynamic half of A8's static table checks: every rule fires on
        # some param, every param gets a spec, at abstract shapes only.
        for name in ("lm_wide", "lm_small", "resnet18", "clip_vit_b32"):
            report = sl.validate_model_rules(name)
            assert report.ok, f"{name}: {report}"


class TestClampAndPlanning:
    def test_clamp_drops_axes_the_mesh_cannot_honor(self):
        mesh = make_mesh({"dp": 2, "tp": 4}, devices=jax.devices())
        # "sp" absent from the mesh; tp=4 does not divide dim 6.
        assert sl.clamp_spec(P("sp", "tp"), mesh, (8, 6)) == P(None, None)
        assert sl.clamp_spec(P(None, "tp"), mesh, (8, 16)) == P(None, "tp")
        # Rank trim: a 2-entry spec against a 1-d shape keeps one entry.
        assert sl.clamp_spec(P("dp", "tp"), mesh, (8,)) == P("dp")

    def test_one_rule_table_compiles_at_every_mesh_shape(self):
        # The same table shards at {tp:4} and fully replicates at {dp:1}.
        wide = make_mesh({"tp": 4}, devices=jax.devices()[:4])
        solo = make_mesh({"dp": 1}, devices=jax.devices()[:1])
        tree = {"query": {"kernel": np.zeros((8, 16), np.float32)}}
        rules = ((r"kernel$", P(None, "tp")),)
        assert sl.shardings_for_tree(wide, tree, rules)["query"]["kernel"].spec == P(None, "tp")
        # clamp keeps rank: the tp entry degrades to None, not to P().
        assert sl.shardings_for_tree(solo, tree, rules)["query"]["kernel"].spec == P(None, None)

    def test_plan_axes_respects_head_divisibility(self):
        assert sl.plan_axes(8, num_heads=4) == {"dp": 2, "tp": 4}
        assert sl.plan_axes(3, num_heads=4) == {"dp": 3, "tp": 1}
        assert sl.plan_axes(4, num_heads=4, max_tp=2) == {"dp": 2, "tp": 2}
        assert sl.plan_axes(1) == {"dp": 1, "tp": 1}

    def test_min_gang_width(self):
        assert sl.min_gang_width(25e6, 10e6, max_width=8) == 3
        assert sl.min_gang_width(25e6, 30e6, max_width=8) == 1
        assert sl.min_gang_width(25e6, 1e6, max_width=8) is None

    def test_sharded_bytes_shrink_with_the_mesh(self):
        full = get_model("lm_wide").param_bytes()
        mesh = make_mesh(sl.plan_axes(8, num_heads=4), devices=jax.devices())
        per_chip = sl.sharded_bytes_per_chip("lm_wide", mesh)
        assert per_chip < full / 2  # tp=4 shards the big matrices 4-way

    def test_prompt_encoding_is_deterministic_and_in_vocab(self):
        a = sl.tokens_for_prompt("p7", 16, 2048)
        b = sl.tokens_for_prompt("p7", 16, 2048)
        assert (a == b).all() and a.dtype == np.int32
        assert int(a.min()) >= 0 and int(a.max()) < 2048
        assert not (a == sl.tokens_for_prompt("p8", 16, 2048)).all()


# ---------------------------------------------------------------------------
# Compiled-program parity (the gang numeric contract)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm_reference():
    prog = sl.ShardedProgram(
        "lm_wide", make_mesh({"dp": 1}, devices=jax.devices()[:1])
    )
    spec = get_model("lm_wide")
    toks = sl.encode_prompts(
        [f"p{i}" for i in range(6)], 16, spec.num_outputs
    )
    return prog, toks, prog.run(toks)


class TestShardedProgramParity:
    @pytest.mark.parametrize("n", [3, 8])
    def test_gang_predict_token_identical_to_reference(self, n, lm_reference):
        _, toks, want = lm_reference
        axes = sl.plan_axes(n, num_heads=get_model("lm_wide").num_heads)
        gang = sl.ShardedProgram(
            "lm_wide", make_mesh(axes, devices=jax.devices()[:n])
        )
        got = gang.run(toks)
        assert (got == want).all(), f"n={n} axes={axes}"

    def test_ragged_batch_pads_and_strips(self, lm_reference):
        _, toks, want = lm_reference
        gang = sl.ShardedProgram(
            "lm_wide",
            make_mesh({"dp": 4}, devices=jax.devices()[:4]),
        )
        got = gang.run(toks[:5])  # 5 % dp(4) != 0: pad path
        assert got.shape == (5,) and (got == want[:5]).all()


# ---------------------------------------------------------------------------
# Acceptance: over-HBM lm_wide serves token-identically through the CLUSTER
# path, on a gang the advisor chose from HBM headroom
# ---------------------------------------------------------------------------


def test_lm_wide_serves_through_cluster_gang_path():
    from dmlc_tpu.cluster.flight import FlightRecorder
    from dmlc_tpu.cluster.profile import CostProfiler
    from dmlc_tpu.cluster.rpc import SimRpcNetwork
    from dmlc_tpu.scheduler.jobs import JobScheduler
    from dmlc_tpu.scheduler.placement import PlacementAdvisor
    from dmlc_tpu.scheduler.worker import LmBackend, PredictWorker

    spec = get_model("lm_wide")
    prompt_len = 16
    prompts = [f"p{i}" for i in range(12)]

    # Truth labels from THIS process's single-chip reference: accuracy 1.0
    # through the cluster path below IS token identity, not a proxy.
    ref = sl.ShardedProgram(
        "lm_wide", make_mesh({"dp": 1}, devices=jax.devices()[:1])
    )
    truth = ref.run(sl.encode_prompts(prompts, prompt_len, spec.num_outputs))

    net = SimRpcNetwork()
    members = ["m0", "m1", "m2", "m3"]
    budget = 10_000_000  # < lm_wide's ~25 MB replicated weights
    for m in members:
        backend = LmBackend(
            "lm_wide", prompt_len=prompt_len, hbm_budget_bytes=budget
        )
        net.serve(m, PredictWorker({"lm_wide": backend}).methods())

    flight = FlightRecorder(clock=net.clock)
    profiler = CostProfiler(window_s=5.0, windows=8, decay=0.5, clock=net.clock)
    for m in members:
        profiler.record("lm_wide", m, "dispatch", 0.1, count=8)
    advisor = PlacementAdvisor(
        profiler, flight=flight, clock=net.clock,
        # The gauges the node leader feeds from devicemon scrapes, scripted:
        # no member can hold the model alone.
        headroom=lambda m: float(budget),
        model_bytes=lambda job: float(spec.param_bytes()),
    )
    sched = JobScheduler(
        net.client("L"),
        lambda: list(members),
        jobs={"lm_wide": list(zip(prompts, (int(t) for t in truth)))},
        shard_size=4,
        shard_timeout_s=30.0,
        timer=net.clock,
        hedge_tail=False,
        flight=flight,
        profiler=profiler,
        advisor=advisor,
    )
    sched.is_leading = True
    sched._start({})
    job = sched.jobs["lm_wide"]

    # The advisor chose a gang from HBM headroom alone (25 MB / 3 fits 10).
    assert job.gang_world == 3, job.report()
    assert len(job.assigned) == 3

    deadline = net.now + 120.0
    while not job.done and net.now < deadline:
        sched.assign_once()
        if sched.dispatch_all_once() == 0:
            net.advance(0.05)
    assert job.done, job.report()
    assert job.correct == len(prompts), (
        "cluster-path predictions diverged from the single-process reference"
    )
    assert job.accuracy == 1.0
    # Every dispatch went through the collective verb; the solo path (which
    # would have raised the typed over-HBM refusal) never fired.
    assert any(m == "job.predict_gang" for _, m in net.calls)
    assert all(m != "job.predict" for _, m in net.calls)
