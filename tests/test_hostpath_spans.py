"""Spans on the threads that feed the device (docs/OBSERVABILITY.md §1,
"Feeding threads"): ``job.predict``'s engine-holding thread and the
generation loop are TILED by leaf spans, work handed to a pool keeps its
shard's trace, the counters ride ``gen/step``, and a disabled tracer costs
nothing — at CPU size, on the tiny models.

Each scenario runs once (module fixtures) and every property is a case of
its own.
"""

import contextlib
import os
import random
import re
import sys
import threading
import time

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dmlc_tpu.cluster import tracectx  # noqa: E402
from dmlc_tpu.generate.engine import GenerationEngine  # noqa: E402
from dmlc_tpu.generate.slots import SlotScheduler  # noqa: E402
from dmlc_tpu.models.registry import get_model  # noqa: E402
from dmlc_tpu.ops import preprocess as pp  # noqa: E402
from dmlc_tpu.scheduler import worker as worker_mod  # noqa: E402
from dmlc_tpu.scheduler.worker import EngineBackend  # noqa: E402
from dmlc_tpu.utils import corpus  # noqa: E402
from dmlc_tpu.utils.tracing import Tracer, tracer  # noqa: E402
from tiny_model import N_CLASSES  # noqa: E402,F401  (registers "tinynet")

BATCH = 8  # the CPU test mesh is dp=8
N_IMAGES = 32  # 4 batches a shard: the stream path

#: What the engine-holding thread does, one leaf span each (they tile engine/run).
PREDICT_LEAVES = ("ingest/decode_submit", "ingest/decode_wait", "ingest/stage",
                  "ingest/dispatch", "device/sync_wait", "ingest/collect",
                  "engine/collect")
#: What a shard does in the ahead slot, before it takes the engine (they tile engine/ahead).
AHEAD_LEAVES = ("engine/resolve_paths", "engine/ahead_submit")
#: Every leaf of a shard on its RPC thread, from the wait for the ahead slot to the reply.
SHARD_LEAVES = ("engine/ahead_wait",) + AHEAD_LEAVES + ("engine/lock_wait",) + PREDICT_LEAVES
#: The decode thread's top-level spans (they tile first admission .. last exit).
LOOP_TOP = ("gen/idle", "gen/admit", "gen/prefill", "gen/retire", "gen/step", "gen/deliver")


def wire(events):
    return [dict(e, t0=e["start"], t1=e["start"] + e["dur"]) for e in events]


#: perf_counter read on two cores of a virtual machine can differ by
#: microseconds: what "at the same instant" is allowed to mean below.
CLOCK_SLACK = 1e-4


def covered(spans, t0, t1):
    """(seconds of [t0, t1] under ``spans``, seconds two of them overlap)."""
    total = overlap = 0.0
    end = t0
    for s in sorted(spans, key=lambda s: s["t0"]):
        a, b = max(s["t0"], t0), min(s["t1"], t1)
        if b <= a:
            continue
        if a < end:
            overlap += min(b, end) - a
        total += max(0.0, b - max(a, end))
        end = max(end, b)
    return total, overlap


@contextlib.contextmanager
def traced_scenario():
    """The process-global tracer, on and empty, for one scenario; off and
    empty afterwards. The interpreter's switch interval is cut to 0.1 ms:
    a feeding thread that loses the interpreter BETWEEN two spans (to a
    decode thread, to the test runner's own threads) otherwise stays out for
    5 ms, which at CPU size is the tiling margin itself."""
    was, cap, interval = tracer.enabled, tracer.max_events, sys.getswitchinterval()
    tracer.reset()
    tracer.max_events = 500_000
    tracer.enabled = True
    sys.setswitchinterval(1e-4)
    try:
        yield tracer
    finally:
        sys.setswitchinterval(interval)
        tracer.enabled, tracer.max_events = was, cap
        tracer.reset()


@pytest.fixture
def tracing_on():
    with traced_scenario() as t:
        yield t


# ---------------------------------------------------------------------------
# job.predict: two shards contend for one EngineBackend on the stream path
# ---------------------------------------------------------------------------


def settled_corpus(root):
    """A corpus at rest: class directories dated an hour back (one changed
    in the last two seconds is looked up on disk, never remembered)."""
    data_dir, _ = corpus.generate(root, n_classes=N_IMAGES, images_per_class=1, size=32)
    then = time.time() - 3600.0
    for d in data_dir.iterdir():
        os.utime(d, (then, then))
    return data_dir


@pytest.fixture(scope="module")
def backend(tmp_path_factory):
    data_dir = settled_corpus(tmp_path_factory.mktemp("hostpath_corpus"))
    synsets = sorted(d.name for d in data_dir.iterdir())
    be = EngineBackend("tinynet", data_dir, batch_size=BATCH)
    be.warmup()
    be(synsets)  # first use builds the stage pool and its threads: not a shard's cost
    return be, synsets


@pytest.fixture(scope="module")
def predict_spans(backend):
    """Spans of two concurrent shards; decode sleeps 50 ms a batch (the GIL
    is released), so a shard's wall is waits the spans must own."""
    be, synsets = backend
    real_load = pp.load_batch

    def slow_load(paths, **kw):
        time.sleep(0.05)
        return real_load(paths, **kw)

    gate = threading.Barrier(2)
    errors = []

    def shard(i):
        try:
            gate.wait(timeout=30)
            with tracer.span("test/shard", i=i):
                assert len(be(synsets)) == len(synsets)
        except BaseException as e:  # surfaced by the fixture below
            errors.append(e)

    threads = [threading.Thread(target=shard, args=(i,)) for i in range(2)]
    pp.load_batch = slow_load
    try:
        with traced_scenario():
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
            spans = wire(tracer.events_wire())
            summary = be._engine.ingest_summary()
    finally:
        pp.load_batch = real_load
    assert not errors, errors
    roots = [s for s in spans if s["name"] == "test/shard"]
    assert len(roots) == 2
    by_trace = {r["trace"]: [s for s in spans if s["trace"] == r["trace"]] for r in roots}
    return {"all": spans, "by_trace": by_trace, "ingest_summary": summary}


@pytest.mark.parametrize("name", ("engine/ahead", "engine/run", "host/decode") + SHARD_LEAVES)
def test_predict_span_in_every_shard(predict_spans, name):
    for spans in predict_spans["by_trace"].values():
        assert any(s["name"] == name for s in spans), (name, sorted({s["name"] for s in spans}))


def test_predict_lock_wait_sees_the_other_shard(predict_spans):
    """One shard takes the lock at once; the other, ahead of it, waits out
    the first's whole hold less its own ahead work (a few lookups)."""
    waits, runs = [], []
    for spans in predict_spans["by_trace"].values():
        waits += [s["dur"] for s in spans if s["name"] == "engine/lock_wait"]
        runs += [s for s in spans if s["name"] == "engine/run"]
    assert len(waits) == 2 and len(runs) == 2
    first, second = sorted(runs, key=lambda s: s["t0"])
    assert second["t0"] >= first["t1"] - CLOCK_SLACK    # the lock serialises the holds
    assert max(waits) >= 0.9 * first["dur"]
    assert min(waits) < 0.5 * first["dur"]


def test_predict_leaves_tile_engine_run(predict_spans):
    """On the thread that holds the engine, the leaf spans cover >= 95% of
    each engine/run and no two of them overlap."""
    for spans in predict_spans["by_trace"].values():
        run = next(s for s in spans if s["name"] == "engine/run")
        leaves = [s for s in spans if s["name"] in PREDICT_LEAVES and s["tid"] == run["tid"]]
        total, overlap = covered(leaves, run["t0"], run["t1"])
        assert overlap < CLOCK_SLACK
        assert total >= 0.95 * run["dur"], (total, run["dur"])
        assert all(run["t0"] - CLOCK_SLACK <= s["t0"] and s["t1"] <= run["t1"] + CLOCK_SLACK
                   for s in leaves)


def test_predict_leaves_tile_the_shard_on_its_thread(predict_spans):
    """The tiling rule covers the ahead span too: from the wait for the ahead
    slot to the reply, the shard's leaves on its RPC thread cover >= 95% and
    no two of them overlap."""
    for spans in predict_spans["by_trace"].values():
        run = next(s for s in spans if s["name"] == "engine/run")
        wait = next(s for s in spans if s["name"] == "engine/ahead_wait")
        leaves = [s for s in spans if s["name"] in SHARD_LEAVES and s["tid"] == run["tid"]]
        total, overlap = covered(leaves, wait["t0"], run["t1"])
        assert overlap < CLOCK_SLACK
        assert total >= 0.95 * (run["t1"] - wait["t0"]), (total, run["t1"] - wait["t0"])


def test_predict_decode_keeps_the_shards_trace(predict_spans):
    """host/decode runs on the stage pool and is still the shard's: its
    trace id, another thread, and as parent the span it was started under:
    engine/ahead for the batches started before the lock, engine/run for
    the rest."""
    for spans in predict_spans["by_trace"].values():
        run = next(s for s in spans if s["name"] == "engine/run")
        ahead = next(s for s in spans if s["name"] == "engine/ahead")
        decodes = [s for s in spans if s["name"] == "host/decode"]
        assert len(decodes) == N_IMAGES // BATCH
        assert all(d["tid"] != run["tid"] for d in decodes)
        assert sorted(d["parent"] for d in decodes) == sorted(
            [ahead["span"]] * run["attrs"]["ahead"]
            + [run["span"]] * (N_IMAGES // BATCH - run["attrs"]["ahead"]))
    # and none is left a root of its own trace
    assert not [s for s in predict_spans["all"]
                if s["name"] == "host/decode" and s["parent"] is None]


def test_predict_parent_edges(predict_spans):
    """ahead_wait, ahead, lock_wait, run: four children of the shard's span,
    in that order; the lookup and the ahead submit lie under engine/ahead,
    the rest under engine/run."""
    for spans in predict_spans["by_trace"].values():
        root = next(s for s in spans if s["name"] == "test/shard")
        order = [next(s for s in spans if s["name"] == name)
                 for name in ("engine/ahead_wait", "engine/ahead", "engine/lock_wait", "engine/run")]
        assert all(s["parent"] == root["span"] for s in order)
        assert all(a["t1"] <= b["t0"] + CLOCK_SLACK for a, b in zip(order, order[1:]))
        ahead, run = order[1], order[3]
        assert ahead["attrs"]["n"] == N_IMAGES and ahead["attrs"]["batches"] == N_IMAGES // BATCH
        assert run["attrs"]["n"] == N_IMAGES and run["attrs"]["batches"] == N_IMAGES // BATCH
        for s in spans:
            if s["name"] in PREDICT_LEAVES:
                assert s["parent"] == run["span"], s["name"]
            if s["name"] in AHEAD_LEAVES:
                assert s["parent"] == ahead["span"], s["name"]
        waits = [s for s in spans if s["name"] == "ingest/decode_wait"]
        assert len(waits) == N_IMAGES // BATCH
        assert all(isinstance(w["attrs"]["ready"], bool) for w in waits)


def test_predict_run_counts_the_batches_started_ahead(predict_spans):
    """engine/run carries ``ahead``, the batches whose decode started before
    the lock (the first ``prefetch`` = 2 of four, whether or not another
    shard held the engine), and ``ready``, those already decoded when the
    lock was taken. The shard that waited out the other's hold finds both
    of its batches decoded (each decode sleeps 50 ms, the hold is longer)."""
    runs = sorted((next(s for s in spans if s["name"] == "engine/run")
                   for spans in predict_spans["by_trace"].values()), key=lambda s: s["t0"])
    assert [r["attrs"]["ahead"] for r in runs] == [2, 2]
    assert all(0 <= r["attrs"]["ready"] <= 2 for r in runs)
    assert runs[1]["attrs"]["ready"] == 2


@pytest.mark.parametrize("name", ("engine/ahead_wait", "engine/ahead", "engine/resolve_paths"))
def test_predict_ahead_spans_keep_the_shards_trace(predict_spans, name):
    """The spans a shard opens before it holds the engine are the shard's:
    its trace id and thread, one each."""
    for trace, spans in predict_spans["by_trace"].items():
        run = next(s for s in spans if s["name"] == "engine/run")
        (mine,) = [s for s in predict_spans["all"] if s["name"] == name and s["trace"] == trace]
        assert mine["tid"] == run["tid"]
    assert len([s for s in predict_spans["all"] if s["name"] == name]) == 2


def test_predict_resolve_paths_counts_its_misses(predict_spans):
    """engine/resolve_paths opens under engine/ahead on every shard with n
    and misses; the fixture's first shard listed every directory, so these
    two list none."""
    for spans in predict_spans["by_trace"].values():
        run = next(s for s in spans if s["name"] == "engine/run")
        ahead = next(s for s in spans if s["name"] == "engine/ahead")
        (resolve,) = [s for s in spans if s["name"] == "engine/resolve_paths"]
        assert resolve["parent"] == ahead["span"] and resolve["tid"] == run["tid"]
        assert resolve["attrs"]["n"] == N_IMAGES and resolve["attrs"]["misses"] == 0


@pytest.mark.parametrize("shard,misses", [(0, N_IMAGES), (1, 0), (2, 0)])
def test_resolve_paths_lists_a_directory_once(backend, tracing_on, tmp_path, monkeypatch,
                                              shard, misses):
    """Over a corpus not seen before, the first shard lists every class
    directory (twice-asked synsets once) and later shards list none."""
    be, synsets = backend
    monkeypatch.setattr(be, "data_dir", settled_corpus(tmp_path))
    for _ in range(shard + 1):
        tracer.reset()
        with tracer.span("test/shard"):
            be(synsets + synsets[:BATCH])
    spans = wire(tracer.events_wire())
    ahead = next(s for s in spans if s["name"] == "engine/ahead")
    (resolve,) = [s for s in spans if s["name"] == "engine/resolve_paths"]
    assert resolve["parent"] == ahead["span"]
    assert resolve["attrs"]["n"] == N_IMAGES + BATCH and resolve["attrs"]["misses"] == misses


def test_resolve_paths_with_an_image_source_never_touches_the_memo(backend, monkeypatch):
    be, synsets = backend
    local = pp.class_image_paths(be.data_dir, synsets)[0]

    def boom(*a, **kw):
        raise AssertionError("the local lookup ran beside an image source")

    monkeypatch.setattr(pp, "class_image_paths", boom)
    monkeypatch.setattr(pp, "class_image_path", boom)
    remembered = dict(pp._CLASS_PATHS)
    asked = []

    def source(names):
        asked.append(list(names))
        return iter(local)

    span = tracer.span("engine/resolve_paths")  # the disabled tracer's shared no-op span
    assert worker_mod._resolve_paths(source, "/nowhere", synsets, span) == local
    assert worker_mod._resolve_paths(source, "/nowhere", synsets) == local
    assert asked == [synsets, synsets] and pp._CLASS_PATHS == remembered


def test_predict_cpu_time_on_engine_spans(predict_spans):
    """cpu_s rides the engine-holding spans; a hold that mostly waits for
    decode burns less CPU than wall."""
    for spans in predict_spans["by_trace"].values():
        run = next(s for s in spans if s["name"] == "engine/run")
        assert 0.0 <= run["attrs"]["cpu_s"] < run["dur"]
        for s in spans:
            if s["name"] in ("engine/ahead", "engine/resolve_paths", "engine/ahead_submit",
                             "ingest/decode_wait", "ingest/collect", "engine/collect"):
                assert s["attrs"]["cpu_s"] >= 0.0


def test_ingest_decode_is_a_statistic_not_a_span(predict_spans):
    """The decode interval is host/decode; ingest_summary still counts it."""
    assert not [s for s in predict_spans["all"] if s["name"] == "ingest/decode"]
    assert predict_spans["ingest_summary"]["decode"]["count"] >= 2 * N_IMAGES // BATCH


def test_one_batch_shard_is_not_dark(backend, tracing_on, monkeypatch):
    """A shard of one batch takes run_paths: decode, forward, collect. Its
    lookup moves ahead of the lock like any shard's; its decode does not
    (run_paths decodes inline), so engine/run says ``ahead`` = 0."""
    be, synsets = backend
    real_load = pp.load_batch
    monkeypatch.setattr(pp, "load_batch",
                        lambda paths, **kw: (time.sleep(0.05), real_load(paths, **kw))[1])
    with tracer.span("test/shard"):
        be(synsets[:BATCH - 1])
    spans = wire(tracer.events_wire())
    run = next(s for s in spans if s["name"] == "engine/run")
    ahead = next(s for s in spans if s["name"] == "engine/ahead")
    assert {s["name"] for s in spans if s["parent"] == ahead["span"]} == {"engine/resolve_paths"}
    assert run["attrs"]["ahead"] == run["attrs"]["ready"] == 0
    leaves = [s for s in spans if s["parent"] == run["span"]]
    assert {s["name"] for s in leaves} == {
        "host/decode", "ingest/stage", "device/forward", "ingest/collect", "engine/collect"}
    total, overlap = covered(leaves, run["t0"], run["t1"])
    assert overlap < CLOCK_SLACK and total >= 0.9 * run["dur"]


# ---------------------------------------------------------------------------
# the generation loop: a SlotScheduler over the tiny engine
# ---------------------------------------------------------------------------

SPEC = get_model("lm_small")
N_REQUESTS = 6


@pytest.fixture(scope="module")
def lm_engine():
    _, variables = SPEC.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = GenerationEngine("lm_small", variables=variables, max_slots=4, page_size=8,
                           num_pages=64, max_prefill=16)
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def gen_spans(lm_engine):
    """Six requests staged before the decode thread starts (four slots, so
    two wait holding their reservations), each submitted under a root span
    of its own; the engine's own state is written down as every step is
    dispatched (the loop reads a step a turn after it dispatched it)."""
    eng = lm_engine
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, SPEC.num_outputs, size=int(rng.integers(3, 12))).tolist(),
             int(rng.integers(3, 9))) for _ in range(N_REQUESTS)]
    state_at_step = []
    real_step = eng.dispatch_step

    def dispatch_step():
        alloc = eng.cache.allocator
        state_at_step.append((alloc.pages_total - alloc.pages_free,
                              int(eng.lengths[eng.active].sum()), int(eng.active.sum())))
        # A step the size of the tracer's own cost would test the tracer; and under
        # the suite's six workers the host work between spans slows several times
        # while a sleep does not: long enough that the tiling pin reads the loop.
        time.sleep(0.02)
        return real_step()

    eng.dispatch_step = dispatch_step
    try:
        with traced_scenario():
            sched = SlotScheduler(eng, max_waiting=N_REQUESTS, autostart=False)
            streams = []
            for i, (prompt, n) in enumerate(reqs):
                with tracer.span("test/request", i=i):
                    streams.append(sched.submit(prompt, max_new_tokens=n))
            sched.start()
            outs = [s.result(timeout=120) for s in streams]
            sched.stop()
            spans = wire(tracer.events_wire())
    finally:
        del eng.dispatch_step
    assert [len(o) for o in outs] == [n for _, n in reqs]
    return {"all": spans, "reqs": reqs, "state_at_step": state_at_step}


def named(spans, name):
    return [s for s in spans["all"] if s["name"] == name]


def reads(spans, name):
    """The ``gen/step`` / ``gen/prefill`` spans that READ a run, oldest first:
    they carry the run's attributes (a span that only dispatches has none)."""
    attr = "slots" if name == "gen/step" else "prompts"
    return sorted((s for s in named(spans, name) if attr in s["attrs"]), key=lambda s: s["t0"])


def test_gen_wait_once_per_request_under_its_trace(gen_spans):
    roots = {r["attrs"]["i"]: r for r in named(gen_spans, "test/request")}
    waits = named(gen_spans, "gen/wait")
    assert len(roots) == N_REQUESTS and len(waits) == N_REQUESTS
    assert sorted(w["trace"] for w in waits) == sorted(r["trace"] for r in roots.values())
    admits = named(gen_spans, "gen/admit")
    for w in waits:
        root = next(r for r in roots.values() if r["trace"] == w["trace"])
        assert w["parent"] == root["span"]
        # from submit (inside the root span) to the admission that dispatches its prefill run;
        # ``tracer.record`` dates a span back from ITS clock read, a few calls after the loop
        # took the duration: a loaded host can put a preemption between the two (1 ms of room)
        assert root["t0"] - CLOCK_SLACK <= w["t0"] <= root["t1"] + 1e-3
        assert any(a["t0"] - CLOCK_SLACK <= w["t1"] <= a["t1"] + CLOCK_SLACK for a in admits)
    # the two that found no slot waited for an exit: longer than any of the first four
    by_len = sorted(w["dur"] for w in waits)
    assert by_len[-2] > by_len[3]


def test_gen_prefill_is_one_span_per_run_of_the_program(gen_spans):
    """Four staged requests find the four slots free: ONE run admits them,
    under the oldest one's trace; the two that waited come in later runs.
    A run's span is its READ, at the end of the turn whose admission
    dispatched it (under ``gen/admit``), and counts the run's requests."""
    runs = reads(gen_spans, "gen/prefill")
    assert len(runs) == len(named(gen_spans, "gen/prefill"))
    steps = sorted(named(gen_spans, "gen/step"), key=lambda s: s["t0"])
    waits = named(gen_spans, "gen/wait")
    for read in runs:
        sent = max(w["t1"] for w in waits if w["trace"] == read["trace"])
        # between the two the loop dispatched ONE step, its own turn's: the
        # device has it to do while the host waits for the run
        assert sum(sent <= s["t0"] and s["t1"] <= read["t0"] + CLOCK_SLACK for s in steps) == 1
    roots = {r["attrs"]["i"]: r for r in named(gen_spans, "test/request")}
    assert runs[0]["attrs"]["prompts"] == 4
    assert sum(p["attrs"]["prompts"] for p in runs) == N_REQUESTS
    assert runs[0]["trace"] == roots[0]["trace"] and runs[1]["trace"] == roots[4]["trace"]


def test_loop_thread_spans_tile_admission_to_exit(gen_spans):
    """From the first admission to the last exit the decode thread's
    top-level spans cover >= 95% and never overlap."""
    loop_tid = named(gen_spans, "gen/step")[0]["tid"]
    top = [s for s in gen_spans["all"] if s["name"] in LOOP_TOP and s["tid"] == loop_tid]
    assert {s["name"] for s in top} >= set(LOOP_TOP) - {"gen/idle"}
    t0 = min(s["t0"] for s in top if s["name"] == "gen/admit")
    t1 = max(s["t1"] for s in top if s["name"] == "gen/deliver")
    total, overlap = covered([s for s in top if s["name"] != "gen/idle"], t0, t1)
    assert overlap < CLOCK_SLACK
    assert total >= 0.95 * (t1 - t0), (total, t1 - t0)


@pytest.mark.parametrize("child,parent", [("gen/step_sync", "gen/step"),
                                          ("gen/prefill_sync", "gen/prefill")])
def test_sync_span_is_the_child_that_blocks(gen_spans, child, parent):
    """One blocking read a run, under the span that reads the run."""
    parents = {s["span"]: s for s in reads(gen_spans, parent)}
    kids = named(gen_spans, child)
    assert len(kids) == len(parents) > 0
    assert sorted(k["parent"] for k in kids) == sorted(parents)
    for k in kids:
        p = parents[k["parent"]]
        assert p["t0"] - CLOCK_SLACK <= k["t0"] and k["t1"] <= p["t1"] + CLOCK_SLACK
        assert k["trace"] == p["trace"]


def test_gen_step_carries_the_allocators_and_engines_state(gen_spans):
    """The span that reads a step carries what the allocator and the engine
    held when THAT step was dispatched, a turn earlier."""
    steps = reads(gen_spans, "gen/step")
    seen = [(s["attrs"]["pages_bound"], s["attrs"]["tokens_resident"], s["attrs"]["slots"])
            for s in steps]
    assert seen == gen_spans["state_at_step"]
    # while two requests waited, their reserved pages were counted as bound
    assert max(p for p, _, _ in seen) > 0 and all(p * 8 >= t for p, t, _ in seen)


def test_gen_step_says_whether_it_left_with_the_step_before_unread(gen_spans):
    """One busy period: its first turn dispatches a step and reads none, its
    last reads one and dispatches none, and every step but the first left
    while the one before it was in flight."""
    spans = sorted(named(gen_spans, "gen/step"), key=lambda s: s["t0"])
    assert "ahead" not in spans[0]["attrs"] and "slots" not in spans[0]["attrs"]
    steps = reads(gen_spans, "gen/step")
    assert len(steps) == len(spans) - 1 == len(gen_spans["state_at_step"])
    assert [s["attrs"]["ahead"] for s in steps] == [0] + [1] * (len(steps) - 1)


@pytest.mark.parametrize("name", LOOP_TOP[1:] + ("gen/step_sync", "gen/prefill_sync", "gen/release"))
def test_loop_spans_carry_cpu_time(gen_spans, name):
    spans = named(gen_spans, name)
    assert spans and all(0.0 <= s["attrs"]["cpu_s"] for s in spans)


def test_gen_idle_is_the_wait_for_work(lm_engine, tracing_on):
    """A loop with nobody to serve sits in gen/idle, burning no CPU, until a submit wakes it."""
    sched = SlotScheduler(lm_engine, max_waiting=2)
    try:
        time.sleep(0.05)
        assert len(sched.submit([1, 2, 3], max_new_tokens=2).result(timeout=60)) == 2
    finally:
        sched.stop()
    spans = wire(tracer.events_wire())
    idle = [s for s in spans if s["name"] == "gen/idle"]
    first_admit = min(s["t0"] for s in spans if s["name"] == "gen/prefill")
    woke = [s for s in idle if s["t1"] <= first_admit]
    assert len(woke) == 1 and woke[0]["dur"] >= 0.04 and woke[0]["attrs"]["cpu_s"] < 0.02


def test_gen_step_binds_the_oldest_residents_trace(gen_spans):
    request_traces = {r["trace"] for r in named(gen_spans, "test/request")}
    assert all(s["trace"] in request_traces for s in named(gen_spans, "gen/step"))
    assert all(s["parent"] is None for s in named(gen_spans, "gen/retire"))


# ---------------------------------------------------------------------------
# a leaf for each host part of a program run (PR 35): operands, the call
# ---------------------------------------------------------------------------

#: A run's host leaves and the loop span they are children of.
RUN_LEAVES = (("gen/step_operands", "gen/step"), ("gen/step_call", "gen/step"),
              ("gen/prefill_operands", "gen/admit"), ("gen/prefill_call", "gen/admit"))
#: What the decode thread can be doing, one leaf each; a parent's self time is the rest.
LOOP_LEAVES = tuple(leaf for leaf, _ in RUN_LEAVES) + (
    "gen/step_sync", "gen/prefill_sync", "gen/release", "gen/retire", "gen/deliver", "gen/idle")
LOOP_PARENTS = ("gen/step", "gen/admit", "gen/prefill")


@pytest.mark.parametrize("leaf,parent", RUN_LEAVES)
def test_run_leaf_once_per_program_run_under_its_loop_span(gen_spans, leaf, parent):
    """One ``operands`` and one ``call`` leaf per run of a program, each with
    its thread CPU, the step's two under ``gen/step``, a prefill run's two
    under the ``gen/admit`` that dispatched it, and the operands before the call."""
    leaves = named(gen_spans, leaf)
    runs = (len(gen_spans["state_at_step"]) if parent == "gen/step"
            else len(reads(gen_spans, "gen/prefill")))
    assert len(leaves) == runs > 0
    parents = {s["span"]: s for s in named(gen_spans, parent)}
    for s in leaves:
        assert s["attrs"]["cpu_s"] >= 0.0
        p = parents[s["parent"]]
        assert p["tid"] == s["tid"] and p["trace"] == s["trace"]
        assert p["t0"] - CLOCK_SLACK <= s["t0"] and s["t1"] <= p["t1"] + CLOCK_SLACK
    # a parent has at most one leaf of a name; its operands end before its call starts
    assert len({s["parent"] for s in leaves}) == len(leaves)
    if leaf.endswith("_call"):
        before = {s["parent"]: s for s in named(gen_spans, leaf.replace("_call", "_operands"))}
        assert all(before[s["parent"]]["t1"] <= s["t0"] + CLOCK_SLACK for s in leaves)
    if parent == "gen/admit":
        assert sorted(s["attrs"]["prompts"] for s in leaves) == sorted(
            p["attrs"]["prompts"] for p in reads(gen_spans, "gen/prefill"))


@pytest.mark.parametrize("parent,sync,key", [("gen/step", "gen/step_sync", "seq"),
                                             ("gen/prefill", "gen/prefill_sync", "run")])
def test_gen_release_once_per_program_run_before_its_read(gen_spans, parent, sync, key):
    """The arrays the programs' calls replaced are let go in ONE leaf,
    ``gen/release``, under the loop span that reads a run, BEFORE the run's
    blocking read (for as long as the result is not ready: ``waiting`` of the
    ``arrays`` it let go), numbered like the run's call and sync."""
    releases = [s for s in named(gen_spans, "gen/release") if key in s["attrs"]]
    syncs = {s["attrs"][key]: s for s in named(gen_spans, sync)}
    assert len(releases) == len(syncs) > 0
    assert sorted(r["attrs"][key] for r in releases) == sorted(syncs)
    parents = {s["span"]: s for s in reads(gen_spans, parent)}
    for r in releases:
        p, read = parents[r["parent"]], syncs[r["attrs"][key]]
        assert p["tid"] == r["tid"] and p["trace"] == r["trace"]
        assert p["t0"] - CLOCK_SLACK <= r["t0"] and r["t1"] <= p["t1"] + CLOCK_SLACK
        assert read["parent"] == r["parent"] and r["t1"] <= read["t0"] + CLOCK_SLACK
        assert 0 <= r["attrs"]["waiting"] <= r["attrs"]["arrays"] and r["attrs"]["cpu_s"] >= 0.0
        assert ("seq" in r["attrs"]) != ("run" in r["attrs"])
    # one release a program run, whichever program: as many as there were calls
    calls = len(named(gen_spans, "gen/step_call")) + len(named(gen_spans, "gen/prefill_call"))
    assert len(named(gen_spans, "gen/release")) == calls
    # ``lm_small``: two pools a call, and none is kept beyond the engine's bound of eight runs' worth
    let_go = sum(s["attrs"]["arrays"] for s in named(gen_spans, "gen/release"))
    assert 2 * calls - 16 <= let_go <= 2 * calls + 16


def uncovered(parent, kids):
    """What of ``parent`` none of ``kids`` covers: its self time, as intervals."""
    out, cursor = [], parent["t0"]
    for k in sorted(kids, key=lambda s: s["t0"]):
        if k["t0"] > cursor:
            out.append({"t0": cursor, "t1": k["t0"]})
        cursor = max(cursor, k["t1"])
    if cursor < parent["t1"]:
        out.append({"t0": cursor, "t1": parent["t1"]})
    return out


def test_loop_leaves_and_parents_self_time_tile_a_busy_period(gen_spans):
    """Every instant of a busy period lies under exactly one leaf, or in the
    self time of one of the three spans that have children (bookkeeping):
    together they cover >= 95% and no two overlap, so no child leaves its
    parent and no two children of one parent meet."""
    loop_tid = named(gen_spans, "gen/step")[0]["tid"]
    mine = [s for s in gen_spans["all"] if s["tid"] == loop_tid]
    leaves = [s for s in mine if s["name"] in LOOP_LEAVES]
    pieces = []
    for p in (s for s in mine if s["name"] in LOOP_PARENTS):
        pieces += uncovered(p, [s for s in leaves if s["parent"] == p["span"]])
    on_thread = {s["span"] for s in mine}
    assert all(s["parent"] in on_thread for s in leaves
               if s["name"] not in ("gen/retire", "gen/deliver", "gen/idle"))
    t0 = min(s["t0"] for s in mine if s["name"] == "gen/admit")
    t1 = max(s["t1"] for s in mine if s["name"] == "gen/deliver")
    total, overlap = covered(leaves + pieces, t0, t1)
    assert overlap < CLOCK_SLACK
    assert total >= 0.95 * (t1 - t0), (total, t1 - t0)


@pytest.mark.parametrize("call,sync,key", [("gen/step_call", "gen/step_sync", "seq"),
                                           ("gen/prefill_call", "gen/prefill_sync", "run")])
def test_a_number_joins_a_runs_dispatch_to_its_read(gen_spans, call, sync, key):
    """A run is dispatched in one span and read in another: a number on both
    joins them, not their order. A step is read in the turn AFTER the one
    that dispatched it (the ``gen/step`` after its call's), a prefill run
    in its own turn, after that turn's step has left."""
    calls = {s["attrs"][key]: s for s in named(gen_spans, call)}
    syncs = {s["attrs"][key]: s for s in named(gen_spans, sync)}
    assert len(calls) == len(named(gen_spans, call)) and len(syncs) == len(named(gen_spans, sync))
    assert sorted(calls) == sorted(syncs) and len(calls) > 1
    steps = sorted(named(gen_spans, "gen/step"), key=lambda s: s["t0"])
    turn = {s["span"]: i for i, s in enumerate(steps)}
    for number, c in calls.items():
        r = syncs[number]
        assert c["t1"] <= r["t0"] + CLOCK_SLACK
        if key == "seq":
            assert turn[r["parent"]] == turn[c["parent"]] + 1
        else:
            read = next(p for p in named(gen_spans, "gen/prefill") if p["span"] == r["parent"])
            assert read["attrs"]["prompts"] == c["attrs"]["prompts"]
            # between the run's call and its read: this turn's step call, and no other
            assert sum(c["t1"] <= s["t0"] and s["t1"] <= r["t0"] + CLOCK_SLACK
                       for s in named(gen_spans, "gen/step_call")) == 1
    if key == "seq":
        # dispatched in order, numbered as the engine counts its steps
        in_order = [s["attrs"]["seq"] for s in sorted(calls.values(), key=lambda s: s["t0"])]
        assert in_order == list(range(in_order[0], in_order[0] + len(in_order)))


# ---------------------------------------------------------------------------
# a request's way to its first token: four legs on one trace id (PR 35)
# ---------------------------------------------------------------------------


class DirectRpc:
    """``rpc.call`` straight into a method table on the caller's stack: the
    handler runs under the caller's ambient trace context, as the simulator's
    fabric does it and as the wire's ``t`` field does it across processes."""

    def __init__(self, methods):
        self.methods = methods

    def call(self, addr, method, payload, timeout=None):
        return self.methods[method](payload)


def client_rpc(engine):
    """(rpc, scheduler): ``generate_stream``'s way into a scheduler over ``engine``."""
    from dmlc_tpu.generate.worker import GenerateWorker, GenerationBackend

    sched = SlotScheduler(engine, max_waiting=N_REQUESTS)
    backend = GenerationBackend("lm_small")
    backend._scheduler = sched     # the engine is built and warm: nothing to build lazily
    return DirectRpc(GenerateWorker({"lm_small": backend}).methods()), sched


@pytest.fixture(scope="module")
def first_token_spans(lm_engine):
    """Six clients at once through ``generate_stream`` (four slots, so two
    wait for an exit), each polling every 2 ms."""
    from dmlc_tpu.generate.worker import generate_stream

    rng = np.random.default_rng(11)
    reqs = [(rng.integers(0, SPEC.num_outputs, size=int(rng.integers(3, 12))).tolist(),
             int(rng.integers(3, 9))) for _ in range(N_REQUESTS)]
    outs, errors = {}, []

    def client(i, rpc):
        prompt, n = reqs[i]
        try:
            outs[i] = list(generate_stream(rpc, "member", "lm_small", prompt, max_new_tokens=n,
                                           poll_interval_s=0.002))
        except BaseException as e:  # surfaced below
            errors.append(e)

    with traced_scenario():
        rpc, sched = client_rpc(lm_engine)
        threads = [threading.Thread(target=client, args=(i, rpc)) for i in range(N_REQUESTS)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
        finally:
            sched.stop()
        spans = wire(tracer.events_wire())
    assert not errors, errors
    assert [len(outs[i]) for i in range(N_REQUESTS)] == [n for _, n in reqs]
    by_trace = {}
    for s in spans:
        if s["name"] in ("cli/generate", "cli/first_token", "rpc/job.generate", "gen/wait", "gen/first"):
            by_trace.setdefault(s["trace"], {}).setdefault(s["name"], []).append(s)
    requests = [t for t in by_trace.values() if "cli/generate" in t]
    assert len(requests) == N_REQUESTS
    return requests


@pytest.mark.parametrize("name,under", [("gen/first", "rpc/job.generate"),
                                        ("cli/first_token", "cli/generate")])
def test_first_token_record_once_per_request_under_its_trace(first_token_spans, name, under):
    """Each request's trace holds exactly one of the record, a child of the
    span that was ambient where the request entered that layer."""
    for request in first_token_spans:
        (record,), (parent,) = request[name], request[under]
        assert record["parent"] == parent["span"] and record["dur"] > 0.0
    assert len({r[name][0]["span"] for r in first_token_spans}) == N_REQUESTS


def legs(request):
    """(A, B, C, D, whole) of one request, in seconds."""
    (cli,), (wait,), (first,) = request["cli/first_token"], request["gen/wait"], request["gen/first"]
    return (wait["t0"] - cli["t0"], wait["dur"], first["dur"], cli["t1"] - first["t1"], cli["dur"])


def all_but_one(errors, room=1e-3):
    """Three records a request are each dated back from a clock read of
    their own: a loaded host can put a preemption between a duration's read
    and the record's. One such among the requests is allowed, none systematic."""
    return sum(abs(e) > room for e in errors) <= 1


def test_gen_first_starts_where_gen_wait_ended(first_token_spans):
    assert all_but_one([r["gen/first"][0]["t0"] - r["gen/wait"][0]["t1"] for r in first_token_spans])
    # the two that waited for a slot waited longer; a first token takes a prefill run at least
    waits = sorted(r["gen/wait"][0]["dur"] for r in first_token_spans)
    assert waits[-2] > waits[3]


def test_four_legs_sum_to_the_clients_first_token(first_token_spans):
    """A (in) + B (gen/wait) + C (gen/first) + D (out) = cli/first_token,
    every leg taken where it happens and none negative."""
    parts = [legs(r) for r in first_token_spans]
    assert all(min(a, b, c, d) >= -CLOCK_SLACK for a, b, c, d, _ in parts), parts
    assert all_but_one([a + b + c + d - whole for a, b, c, d, whole in parts]), parts


def test_cli_first_token_lies_inside_cli_generate_and_ends_before_it(first_token_spans):
    for request in first_token_spans:
        (cli,), (whole,) = request["cli/first_token"], request["cli/generate"]
        assert whole["t0"] - CLOCK_SLACK <= cli["t0"] and cli["t1"] <= whole["t1"] + CLOCK_SLACK
        assert request["gen/first"][0]["t1"] <= cli["t1"] + CLOCK_SLACK


# ---------------------------------------------------------------------------
# disabled: nothing recorded, no thread clock read, no context copied
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ("predict", "generate", "client"))
def test_disabled_tracer_records_nothing_and_reads_no_thread_clock(
        path, backend, lm_engine, monkeypatch):
    def boom():
        raise AssertionError("time.thread_time read with the tracer off")

    def no_instant():
        raise AssertionError("the tracer's clock read with the tracer off")

    def no_copy():
        raise AssertionError("a context was copied with the tracer off")

    assert not tracer.enabled
    tracer.reset()
    monkeypatch.setattr(time, "thread_time", boom)
    if path == "predict":
        from dmlc_tpu.parallel import inference

        monkeypatch.setattr(inference.contextvars, "copy_context", no_copy)
        be, synsets = backend
        assert len(be(synsets)) == len(synsets)
    elif path == "generate":
        # the run leaves (gen/release too) are the shared no-op span; gen/wait and gen/first keep no instant
        monkeypatch.setattr(tracer, "now", no_instant)
        sched = SlotScheduler(lm_engine, max_waiting=2)
        try:
            assert len(sched.submit([1, 2, 3], max_new_tokens=3).result(timeout=60)) == 3
        finally:
            sched.stop()
    else:
        from dmlc_tpu.generate.worker import generate_stream

        monkeypatch.setattr(tracer, "now", no_instant)
        rpc, sched = client_rpc(lm_engine)
        try:
            assert len(list(generate_stream(rpc, "member", "lm_small", [1, 2, 3], max_new_tokens=3,
                                            poll_interval_s=0.002))) == 3
        finally:
            sched.stop()
    assert tracer.event_count == 0 and tracer.summary() == {}


# ---------------------------------------------------------------------------
# the tracer itself: ids, cpu=True
# ---------------------------------------------------------------------------


def test_span_ids_16_hex_unique_across_threads():
    """100,000 spans from two threads: every span id and trace id is 16 hex
    characters and no id repeats."""
    t = Tracer(max_events=200_000)
    t.enabled = True

    def burn():
        for _ in range(50_000):
            with t.span("x"):
                pass

    threads = [threading.Thread(target=burn) for _ in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not any(th.is_alive() for th in threads)
    events = t.events_wire()
    assert len(events) == 100_000
    ids = [e["span"] for e in events] + [e["trace"] for e in events]
    assert len(set(ids)) == 200_000
    hex16 = re.compile(r"[0-9a-f]{16}\Z")
    assert all(hex16.match(i) for i in ids)
    assert t.summary()["x"]["count"] == 100_000


def test_ids_leave_the_global_random_state_alone():
    random.seed(1234)
    want = random.random()
    random.seed(1234)
    ids = {tracectx.new_id() for _ in range(1000)}
    assert random.random() == want and len(ids) == 1000


@pytest.mark.parametrize("cpu", (True, False))
def test_cpu_attr_only_when_asked(cpu):
    t = Tracer()
    t.enabled = True
    with t.span("sleeps", cpu=cpu, n=1):
        time.sleep(0.05)
    (e,) = t.events_wire()
    assert e["attrs"]["n"] == 1
    if cpu:
        assert 0.0 <= e["attrs"]["cpu_s"] < 0.04 <= e["dur"]
    else:
        assert "cpu_s" not in e["attrs"]


@pytest.mark.parametrize("enabled", (True, False))
def test_span_set_adds_what_the_block_counted(enabled):
    t = Tracer()
    t.enabled = enabled
    with t.span("x", n=3) as span:
        span.set(misses=2)
    events = t.events_wire()
    assert [e["attrs"] for e in events] == ([{"n": 3, "misses": 2}] if enabled else [])


def test_span_records_error_and_reraises():
    t = Tracer()
    t.enabled = True
    with pytest.raises(KeyError):
        with t.span("fails", cpu=True):
            raise KeyError("x")
    (e,) = t.events_wire()
    assert e["attrs"]["error"] == "KeyError" and tracectx.current() is None
