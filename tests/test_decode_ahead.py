"""The decode loop keeps one turn's program runs in flight (generate/slots.py
``_turn``): it dispatches step t, and the turn's prefill run, before it reads
step t-1, with the last-token register on the device.

- token identity: one schedule (admissions mid-decode, an ``eos`` exit, a
  ``max_tokens`` exit, a cancel, a deadline exit, a page-pool eviction, a slot
  re-admitted while its old step is in flight) gives every request the tokens
  serial ``join`` + ``step()`` give it alone, for a tiny model of each of the
  four families, paged and contiguous, greedy and sampled; both programs keep
  their one compiled entry;
- order: step t is dispatched before step t-1 is read, and everything in
  flight is read before ``gen/idle`` and in ``stop()``;
- a run that fails when it is read fails the streams it failed when it was
  read at once (a step: every resident; a prefill run: its batch);
- the arrays a program's call replaced (the donated pools and recurrent
  state) are let go by no dispatch half: the engine keeps them, lets them go
  before a read for as long as the run's result is not ready, never holds
  more than its bound, and holds none after ``stop()``, ``_fail_everyone`` or
  a read that raised.
"""

import gc
import threading
import time
import weakref

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dmlc_tpu.generate.engine import Admission, GenerationEngine  # noqa: E402
from dmlc_tpu.generate.slots import SlotScheduler  # noqa: E402
from dmlc_tpu.models.registry import get_model  # noqa: E402

FAMILIES = ("lm_small", "nemotron_h_tiny", "olmo_hybrid_tiny", "lfm2_moe_tiny")
PAGE = 8


class Expiry:
    """A deadline the test expires by hand."""

    def __init__(self) -> None:
        self.over = False

    def expired(self) -> bool:
        return self.over


_VARIABLES: dict = {}
_ENGINES: dict = {}


def variables_of(model):
    if model not in _VARIABLES:
        _, _VARIABLES[model] = get_model(model).init_params(
            jax.random.PRNGKey(3), dtype=jnp.float32)
    return _VARIABLES[model]


def engines_of(model, cache):
    """The engine the loop drives and its serial twin, built once a (family,
    cache mode): the greedy and the sampled schedule run on the same two, so
    the compiled-entry count is that of a mixed lifetime. 8 usable pages:
    what the schedule's eight staged requests reserve between them."""
    if (model, cache) not in _ENGINES:
        _ENGINES[model, cache] = tuple(
            GenerationEngine(model, variables=variables_of(model), max_slots=4, page_size=PAGE,
                             num_pages=9, max_prefill=16, cache=cache)
            for _ in range(2))
    return _ENGINES[model, cache]


def small_engine():
    return GenerationEngine("lm_small", variables=variables_of("lm_small"), max_slots=4,
                            page_size=PAGE, num_pages=64, max_prefill=16)


def serial_tokens(engine, prompt, n, temperature, seed):
    """What the request gets alone: ``join`` then ``step()`` by ``step()``."""
    toks = [engine.join(0, np.asarray(prompt, np.int32), temperature=temperature, seed=seed)]
    for _ in range(n - 1):
        engine.ensure_capacity(0)
        toks.append(int(engine.step()[0]))
    engine.release(0)
    return toks


def in_flight(sched):
    """The unread prefill run and the unread step (None where there is none)."""
    return [sched._prefill_in_flight, sched._step_in_flight]


def drive(sched, until, turns=400):
    for _ in range(turns):
        if until():
            return
        sched._turn()
    raise AssertionError("the schedule did not finish")


@pytest.mark.parametrize("temperature", (0.0, 0.9), ids=("greedy", "sampled"))
@pytest.mark.parametrize("cache", ("paged", "contiguous"))
@pytest.mark.parametrize("model", FAMILIES)
def test_ahead_loop_is_token_identical_to_serial_steps(model, cache, temperature):
    """The loop's turns are called one by one from here (the decode thread is
    never started), so the schedule is the same every time."""
    engine, twin = engines_of(model, cache)
    paged = cache == "paged"
    vocab = engine.vocab
    rng = np.random.default_rng(17)
    #         name: (prompt length, max_new_tokens)
    sizes = {"A": (3, 5), "B": (4, 12), "C": (2, 30), "V": (7, 12),
             "E": (5, 3), "F": (3, 1), "G": (4, 2), "D": (3, 30)}
    prompts = {k: rng.integers(0, vocab, size=n).tolist() for k, (n, _) in sizes.items()}
    seeds = {k: 9000 + i for i, k in enumerate(sizes)}
    alone = {k: serial_tokens(twin, prompts[k], sizes[k][1], temperature, seeds[k])
             for k in sizes}
    # B stops at the first token of a STEP that it has not seen before (a tiny greedy model
    # repeats itself; an eos on the first token would be read before any step was thrown away).
    eos_at = next(i for i in range(1, 12) if alone["B"][i] not in alone["B"][:i])
    expiry = Expiry()

    sched = SlotScheduler(engine, max_waiting=8, autostart=False)
    steps_before = engine.steps
    admitted_over_a_step_in_flight = []
    real_dispatch_admit = engine.dispatch_admit

    def dispatch_admit(batch):
        step = sched._step_in_flight
        stale = {slot for slot, req in (step.seats if step else ()) if req.stream.done}
        admitted_over_a_step_in_flight.extend(a.slot for a in batch if a.slot in stale)
        return real_dispatch_admit(batch)

    engine.dispatch_admit = dispatch_admit
    try:
        streams = {k: sched.submit(
            prompts[k], max_new_tokens=sizes[k][1], temperature=temperature, seed=seeds[k],
            eos_id=alone["B"][eos_at] if k == "B" else None,
            deadline=expiry if k == "D" else None) for k in sizes}
        # A, B, C, V take the four slots in one run; E, F, G, D wait, holding a page each.
        sched._turn()
        assert len(sched._resident) == 4 and len(sched._pending) == 4
        drive(sched, lambda: len(streams["C"].tokens()) >= 4)
        streams["C"].cancel()
        drive(sched, lambda: len(streams["D"].tokens()) >= 2)
        expiry.over = True
        drive(sched, lambda: all(s.done for s in streams.values()))
        assert not any(in_flight(sched)) and not sched._resident
    finally:
        engine.dispatch_admit = real_dispatch_admit

    # max_tokens exits: whole, and nothing else in the stream.
    for k in ("A", "E", "F", "G"):
        assert streams[k].error is None and streams[k].tokens() == alone[k], k
    # eos: seen a step late, the row after it computed and never delivered.
    assert streams["B"].error is None
    assert streams["B"].tokens() == alone["B"][: eos_at + 1]
    assert sched.tokens_discarded >= 1
    # cancel and deadline: typed, and what was delivered is a prefix.
    for k, kind in (("C", "cancelled:"), ("D", "deadline:")):
        got = streams[k].tokens()
        assert streams[k].error.startswith(kind), (k, streams[k].error)
        assert 0 < len(got) < sizes[k][1] and got == alone[k][: len(got)], k
    if paged:
        # V's second step needs a second page before any request has left, and
        # the pool is empty (eight requests hold a page each): out it goes with
        # its first token (read in the turn that admitted it), its first step's row still in flight.
        assert streams["V"].error.startswith("overloaded: evicted mid-decode")
        assert sched.evictions == 1 and streams["V"].tokens() == alone["V"][:1]
        assert sched.tokens_discarded >= 2
        assert engine.pages_free == engine.cache.allocator.pages_total
    else:
        assert streams["V"].error is None and streams["V"].tokens() == alone["V"]
    # B's slot was taken again while the step that still computed B's row was in flight.
    assert admitted_over_a_step_in_flight
    # Every step but a busy period's first was dispatched with the one before it unread.
    assert sched.steps_ahead >= engine.steps - steps_before - 4 > 0
    assert sched.completions == 6 + (not paged)  # a deadline exit counts, a cancel and an eviction do not
    assert engine.jit_cache_sizes() == {"step": 1, "prefill": 1}
    assert twin.jit_cache_sizes() == {"step": 1, "prefill": 1}
    assert not engine.active.any()


# ---------------------------------------------------------------------------
# order, with the engine's four halves recorded
# ---------------------------------------------------------------------------


@pytest.fixture()
def recorded():
    """A small engine whose dispatch and collect calls are written down, in
    order, with the scheduler's verdict that it may sleep."""
    engine = small_engine()
    engine.warmup()
    log: list[str] = []
    for name in ("dispatch_step", "collect_step", "dispatch_admit", "collect_admit"):
        def spy(*args, _real=getattr(engine, name), _name=name):
            log.append(_name)
            return _real(*args)
        setattr(engine, name, spy)
    return engine, log


def scheduler_on(engine, log, **kw):
    sched = SlotScheduler(engine, max_waiting=8, autostart=False, **kw)
    real_idle = sched._idle

    def idle():
        verdict = real_idle()
        if verdict and (not log or log[-1] != "idle"):
            log.append("idle")
        return verdict

    sched._idle = idle
    return sched


def positions(log, name):
    return [i for i, event in enumerate(log) if event == name]


def test_step_t_is_dispatched_before_step_t_minus_1_is_read(recorded):
    engine, log = recorded
    sched = scheduler_on(engine, log)
    try:
        streams = [sched.submit([1 + i, 2, 3], max_new_tokens=6 + 3 * i) for i in range(3)]
        sched.start()
        outs = [s.result(timeout=60) for s in streams]
        time.sleep(0.05)  # the loop reaches gen/idle
    finally:
        sched.stop()
    assert [len(o) for o in outs] == [6, 9, 12]
    dispatched, read = positions(log, "dispatch_step"), positions(log, "collect_step")
    assert len(dispatched) == len(read) == engine.steps == 11
    # One busy period: every step after the first leaves before the one before it is read...
    assert all(dispatched[t] < read[t - 1] for t in range(1, len(read)))
    # ...and each is read exactly one turn late, never two.
    assert all(read[t - 1] < dispatched[t + 1] for t in range(1, len(read) - 1))
    assert sched.steps_ahead == 10
    # The prefill run is read in its own turn, after the turn's step has left: the
    # device has that step to do while the host delivers and prepares the next turn.
    assert (positions(log, "dispatch_admit")[0] < dispatched[0]
            < positions(log, "collect_admit")[0] < dispatched[1] < read[0])
    # Before the loop sleeps, everything it dispatched has been read.
    sleeps = positions(log, "idle")
    assert sleeps
    for at in sleeps:
        before = log[:at]
        assert before.count("dispatch_step") == before.count("collect_step")
        assert before.count("dispatch_admit") == before.count("collect_admit")


def test_stop_reads_what_is_in_flight_then_fails_the_rest(recorded):
    engine, log = recorded
    sched = scheduler_on(engine, log)
    try:
        short = sched.submit([5, 6], max_new_tokens=40)
        sched.start()
        while len(short.tokens()) < 3:
            time.sleep(0.001)
    finally:
        sched.stop()
    assert short.done and short.error == "overloaded: scheduler stopped"
    assert log.count("dispatch_step") == log.count("collect_step")
    assert log.count("dispatch_admit") == log.count("collect_admit")
    assert not any(in_flight(sched)) and not engine.active.any()
    assert engine.pages_free == engine.cache.allocator.pages_total


def test_a_request_whose_last_token_is_in_flight_at_stop_ends_whole(recorded):
    """Driven by hand up to the turn that dispatched the last token, then the
    thread starts into a closed scheduler: it reads the step and delivers."""
    engine, log = recorded
    sched = scheduler_on(engine, log)
    stream = sched.submit([7, 8, 9], max_new_tokens=2)
    sched._turn()  # the prefill run, read in its own turn, and the step that computes the second token
    assert len(stream.tokens()) == 1 and not stream.done
    assert in_flight(sched)[0] is None and in_flight(sched)[1] is not None
    sched._closed = True
    sched.start()
    sched.stop()
    assert stream.done and stream.error is None and len(stream.tokens()) == 2


# ---------------------------------------------------------------------------
# a run that fails where it is read
# ---------------------------------------------------------------------------


def fail_once(engine, name, seen: threading.Event):
    real = getattr(engine, name)

    def broken(run):
        setattr(engine, name, real)
        real(run)  # the device did its part; the read is what fails
        seen.set()
        raise RuntimeError("device said no")

    setattr(engine, name, broken)


def reference(prompt, n):
    twin = engines_of("lm_small", "paged")[1]
    return serial_tokens(twin, prompt, n, 0.0, None)


@pytest.fixture()
def served():
    engine = small_engine()
    sched = SlotScheduler(engine, max_waiting=8)
    yield engine, sched
    sched.stop()


def test_a_step_that_fails_at_its_read_fails_every_resident(served):
    engine, sched = served
    residents = [sched.submit([1, 2, 3 + i], max_new_tokens=50) for i in range(2)]
    while not all(s.tokens() for s in residents):
        time.sleep(0.001)
    seen = threading.Event()
    fail_once(engine, "collect_step", seen)
    for s in residents:
        assert s.wait(60) and s.error == "RpcError: generation engine failed"
    assert seen.is_set()
    # The loop and the engine are whole: nothing in flight, every page back, the next request served.
    assert sched.submit([9, 9], max_new_tokens=3).result(timeout=60) == reference([9, 9], 3)
    assert engine.pages_free == engine.cache.allocator.pages_total and not any(in_flight(sched))


def test_a_prefill_run_that_fails_at_its_read_fails_its_batch_alone(served):
    engine, sched = served
    resident = sched.submit([1, 2, 3], max_new_tokens=40)
    while not resident.tokens():
        time.sleep(0.001)
    seen = threading.Event()
    fail_once(engine, "collect_admit", seen)
    batch = [sched.submit([4, 5], max_new_tokens=4), sched.submit([6, 7, 8], max_new_tokens=4)]
    for s in batch:
        assert s.wait(60) and s.error == "RuntimeError: device said no" and s.tokens() == []
    assert seen.is_set()
    assert resident.result(timeout=60) == reference([1, 2, 3], 40)
    assert sched.submit([9, 9], max_new_tokens=3).result(timeout=60) == reference([9, 9], 3)
    assert engine.pages_free == engine.cache.allocator.pages_total
    assert sched.completions == 2 and sched.tokens_discarded >= 1


def test_step_and_admit_are_their_halves_in_a_row():
    """Outside the loop ``step()`` / ``admit()`` / ``join()`` give what they gave:
    the register is read back through ``last_tokens`` and can be forced."""
    engine, twin = engines_of("lm_small", "paged")
    first = engine.join(1, [3, 1, 4], temperature=0.0)
    run = twin.dispatch_admit([Admission(1, [3, 1, 4])])
    assert twin.collect_admit(run) == [first] and engine.last_tokens[1] == first
    np.testing.assert_array_equal(engine.lengths, twin.lengths)
    engine.ensure_capacity(1), twin.ensure_capacity(1)
    steps_before = twin.steps
    handle = twin.dispatch_step()
    assert twin.lengths[1] == 4 and twin.steps == steps_before + 1  # advanced before the read
    assert engine.step()[1] == twin.collect_step(handle)[1] == twin.last_tokens[1]  # the active row
    forced = engine.last_tokens.copy()
    forced[1] = 7
    engine.last_tokens = forced
    assert engine.last_tokens[1] == 7
    for e in (engine, twin):
        e.release(1)
        assert e.jit_cache_sizes() == {"step": 1, "prefill": 1}


# ---------------------------------------------------------------------------
# the arrays a call replaced: kept by the engine, let go where the thread waits
# ---------------------------------------------------------------------------


def device_state(engine):
    """The pools and every leaf of the recurrent state, as the engine holds them now."""
    return jax.tree_util.tree_leaves((engine._k_state, engine._v_state, engine._r_state))


class Result:
    """A run's tokens on the device, for ``_release``: not ready for the first ``polls`` asks."""

    def __init__(self, polls=0):
        self.polls = polls

    def is_ready(self):
        self.polls -= 1
        return self.polls < 0


def always_ready(engine):
    """Every collect of ``engine`` finds its run's result ready, as where the
    host sets the pace: only the bound lets replaced arrays go."""
    real = engine._release
    engine._release = lambda result, **number: real(Result(), **number)


@pytest.mark.parametrize("program", ("prefill", "step"))
@pytest.mark.parametrize("cache", ("paged", "contiguous"))
@pytest.mark.parametrize("model", ("lm_small", "nemotron_h_tiny"))
def test_a_dispatch_lets_no_replaced_array_go(model, cache, program):
    """When a dispatch half returns, what the engine held before the call is
    donated (it holds no memory) and still referenced: by the engine's stock,
    last in; the engine's state is the call's outputs."""
    engine, _ = engines_of(model, cache)
    if program == "step":
        engine.join(0, [3, 1, 4])
        engine.ensure_capacity(0)
    before = device_state(engine)
    assert (len(before) > 2) == (model != "lm_small")  # a hybrid keeps recurrent state beside the pools
    refs = [weakref.ref(a) for a in before]
    run = (engine.dispatch_step() if program == "step"
           else engine.dispatch_admit([Admission(0, [3, 1, 4])]))
    assert all(a.is_deleted() for a in before)
    kept = list(engine._replaced)[-len(before):]
    assert len(kept) == len(before) and all(held is was for held, was in zip(kept, before))
    now = device_state(engine)
    assert not any(a.is_deleted() for a in now) and not any(
        new is old for new in now for old in before)
    if cache == "paged":  # whoever reads the cache's pools sees the engine's
        assert engine.cache.k_pages is engine._k_state and engine.cache.v_pages is engine._v_state
    del before, kept
    gc.collect()
    assert all(r() is not None for r in refs)
    (engine.collect_step if program == "step" else engine.collect_admit)(run)
    assert len(engine._replaced) <= engine._replaced_max
    engine.release(0)
    engine.release_replaced()
    gc.collect()
    assert not engine._replaced and all(r() is None for r in refs)


@pytest.mark.parametrize("stock,polls,left", [
    (5, 0, 5),      # the result is ready and the stock within its bound: nothing goes
    (5, 3, 2),      # not ready for three asks: three go, the oldest
    (5, 9, 0),      # a wait longer than the stock: all of it, then the read
    (40, 0, 16),    # ready, over the bound: down to the bound, no further
    (40, 30, 10),   # over the bound AND a wait: the wait takes what it can
])
def test_release_lets_go_while_the_result_is_not_ready_and_beyond_that_the_excess(stock, polls, left):
    engine = small_engine()
    assert engine._replaced_max == 16  # eight runs' worth of two pools
    husks = [object() for _ in range(stock)]
    engine._replaced.extend(husks)
    engine._release(Result(polls), seq=0)
    assert list(engine._replaced) == husks[stock - left:]


def test_the_stock_of_replaced_arrays_is_bounded_and_holds_husks_only():
    engine = GenerationEngine("nemotron_h_tiny", variables=variables_of("nemotron_h_tiny"),
                              max_slots=4, page_size=PAGE, num_pages=64, max_prefill=16)
    always_ready(engine)
    engine.join(0, [3, 1, 4])
    for _ in range(3 * 8):
        engine.ensure_capacity(0)
        engine.step()
        assert len(engine._replaced) <= engine._replaced_max == 8 * 4
    assert len(engine._replaced) == engine._replaced_max
    assert all(a.is_deleted() for a in engine._replaced)


def test_a_replaced_array_that_was_not_donated_is_not_kept():
    """Keeping an array that still owns its memory would keep a second copy of a pool."""
    engine = small_engine()
    live = device_state(engine)
    engine._set_state(*(a + 0 for a in live), engine._r_state)
    assert not engine._replaced and not any(a.is_deleted() for a in live)


def test_a_run_that_admits_nobody_replaces_nothing():
    engine, _ = engines_of("lm_small", "paged")
    before, stock = device_state(engine), len(engine._replaced)
    run = engine.dispatch_admit([Admission(0, [])])
    assert run.tokens is None
    (refused,) = engine.collect_admit(run)
    assert isinstance(refused, ValueError) and len(engine._replaced) == stock
    assert all(now is was for now, was in zip(device_state(engine), before))


class Unreadable:
    """A run's tokens whose read raises: the device said no."""

    def is_ready(self):
        return True

    def __array__(self, *args, **kwargs):
        raise RuntimeError("device said no")


@pytest.mark.parametrize("how", ("stop", "fail_everyone", "fail_everyone_mid_turn",
                                 "step_read_raises", "prefill_read_raises"))
@pytest.mark.parametrize("model", ("lm_small", "nemotron_h_tiny"))
def test_no_replaced_array_outlives_the_serving(model, how):
    """However the loop ends, the engine keeps none of the arrays its calls
    replaced. The turns are called by hand and every result is found ready,
    so what the engine holds at the end is known."""
    engine = GenerationEngine(model, variables=variables_of(model), max_slots=4, page_size=PAGE,
                              num_pages=64, max_prefill=16)
    always_ready(engine)
    sched = SlotScheduler(engine, max_waiting=8, autostart=False)
    streams = [sched.submit([1, 2, 3 + i], max_new_tokens=40) for i in range(2)]
    refs = []

    def held():
        """The engine's stock is not empty; remember what is in it."""
        refs.extend(weakref.ref(a) for a in engine._replaced)
        return len(engine._replaced)

    if how == "fail_everyone_mid_turn":
        sched._admit_pending()  # the prefill run dispatched and not read
        assert sched._prefill_in_flight is not None and held()
        sched._fail_everyone("RpcError: generation engine failed")
    elif how == "prefill_read_raises":
        real = engine.collect_admit
        engine.collect_admit = lambda run: real(run._replace(tokens=Unreadable()))
        sched._turn()  # admits, dispatches a step, fails at the run's read: the batch's streams
        assert all(s.done and s.error == "RuntimeError: device said no" for s in streams)
        assert held()
        sched._fail_everyone("RpcError: generation engine failed")  # the step still in flight
    else:
        sched._turn()
        sched._turn()
        assert sched._step_in_flight is not None and held()
        if how == "stop":
            sched._closed = True
            sched.start()
            sched.stop()
        elif how == "fail_everyone":
            sched._fail_everyone("RpcError: generation engine failed")
        else:
            real = engine.collect_step
            engine.collect_step = lambda run: real(run._replace(tokens=Unreadable()))
            try:
                sched._turn()  # dispatches a step, then fails at the read of the one before
            except RuntimeError:
                held()
            else:
                raise AssertionError("the read did not raise")
            sched._fail_everyone("RpcError: generation engine failed")  # as the loop does
    assert all(s.done and s.error for s in streams)
    assert not any(in_flight(sched)) and not engine.active.any()
    gc.collect()
    assert refs and not engine._replaced and all(r() is None for r in refs)
    # what the engine holds is whole: the next request is served from it
    assert not any(a.is_deleted() for a in device_state(engine))
    vars(engine).pop("collect_step", None), vars(engine).pop("collect_admit", None)
    assert len(engine.admit([Admission(0, [9, 9])])) == 1 and engine.step().shape == (4,)
