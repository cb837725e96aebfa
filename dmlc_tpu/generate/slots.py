"""Step-level slot scheduler: continuous batching over the generation engine.

``scheduler/worker.DynamicBatcher`` coalesces ONE-SHOT predict requests into
a batch and disbands it after a single device dispatch. Generation needs the
Orca-style evolution of that idea: the batch is PERSISTENT (one jitted
decode step ticking at a fixed shape) and requests are SLOTS that join and
leave it between steps — a 5-token reply exits after 5 steps while a
500-token neighbor keeps its slot, and the freed slot (plus its recycled KV
pages) admits the next waiting request immediately. Throughput scales with
resident slots at roughly constant step cost, which is the 2x-over-
sequential pin in tests/test_generate_cluster.py.

Admission follows the predict path's overload contract (docs/OVERLOAD.md):

- submit-time shed — no free slot (and the bounded wait queue full) or not
  enough free pages for the prompt+1 reservation raises a typed
  ``Overloaded`` with a retry-after hint; nothing buffers toward a
  guaranteed deadline miss. Flight-recorder ``shed`` events mark each.
- deadline-carrying — a request captures the ambient RPC deadline
  (cluster/deadline.py) at submit; the decode loop exits expired slots
  with a ``deadline:``-typed error between steps, never mid-step.
- mid-decode eviction — a slot whose next token needs a page the pool
  cannot grant is EVICTED with a typed ``Overloaded`` error (flight
  ``slot_evict``): admission only reserved its prompt, so a full pool is
  the overload signal arriving late, and the evicted client retries
  against the retry-after hint like any shed.

Tokens stream out through per-request ``GenStream``s: seq-numbered chunks
retained until the consumer's cumulative ack — the exactly-once delivery
substrate the RPC worker (generate/worker.py) exposes as
``job.generate_poll`` (wire format: docs/GENERATE.md).

The loop keeps one decode step IN FLIGHT (``SlotScheduler._turn``): a turn
dispatches its prefill run and its step, reads the step of the turn before,
and only then waits for its own prefill run, so the device has a step queued
while the host reads, delivers and prepares. What the host knows without a
result decides who is in a step (cancel, deadline, ``emitted + in flight >=
max_new_tokens``, page growth); an ``eos`` is seen one step late and the row
computed meanwhile is thrown away (``gen_tokens_discarded``).

Tracing: a loop turn has ONE ``gen/step`` span, which covers the dispatch of
its step (children ``gen/step_operands``, the register copies, and
``gen/step_call``, the jitted call alone: ``GenerationEngine.dispatch_step``)
and the read of the step before (children ``gen/release``, then
``gen/step_sync``); its attributes
(``slots``, ``ahead``, ``pages_bound``, ``tokens_resident``, the family's
counts) are those of the step it READ, and ``seq`` on the call and on the sync
joins a step's dispatch to its read a turn later. It is bound to the OLDEST
resident slot's submit-time trace context, so a request's timeline
shows the steps that produced its tokens parented under its
``rpc/job.generate`` span (trace smoke asserts this). ONE run of the prefill
program admits every request the loop turn admits: its dispatch is two
children of ``gen/admit`` (``gen/prefill_operands``: checks, page binding,
operand arrays; ``gen/prefill_call``: the jitted call alone), and it has ONE
``gen/prefill`` span: its READ, at the end of the turn that dispatched it
(children ``gen/release`` and ``gen/prefill_sync``, joined to the call by
``run``), with the run's attribute ``prompts``, bound to the oldest admitted
request. Each request has
two records under its own context: ``gen/wait`` (submit to the admission that
dispatches its run) and ``gen/first`` (from there to the push of its first
token), which abut. The decode thread feeds the device, so its time
is TILED by leaf spans (docs/OBSERVABILITY.md §1): ``gen/idle`` (waiting for
work), ``gen/retire`` (the resident sweep, page growth), ``gen/deliver``
(token pushes, exits), the four dispatch leaves, ``gen/release`` and the two
syncs; what is
left as the SELF time of ``gen/admit`` (who gets a slot, seating),
``gen/step`` and ``gen/prefill`` is bookkeeping — an idle gap of the chip
always has an owner on this thread.

The arrays a program's call replaced (the donated pools and recurrent state:
husks that hold no device memory) are let go by NO dispatch half: each costs
the decode thread the interpreter and the wait to win it back among the
polling clients. They wait in the engine (``GenerationEngine._replaced``) and
go in ONE place, the leaf ``gen/release`` before a run's sync (once per
program run, numbered ``seq`` / ``run`` like the call and the sync): for as
long as the run's result is not ready (``waiting`` of its ``arrays``: time the
thread would wait in the read anyway), beyond that only what the engine's
bounded stock cannot keep. ``_fail_everyone``, and so ``stop()``, empties it.
"""

from __future__ import annotations

import logging
import os
import threading
from collections.abc import Callable, Iterable
from time import monotonic
from typing import Any, NoReturn

from dmlc_tpu.cluster import deadline as deadline_mod
from dmlc_tpu.cluster import tenant as tenant_mod
from dmlc_tpu.cluster import tracectx
from dmlc_tpu.cluster.rpc import Overloaded
from dmlc_tpu.generate.engine import Admission
from dmlc_tpu.generate.kvcache import PagePoolExhausted
from dmlc_tpu.utils import tracing
from dmlc_tpu.utils.metrics import LatencyStats
from dmlc_tpu.utils.tracing import tracer

log = logging.getLogger(__name__)


class GenStream:
    """One request's token stream with exactly-once chunk delivery.

    Producer side (the decode loop): ``push`` appends tokens; ``finish``
    seals the stream (optionally with a typed error string). Consumer side:
    ``chunks_after(ack)`` returns every chunk with seq > ack — chunks are
    retained until covered by a later cumulative ack, so a lost/retried
    poll re-reads the same chunks and the consumer dedups by seq.
    ``tokens()``/``wait`` serve in-process consumers (CLI, tests).

    Lifecycle hooks for the session plane (generate/worker.py,
    scheduler/genrouter.py): ``cancel`` requests a cooperative exit — the
    decode loop retires the slot between steps with a ``cancelled:`` error;
    ``hold``/``unhold`` pin the stream against the worker's TTL sweep while
    a migration handoff is reading it; ``step_gen`` is the engine step
    count at the last delivered token, the sweep's liveness witness."""

    def __init__(self, request_id: str) -> None:
        self.request_id = request_id
        self._cv = threading.Condition()
        self._chunks: list[tuple[int, list[int]]] = []
        self._next_seq = 1
        self._all: list[int] = []
        self.done = False
        self.error: str | None = None
        self.acked = 0
        self.cancelled = False
        self.step_gen = 0
        self._holds = 0

    # ---- producer --------------------------------------------------------

    def push(self, tokens: list[int]) -> None:
        if not tokens:
            return
        with self._cv:
            if self.done:
                raise RuntimeError("stream already finished")
            self._chunks.append((self._next_seq, [int(t) for t in tokens]))
            self._next_seq += 1
            self._all.extend(int(t) for t in tokens)
            self._cv.notify_all()

    def finish(self, error: str | None = None) -> None:
        with self._cv:
            if self.done:
                return
            self.done = True
            self.error = error
            self._cv.notify_all()

    # ---- session-plane hooks --------------------------------------------

    def cancel(self) -> None:
        """Request a cooperative exit: the decode loop retires the slot
        between steps (never mid-step). Idempotent; a finished stream is
        left as-is."""
        with self._cv:
            self.cancelled = True
            self._cv.notify_all()

    def hold(self) -> None:
        with self._cv:
            self._holds += 1

    def unhold(self) -> None:
        with self._cv:
            self._holds = max(0, self._holds - 1)

    def held(self) -> bool:
        with self._cv:
            return self._holds > 0

    # ---- consumer --------------------------------------------------------

    def chunks_after(self, ack: int) -> dict[str, Any]:
        """The poll reply body: unacked chunks + completion state. ``ack``
        is cumulative — chunks with seq <= ack are dropped for good."""
        with self._cv:
            if ack > self.acked:
                self.acked = int(ack)
                self._chunks = [c for c in self._chunks if c[0] > self.acked]
            return {
                "chunks": [[seq, list(toks)] for seq, toks in self._chunks],
                "done": self.done,
                "error": self.error,
            }

    def drained(self) -> bool:
        """Finished AND every chunk acked — safe to garbage-collect."""
        with self._cv:
            return self.done and not self._chunks

    def wait(self, timeout: float | None = None) -> bool:
        with self._cv:
            self._cv.wait_for(lambda: self.done, timeout=timeout)
            return self.done

    def tokens(self) -> list[int]:
        with self._cv:
            return list(self._all)

    def result(self, timeout: float | None = None) -> list[int]:
        """Block until done; raise the stream's typed error if it failed."""
        if not self.wait(timeout):
            raise TimeoutError(f"generation {self.request_id} still running")
        with self._cv:
            if self.error is not None:
                from dmlc_tpu.cluster.rpc import remote_error

                raise remote_error(self.error)
            return list(self._all)


class _Slot:
    """Host-side request state riding one engine slot."""

    __slots__ = (
        "stream", "prompt", "max_new_tokens", "temperature", "eos_id",
        "deadline", "trace_ctx", "pages", "emitted", "in_flight", "slot",
        "submitted_t", "tenant", "seed", "wait_t0", "first_t0",
    )

    def __init__(self, stream: GenStream, prompt: list[int],
                 max_new_tokens: int, temperature: float, eos_id: int | None,
                 deadline: Any, trace_ctx: Any, pages: list[int],
                 submitted_t: float, tenant: str,
                 seed: int | None = None) -> None:
        self.stream = stream
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.eos_id = eos_id
        self.deadline = deadline
        self.trace_ctx = trace_ctx
        self.pages = pages
        self.emitted = 0
        # Tokens the device has been asked for and the loop has not read: a
        # prefill run's first token, a row of a step.
        self.in_flight = 0
        self.slot = -1
        self.submitted_t = submitted_t
        self.tenant = tenant
        self.seed = seed
        # Submit instant on the TRACER's clock (gen/wait is a span, so it
        # lives on the timebase spans live on, not the injectable clock).
        self.wait_t0 = tracer.now() if tracer.enabled else None
        # Where gen/wait ended and gen/first starts (set at admission, only
        # while the tracer is on; None again once the first token is pushed).
        self.first_t0: float | None = None


class _Run:
    """One program run the device has and the loop has not read: the
    engine's handle, who sat in each slot WHEN it was dispatched (the only
    requests its rows may go to), the span attributes that describe it, and
    the trace its spans are bound to."""

    __slots__ = ("handle", "seats", "attrs", "trace_ctx")

    def __init__(self, handle: Any, seats: list[tuple[int, _Slot]],
                 attrs: dict[str, Any], trace_ctx: Any) -> None:
        self.handle = handle
        self.seats = seats
        self.attrs = attrs
        self.trace_ctx = trace_ctx


class SlotScheduler:
    """Continuous-batching loop: admit between steps, step while anyone is
    resident, shed at the door when the slot table / page pool is full. The
    loop keeps one decode step in flight: it reads a step's tokens a turn
    after it dispatched it (``_turn``)."""

    def __init__(
        self,
        engine: Any,
        *,
        max_waiting: int = 0,
        name: str = "generate",
        metrics: Any = None,
        flight: Any = None,
        registry: Any = None,
        retry_after_s: float = 0.25,
        clock: Callable[[], float] = monotonic,
        autostart: bool = True,
        lane: Any = None,
        profile: Callable[[float], None] | None = None,
        tenants: Any = None,
    ) -> None:
        self.engine = engine
        self.name = name
        self.metrics = metrics
        self.flight = flight
        self.retry_after_s = float(retry_after_s)
        self.clock = clock
        # Cost-profile feed (cluster/profile.py): called with each decode
        # step's wall seconds so the node's profiler grows a gen/step lane.
        self.profile = profile
        # Node identity for span attribution (utils/tracing.lane): the
        # decode thread does not inherit the RPC server's ambient lane, so
        # it binds its own. A callable defers resolution to thread start
        # (the node's lane can still change while ports resolve).
        self.lane = lane
        # Bounded join queue beyond the slot table itself: 0 = no waiting,
        # a submit either takes a slot-table place or sheds.
        self.max_waiting = max(0, int(max_waiting))
        # Per-tenant quotas over the in-flight bound (cluster/tenant.py):
        # a tenant's share of (slot table + wait queue), enforced at
        # submit; eviction ordering below prefers low-priority-and-over-
        # quota residents. No tenants declared = legacy behavior.
        self.ledger = tenant_mod.TenantLedger(
            tenants, int(engine.max_slots) + self.max_waiting
        )
        # Autoscaler-adjustable soft bounds (scheduler/autoscaler.py):
        # max_active caps ADMITTED slots at <= the compiled slot table;
        # page_budget caps pages-in-use at <= the allocated pool (0 = the
        # pool itself). Both resize live — the compiled step shape and the
        # HBM pool never change, only how much of them admission hands out.
        self.max_active = int(engine.max_slots)
        self.page_budget = 0
        self._page_total = int(getattr(engine, "pages_free", 0))
        self._cv = threading.Condition()
        self._pending: list[_Slot] = []
        self._closed = False
        # Owned exclusively by the decode thread after admission.
        self._resident: list[_Slot] = []
        # Dispatched and unread (decode thread only): a prefill run from its
        # turn's admission to that turn's end; a step from its turn to the
        # next one's read. While the newer step is dispatched and the older
        # read, the turn holds the newer itself, everyone in it resident.
        self._prefill_in_flight: _Run | None = None
        self._step_in_flight: _Run | None = None
        self.steps_ahead = 0
        self.tokens_discarded = 0
        self.requests = 0
        self.sheds = 0
        self.evictions = 0
        self.completions = 0
        self.step_stats = LatencyStats()
        self.tokens_streamed = 0
        self._t_first_token: float | None = None
        self._t_last_token: float | None = None
        if registry is not None:
            registry.gauge(f"{name}_slots_active", lambda: self.engine.slots_active)
            registry.gauge(f"{name}_pages_free", lambda: self.engine.pages_free)
            registry.gauge(f"{name}_tok_s", self.tok_s)
            if engine.state.nbytes:
                registry.gauge(f"gen_state_bytes_{engine.model_name}",
                               lambda: self.engine.state_bytes_active)
        self._thread = threading.Thread(
            target=self._loop, name=f"gen-{name}", daemon=True
        )
        # ``autostart=False`` defers the decode thread so a test can stage
        # several submissions and observe a DETERMINISTIC admission order;
        # production always autostarts.
        if autostart:
            self._thread.start()

    def start(self) -> None:
        if not self._thread.is_alive():
            self._thread.start()

    # ---- request side ----------------------------------------------------

    def submit(
        self,
        prompt: Iterable[int],
        *,
        max_new_tokens: int,
        temperature: float = 0.0,
        eos_id: int | None = None,
        request_id: str | None = None,
        deadline: Any = None,
        seed: int | None = None,
        resume_tokens: Iterable[int] | None = None,
    ) -> GenStream:
        """Admit one generation request; returns its stream immediately.
        Sheds with a typed ``Overloaded`` when the slot table (plus the
        bounded wait queue) or the page pool cannot take it. Captures the
        ambient RPC deadline and trace context (the decode loop carries
        both forward).

        ``seed`` keys the engine's position-seeded sampling RNG.
        ``resume_tokens`` is the migration entry (docs/GENERATE.md
        §Migration): tokens already delivered to the client elsewhere are
        prefilled along with the prompt (same seed → the continuation is
        token-identical to the uninterrupted run), and the stream emits
        only the ``max_new_tokens`` NEW tokens from the resume point on."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must be non-empty")
        if resume_tokens is not None:
            prompt = prompt + [int(t) for t in resume_tokens]
        if len(prompt) > self.engine.max_prefill:
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds max_prefill="
                f"{self.engine.max_prefill}"
            )
        total = len(prompt) + int(max_new_tokens)
        if total > self.engine.max_tokens:
            raise ValueError(
                f"prompt+max_new_tokens={total} exceeds the engine's "
                f"max_tokens={self.engine.max_tokens}"
            )
        if deadline is None:
            deadline = deadline_mod.current()
        tenant = tenant_mod.current()
        stream = GenStream(request_id or os.urandom(6).hex())
        with self._cv:
            if self._closed:
                raise RuntimeError("slot scheduler is stopped")
            if self.ledger.would_exceed(tenant):
                self._shed(
                    f"tenant {tenant!r} at quota "
                    f"({self.ledger.active(tenant)}/{self.ledger.quota(tenant)})",
                    tenant=tenant, verdict="over_quota",
                )
            in_flight = len(self._resident) + len(self._pending)
            limit = min(int(self.engine.max_slots), self.max_active) + self.max_waiting
            if in_flight >= limit:
                self._shed(f"slot table full ({in_flight} in flight)",
                           tenant=tenant)
            if self.page_budget > 0 and \
                    self._page_total - self.engine.pages_free >= self.page_budget:
                self._shed(
                    f"page budget exhausted "
                    f"({self._page_total - self.engine.pages_free}/"
                    f"{self.page_budget} pages in use)",
                    tenant=tenant,
                )
            try:
                pages = self.engine.reserve(len(prompt))
            except PagePoolExhausted as e:
                self._shed(f"page pool exhausted: {e}", tenant=tenant)
            self.requests += 1
            if self.metrics is not None:
                self.metrics.inc("gen_requests")
            slot = _Slot(
                stream, prompt, int(max_new_tokens), float(temperature),
                eos_id, deadline, tracectx.current(), pages, self.clock(),
                tenant, seed,
            )
            self._pending.append(slot)
            self.ledger.acquire(tenant)
            self._cv.notify_all()
        return stream

    def _shed(self, why: str, tenant: str | None = None,
              verdict: str = "gate_full") -> NoReturn:
        self.sheds += 1
        if tenant is not None:
            self.ledger.note_shed(tenant)
        if self.metrics is not None:
            self.metrics.inc("shed")
            self.metrics.inc(f"shed_{self.name}")
            if verdict == "over_quota":
                self.metrics.inc(f"shed_over_quota_{self.name}")
        tracer.record(f"overload/shed_{self.name}", 0.0)
        if self.flight is not None:
            self.flight.note("shed", gate=self.name,
                             active=len(self._resident), tenant=tenant,
                             quota=verdict)
        raise Overloaded(f"{self.name}: {why}",
                         retry_after_s=self.retry_after_s,
                         tenant=tenant, quota=verdict)

    def set_limits(self, max_active: int | None = None,
                   page_budget: int | None = None) -> dict[str, int]:
        """Autoscaler actuation seam: resize the admitted share of the
        slot table / page pool. Clamped to the compiled/allocated sizes —
        the engine itself never reshapes. Returns the effective limits."""
        with self._cv:
            if max_active is not None:
                self.max_active = max(1, min(int(max_active),
                                             int(self.engine.max_slots)))
            if page_budget is not None:
                pb = int(page_budget)
                if pb <= 0 or (self._page_total and pb >= self._page_total):
                    self.page_budget = 0
                else:
                    self.page_budget = max(1, pb)
            return {"max_active": self.max_active,
                    "page_budget": self.page_budget}

    # ---- decode loop -----------------------------------------------------

    def _loop(self) -> None:
        lane_name = self.lane() if callable(self.lane) else self.lane
        with tracing.lane(lane_name):
            self._loop_body()

    def _idle(self) -> bool:
        """Nothing to admit, step, read or shut down (under ``_cv``)."""
        return not (self._pending or self._resident or self._step_in_flight or self._closed)

    def _loop_body(self) -> None:
        while True:
            with self._cv:
                if self._idle():
                    with tracer.span("gen/idle", cpu=True):
                        while self._idle():
                            self._cv.wait()
                if self._closed:
                    drained = self._pending
                    self._pending = []
                else:
                    drained = None
            if drained is not None:
                for s in drained:
                    self.engine.release_reservation(s.pages)
                    self._ledger_release(s)
                    s.stream.finish("overloaded: scheduler stopped")
                try:
                    # What the device still holds (a step: a prefill run is read
                    # in its own turn) is read and delivered first: a request
                    # whose last token was in flight ends whole.
                    self._collect_step()
                except Exception:
                    log.exception("reading the step in flight at stop failed")
                self._fail_everyone("overloaded: scheduler stopped")
                return
            try:
                self._turn()
            except Exception:
                # A crashed decode loop must fail every resident request
                # visibly, not hang their streams forever.
                log.exception("decode loop error; failing resident slots")
                self._fail_everyone("RpcError: generation engine failed")

    def _fail_everyone(self, error: str) -> None:
        """End every resident's stream, and the stream of whoever left its
        slot with tokens still in flight; nothing stays in flight (a run
        nobody has read is waited for, best effort, and thrown away) and the
        engine keeps none of the arrays its calls replaced."""
        flying = [(run, collect) for run, collect in (
            (self._prefill_in_flight, self.engine.collect_admit),
            (self._step_in_flight, self.engine.collect_step)) if run is not None]
        self._prefill_in_flight = self._step_in_flight = None
        for run, collect in flying:
            try:
                collect(run.handle)
            except Exception:  # dmlc-lint: disable=E1 -- best-effort cleanup mid-failure; the stream error below is the observable verdict
                pass
        for s in list(self._resident):
            try:
                self.engine.release(s.slot)
            except Exception:  # dmlc-lint: disable=E1 -- best-effort cleanup mid-failure; the stream error below is the observable verdict
                pass
            self._ledger_release(s)
            s.stream.finish(error)
        self._resident = []
        for run, _ in flying:
            for _, req in run.seats:
                req.stream.finish(error)
        self.engine.release_replaced()

    def _turn(self) -> None:
        """One loop turn: dispatch this turn's runs, THEN read, so that the
        device has a step queued while the host reads, delivers and prepares
        the next turn:

            admit (dispatch prefill run P_t, seat its requests) -> retire by
            what the host knows -> dispatch step S_t -> read S_(t-1), deliver
            its tokens -> read P_t, deliver its first tokens.

        A step is read a turn late; a prefill run in its own turn, last, with
        S_t queued behind it: a first token does not wait a turn, and when
        the read returns the host has S_t's device time to prepare the next
        turn.

        ORDERING INVARIANT the loop relies on: the pools, the recurrent
        state and the token register are touched by ONE in-order stream of
        two donating programs, each taking what the one before returned. So
        a slot may be released, its pages recycled and the slot re-admitted
        while a step that still computes its old row is in flight: the
        prefill dispatched later runs later and overwrites what that row
        wrote. What must not cross is a RESULT: a run's row goes only to
        the request that sat in the slot when the run was dispatched
        (``_Run.seats``) and has not left since (``_hand_out``)."""
        self._admit_pending()
        with tracer.span("gen/retire", cpu=True):
            self._retire()
        if self._resident:
            due = self._step_in_flight
            trace_ctx = min(self._resident, key=lambda r: r.submitted_t).trace_ctx
            t0 = self.clock()
            with tracectx.bind(trace_ctx):
                with tracer.span("gen/step", cpu=True) as span:
                    run = self._dispatch_step(trace_ctx, ahead=due is not None)
                    # A step that fails here fails every resident (the caller's
                    # crash path), as a step that failed did.
                    values = self._read_step(due, span)
                    self._step_in_flight = run
            self._deliver_step(due, values, self.clock() - t0)
        else:
            self._collect_step()  # a busy period's last turn dispatches nothing
        self._collect_prefill()

    def _admit_pending(self) -> None:
        """Move the waiting requests that find a free engine slot into the
        batch (between steps): ONE run of the prefill program for all of
        them, first in first out, dispatched and not waited for: they are
        seated at once, their first tokens in the device's register and in
        flight (the step dispatched next decodes them), and the run is read
        at the end of the turn (``_collect_prefill``). The dispatch is two
        children of ``gen/admit`` (``gen/prefill_operands``,
        ``gen/prefill_call``): a run's ``gen/prefill`` span is its read.

        A request stays IN ``_pending`` until it lands in ``_resident``:
        submit-time admission counts both lists, and a request invisible to
        that count during the prefill would let another slip past a full
        slot table."""
        with tracer.span("gen/admit", cpu=True):
            batch = self._admissible()
            if not batch:
                return
            for req in batch:
                if req.wait_t0 is not None:
                    req.first_t0 = tracer.now()
                    with tracectx.bind(req.trace_ctx):
                        tracer.record("gen/wait", max(0.0, req.first_t0 - req.wait_t0))
            handle: Any = None
            results: list[int | Exception]
            try:
                handle = self.engine.dispatch_admit([
                    Admission(req.slot, req.prompt, req.temperature, req.pages, req.seed)
                    for req in batch])
                results = handle.results
            except Exception as e:
                # The run failed: that fails the streams of ITS batch, never
                # the resident one.
                log.exception("prefill run of %d requests failed", len(batch))
                results = [e] * len(batch)
            seats = []
            for req, result in zip(batch, results):
                if isinstance(result, Exception):
                    # A bad request fails ITS stream. Pages go back wherever
                    # they are: bound to the slot (the engine got past its
                    # check) or still the submit-time reservation.
                    log.error("prefill failed for %s: %s", req.stream.request_id, result)
                    self._unpend(req)
                    if (self.engine.cache_mode == "paged"
                            and not self.engine.cache.slot_pages(req.slot)):
                        self.engine.release_reservation(req.pages)
                    self.engine.release(req.slot)
                    self._ledger_release(req)
                    req.stream.finish(f"{type(result).__name__}: {result}")
                    continue
                self._seat(req)
                req.in_flight = 1
                seats.append((req.slot, req))
            if not seats:
                return
            attrs = {"prompts": len(seats)}
            # Read under the oldest admitted request's trace, as gen/step is
            # under the oldest resident's.
            self._prefill_in_flight = _Run(handle, seats, attrs, batch[0].trace_ctx)

    def _admissible(self) -> list[_Slot]:
        """The waiting requests, oldest first, that each get a free slot
        (assigned here); empty when nobody waits or no slot is free. A
        request that expired or was cancelled while waiting (the router
        migrated it away, or the client gave up) is finished here: a
        prefill now would be dead work."""
        free = self.engine.free_slots()
        with self._cv:
            waiting = list(self._pending)
        batch: list[_Slot] = []
        for req in waiting:
            if len(batch) == len(free):
                break
            if req.deadline is not None and req.deadline.expired():
                self._drop_waiting(req, "deadline: expired before a slot freed")
            elif req.stream.cancelled:
                self._drop_waiting(req, "cancelled: before a slot freed")
            else:
                req.slot = free[len(batch)]
                batch.append(req)
        return batch

    def _drop_waiting(self, req: _Slot, error: str) -> None:
        self._unpend(req)
        self.engine.release_reservation(req.pages)
        self._ledger_release(req)
        req.stream.finish(error)

    def _seat(self, req: _Slot) -> None:
        """A prefilled request becomes a resident."""
        req.pages = []  # ownership moved to the cache's slot binding
        with self._cv:
            self._pending.remove(req)
            self._resident.append(req)
        if self.flight is not None:
            # ``step`` stamps WHEN in the batch's life the slot joined:
            # admits at step > 0 are the continuous-batching evidence
            # (a request entered a batch already mid-decode).
            self.flight.note(
                "slot_admit", slot=req.slot, prompt=len(req.prompt),
                step=self.engine.steps, request=req.stream.request_id,
                pages=len(self.engine.cache.slot_pages(req.slot))
                if self.engine.cache_mode == "paged" else 0,
            )

    def _unpend(self, req: _Slot) -> None:
        with self._cv:
            if req in self._pending:
                self._pending.remove(req)

    def _ledger_release(self, req: _Slot) -> None:
        with self._cv:
            self.ledger.release(req.tenant)

    def _eviction_victim(self, req: _Slot) -> _Slot:
        """Eviction ordering (docs/OVERLOAD.md §Priority classes): when
        ``req`` needs a page the pool cannot grant, the slot that dies is
        the newest LOW-PRIORITY-AND-OVER-QUOTA resident — the workload
        holding more than its share pays for the pressure it created.
        With no such victim (everyone within quota, or ``req`` itself is
        the over-quota low-priority one) the requester is evicted, as
        before: within-quota work of another tenant is NEVER the victim."""
        with self._cv:
            spec = self.ledger.spec(req.tenant)
            if spec.high_priority and not self.ledger.over_quota(req.tenant):
                for other in reversed(self._resident):
                    if other is req:
                        continue
                    if self.ledger.over_quota(other.tenant) and \
                            not self.ledger.spec(other.tenant).high_priority:
                        return other
            return req

    def _evict(self, victim: _Slot, why: Exception) -> None:
        self.evictions += 1
        if self.metrics is not None:
            self.metrics.inc("gen_evictions")
        if self.flight is not None:
            self.flight.note("slot_evict", slot=victim.slot,
                             emitted=victim.emitted, tenant=victim.tenant)
        self._exit(victim, "evicted",
                   error=f"overloaded: evicted mid-decode ({why})",
                   counted=False)

    def _collect_prefill(self) -> None:
        """Read the prefill run in flight, if any (``gen/prefill``, which
        carries the run's attributes) and deliver its first tokens. A run
        that fails here fails the streams of ITS batch, never the residents."""
        run, self._prefill_in_flight = self._prefill_in_flight, None
        if run is None:
            return
        with tracectx.bind(run.trace_ctx):
            with tracer.span("gen/prefill", cpu=True) as span:
                try:
                    firsts = self.engine.collect_admit(run.handle)
                except Exception as e:
                    log.exception("prefill run of %d requests failed", len(run.seats))
                    for _, req in run.seats:
                        req.in_flight -= 1
                        if not req.stream.done:
                            self._exit(req, "prefill_failed", counted=False,
                                       error=f"{type(e).__name__}: {e}")
                    return
                span.set(**run.attrs)
                self._count_work(span, self.engine.prefill_attrs)
        values = {slot: first for slot, first in zip(run.handle.results, firsts)
                  if not isinstance(first, Exception)}
        with tracer.span("gen/deliver", cpu=True):
            self._hand_out(run, values)

    def _collect_step(self) -> None:
        """Read and deliver the step in flight and dispatch none: a turn
        with nobody resident, ``stop()``."""
        due = self._step_in_flight
        if due is None:
            return
        t0 = self.clock()
        with tracectx.bind(due.trace_ctx):
            with tracer.span("gen/step", cpu=True) as span:
                values = self._read_step(due, span)
                self._step_in_flight = None
        self._deliver_step(due, values, self.clock() - t0)

    def _dispatch_step(self, trace_ctx: Any, ahead: bool) -> _Run:
        """One fixed-shape step for whoever is resident, not waited for."""
        attrs = {"slots": len(self._resident), "ahead": int(ahead)}
        if tracer.enabled:
            # Pages handed out (bound to slots + reserved by waiting
            # requests) against the tokens that sit in them, measured where
            # the pages are handed out. A resident's cache holds its prompt
            # and all but the newest of the tokens it was sent or is due.
            attrs["pages_bound"] = self._page_total - self.engine.pages_free
            attrs["tokens_resident"] = sum(
                len(r.prompt) + r.emitted + r.in_flight - 1 for r in self._resident)
        handle = self.engine.dispatch_step()
        for req in self._resident:
            req.in_flight += 1
        if ahead:
            self.steps_ahead += 1
            if self.metrics is not None:
                self.metrics.inc("gen_steps_ahead")
        return _Run(handle, [(r.slot, r) for r in self._resident], attrs, trace_ctx)

    def _read_step(self, due: _Run | None, span: Any) -> Any:
        """The due step's one blocking read: its tokens by slot (None when
        nothing is due: a busy period's first turn). The span that reads a
        step is the span that describes it."""
        if due is None:
            return None
        values = self.engine.collect_step(due.handle)
        span.set(**due.attrs)
        self._count_work(span, self.engine.step_attrs)
        return values

    def _deliver_step(self, due: _Run | None, values: Any, elapsed: float) -> None:
        if due is None:
            return
        with tracer.span("gen/deliver", cpu=True):
            self.step_stats.record(max(0.0, elapsed))
            if self.profile is not None:
                self.profile(elapsed)
            self._hand_out(due, values)

    def _hand_out(self, run: _Run, values: Any) -> None:
        """A run's rows to the requests that sat in its slots when it was
        dispatched and are still here: a request that left meanwhile (eos
        seen a step late, cancel, deadline, eviction) gets nothing, whoever
        holds its slot now."""
        for slot, req in run.seats:
            req.in_flight -= 1
            if req.stream.done:
                self.tokens_discarded += 1
                if self.metrics is not None:
                    self.metrics.inc("gen_tokens_discarded")
                continue
            tok = int(values[slot])
            self._deliver(req, tok)
            if req.eos_id is not None and tok == req.eos_id:
                self._exit(req, "eos")
            elif req.emitted >= req.max_new_tokens:
                self._exit(req, "max_tokens")

    def _count_work(self, span: Any, attrs: dict) -> None:
        """What the engine says its last program run did (expert pairs,
        recurrent state; nothing for a model that has neither): onto the
        caller's span, and the pairs into the node's counters."""
        if not attrs:
            return
        span.set(**attrs)
        if self.metrics is not None and "expert_pairs" in attrs:
            model = self.engine.model_name
            self.metrics.inc(f"gen_expert_pairs_{model}", attrs["expert_pairs"])
            self.metrics.inc(f"gen_expert_pairs_absent_{model}", attrs["expert_pairs_absent"])

    def _retire(self) -> None:
        for req in list(self._resident):
            if req not in self._resident:
                continue  # already evicted as another slot's page victim
            if req.stream.cancelled:
                self._exit(req, "cancel",
                           error="cancelled: stream cancelled",
                           counted=False)
                continue
            if req.deadline is not None and req.deadline.expired():
                self._exit(req, "deadline",
                           error="deadline: generation exceeded its budget")
                continue
            if req.emitted + req.in_flight >= req.max_new_tokens:
                # Every token it is due is in flight: the slot is free for
                # the next request now, the stream ends when the last lands.
                self._vacate(req, "max_tokens", req.max_new_tokens)
                continue
            try:
                self.engine.ensure_capacity(req.slot)
            except PagePoolExhausted as e:
                victim = self._eviction_victim(req)
                self._evict(victim, e)
                if victim is not req:
                    # The freed pages may now cover the requester; if the
                    # pool STILL cannot grant, the requester exits too.
                    try:
                        self.engine.ensure_capacity(req.slot)
                    except PagePoolExhausted as e2:
                        self._evict(req, e2)

    def _deliver(self, req: _Slot, token: int) -> None:
        req.emitted += 1
        req.stream.step_gen = self.engine.steps
        req.stream.push([token])
        if req.first_t0 is not None:
            # The request's first token, pushed: the leg that gen/wait started.
            with tracectx.bind(req.trace_ctx):
                tracer.record("gen/first", max(0.0, tracer.now() - req.first_t0))
            req.first_t0 = None
        self.tokens_streamed += 1
        if self.metrics is not None:
            self.metrics.inc("gen_tokens")
        now = self.clock()
        if self._t_first_token is None:
            self._t_first_token = now
        self._t_last_token = now

    def _vacate(self, req: _Slot, reason: str, emitted: int) -> None:
        """The request's slot and pages go back to the engine; its stream
        stays open for the tokens still in flight."""
        freed = self.engine.release(req.slot)
        with self._cv:  # submit reads len(_resident) for admission
            self._resident.remove(req)
            self.ledger.release(req.tenant)
        if self.flight is not None:
            self.flight.note("slot_exit", slot=req.slot, reason=reason,
                             step=self.engine.steps, emitted=emitted,
                             pages_freed=len(freed))
        req.slot = -1

    def _exit(self, req: _Slot, reason: str, error: str | None = None,
              counted: bool = True) -> None:
        """The request is over: out of its slot if it still holds one, its
        stream finished. Rows of runs in flight find the stream done."""
        if req.slot >= 0:
            self._vacate(req, reason, req.emitted)
        if counted:
            self.completions += 1
        req.stream.finish(error)

    # ---- observability / lifecycle ---------------------------------------

    def tok_s(self) -> float:
        """Streamed-token rate over the window tokens actually flowed."""
        if self._t_first_token is None or self._t_last_token is None:
            return 0.0
        dt = self._t_last_token - self._t_first_token
        if dt <= 0:
            return 0.0
        return self.tokens_streamed / dt

    def summary(self) -> dict[str, Any]:
        with self._cv:
            tenants = self.ledger.summary()
        return {
            "requests": self.requests,
            "sheds": self.sheds,
            "evictions": self.evictions,
            "completions": self.completions,
            "tokens_streamed": self.tokens_streamed,
            "tok_s": round(self.tok_s(), 2),
            "slots_active": self.engine.slots_active,
            "pages_free": self.engine.pages_free,
            # Which decode attention serves: the fused Pallas kernel (compiled
            # on a TPU) or the XLA take + mask — on node.status so a caller
            # can tell, and chip_smoke.py can refuse, the fallback.
            "use_pallas": self.engine.use_pallas,
            "max_active": self.max_active,
            "page_budget": self.page_budget,
            **({"tenants": tenants} if tenants else {}),
            "steps": self.engine.steps,
            "steps_ahead": self.steps_ahead,
            "tokens_discarded": self.tokens_discarded,
            "step_ms_p50": round(self.step_stats.percentile(50) * 1e3, 3)
            if len(self.step_stats) else None,
            "step_ms_p99": round(self.step_stats.percentile(99) * 1e3, 3)
            if len(self.step_stats) else None,
        }

    def stop(self, timeout_s: float = 10.0) -> None:
        """Fail-fast shutdown: waiting and resident requests finish with a
        typed error (node stop must be bounded, not generation-length)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        self._thread.join(timeout=timeout_s)
