"""Driver for ``kind="lm"`` configurations: token streams through
``job.generate`` / ``job.generate_poll`` on the leader's GenRouter, from a
closed loop of clients that each use the repo's ``generate_stream``."""

from __future__ import annotations

import gc
import random
import threading
import time

from benchlib import manifest, stats, system, traffic as traffic_lib, weights

#: Program counters whose movement inside the window means an operation was
#: shed, evicted, migrated or lost.
FAILURE_COUNTERS = ("shed", "gen_evictions", "gen_migrations", "gen_sessions_lost",
                    "deadline_exceeded", "breaker_open")


class Client(threading.Thread):
    """One closed-loop caller: its next request goes out when its stream ends."""

    def __init__(self, index, requests, call, records, lock, stop):
        super().__init__(name=f"bench-client-{index}", daemon=True)
        self.index, self.requests, self.call = index, requests, call
        self.records, self.lock, self.stop_event = records, lock, stop

    def run(self) -> None:
        k = 0
        while not self.stop_event.is_set():
            req = self.requests[k % len(self.requests)]
            k += 1
            rec = {"client": self.index, "prompt": req["prompt"],
                   "max_new_tokens": req["max_new_tokens"], "submit": time.perf_counter(),
                   "token_t": [], "tokens": [], "error": None, "abandoned": False}
            try:
                for tok in self.call(req):
                    rec["token_t"].append(time.perf_counter())
                    rec["tokens"].append(int(tok))
                    if self.stop_event.is_set() and len(rec["tokens"]) < req["max_new_tokens"]:
                        rec["abandoned"] = True
                        break
            except Exception as e:  # a failed request is data: it counts in `failed`
                rec["error"] = f"{type(e).__name__}: {e}"[:300]
            rec["end"] = time.perf_counter()
            with self.lock:
                self.records.append(rec)
            if rec["error"]:
                self.stop_event.wait(0.05)


def completions(records):
    """[(instant, tokens of work)] of the requests that ran to their end."""
    return [(r["end"], len(r["prompt"]) + len(r["tokens"])) for r in records
            if not r["abandoned"] and not r["error"]]


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    model = cfg["model"]
    spec = system.register_lm(cfg)
    dtype = system.dtype_of(cfg["dtype"])
    flat = weights.make(system.abstract_shapes(spec), cfg["init"], ctx.seed, dtype)
    tmp = system.workdir(ctx.cell["name"])
    with system.engine_defaults(dtype, weights.unflatten(flat)):
        nodes = system.start_cluster(tmp, cfg["cluster"])
    node = nodes[0]
    engine = node._gen_backends[model]._scheduler.engine
    if engine.dtype != dtype or engine.cache.k_pages.dtype != dtype:
        raise SystemExit(f"benchmark: the engine serves {engine.dtype}, the configuration states {dtype}")
    system.say(f"cluster up at {time.perf_counter() - ctx.t_start:.1f} s; "
               f"use_pallas={engine.use_pallas} pages={engine.cache.k_pages.shape}")

    from dmlc_tpu.generate.worker import generate_stream

    rpc, leader = node.rpc, node.tracker.current

    def call(req):
        return generate_stream(
            rpc, leader, model, req["prompt"], max_new_tokens=req["max_new_tokens"],
            temperature=0.0, poll_timeout=float(mix["poll_timeout_s"]),
            poll_interval_s=float(mix["poll_interval_s"]))

    per_client = traffic_lib.requests(mix, ctx.seed, cfg["vocab_size"])
    records: list = []
    lock, stop = threading.Lock(), threading.Event()
    tap = system.SpanTap() if ctx.trace else None
    profile = system.Profile(tmp / "profile") if ctx.trace else None
    clients = [Client(i, reqs, call, records, lock, stop) for i, reqs in enumerate(per_client)]
    for c in clients:
        c.start()

    def snapshot():
        with lock:
            return list(records)

    warm = int(mix["warm_completions"])
    t_open = system.wait_for(lambda: stats.open_instant(completions(snapshot()), warm),
                      600.0, "the ramp's completions")
    compiles_at_open = ctx.compiles.count
    counters_at_open = system.counters(node)
    setup_s = t_open - ctx.t_start
    system.say(f"window open: setup_s={setup_s:.2f}")

    profiler = None
    if ctx.trace:
        profiler = profile.start_after(float(mix["profile_delay_s"]),
                                       float(mix["profile_seconds"]), stop)
    system.wait_for(lambda: stats.close_instant(completions(snapshot()), t_open, ctx.seconds),
             ctx.seconds + 300.0, "the window's closing completion")
    compiles_in_window = ctx.compiles.count - compiles_at_open
    counters_at_close = system.counters(node)
    stop.set()
    if profiler is not None:
        profiler.join(timeout=240)
    for c in clients:
        c.join(timeout=60)
    memory_peak = system.memory_peak_bytes()
    spans = tap.spans() if tap else []
    if tap:
        tap.close()
    records = snapshot()
    window = stats.window(completions(records), t_open, ctx.seconds)

    # The program's state is freed before the reference runs.
    system.stop_cluster(nodes)
    system.free_pools(engine)
    del nodes, node, engine, clients
    gc.collect()

    ended = [r for r in records if window.t_open < r["end"] <= window.t_close and not r["abandoned"]]
    short = [r for r in ended if not r["error"] and len(r["tokens"]) != r["max_new_tokens"]]
    moved = system.counter_delta(counters_at_open, counters_at_close, FAILURE_COUNTERS)
    failed = sum(1 for r in ended if r["error"]) + len(short) + sum(moved.values())
    for r in ended:
        if r["error"]:
            system.say(f"failed request: {r['error']}")
    if moved:
        system.say(f"failure counters moved in the window: {moved}")

    gaps = [(r["token_t"][i], r["token_t"][i] - r["token_t"][i - 1])
            for r in records for i in range(1, len(r["token_t"]))]
    ttft = [(r["token_t"][0], r["token_t"][0] - r["submit"]) for r in records if r["token_t"]]
    gaps_in = stats.in_window(gaps, window)
    end_to_end = {
        "tokens_per_s": window.rate,
        "token_gap_ms_p95": 1e3 * stats.quantile(gaps_in, 0.95),
        "setup_s": setup_s,
    }
    system.say(f"window {window.seconds:.2f} s, {window.n} requests, {len(gaps_in)} gaps, "
               f"{window.work:.0f} tokens; compiles in window: {compiles_in_window}")

    # `correct`: the reference over a seed-drawn sample of finished requests.
    good = [r for r in ended if not r["error"] and r["tokens"]]
    rng = random.Random(int(ctx.seed) ^ 0x5EED)
    sample = rng.sample(good, min(len(good), int(mix["check_requests"])))
    longest = max(good, key=lambda r: len(r["prompt"]) + len(r["tokens"]), default=None)
    if longest is not None and longest not in sample:
        sample[-1:] = [longest]
    reference = manifest.plugin("reference", cfg["reference"])
    t_ref = time.perf_counter()
    checks = reference.check(cfg, flat, sample, ctx.limits, mix)
    system.say(f"reference over {len(sample)} requests, "
               f"{sum(len(r['tokens']) for r in sample)} served tokens: "
               f"{time.perf_counter() - t_ref:.1f} s")
    control = {}
    if getattr(ctx, "control", None):
        control = reference.check(cfg, flat, sample, ctx.limits, mix, control=ctx.control)
    checks["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    checks["window_full"] = {"value": 0 if window.full else 1, "limit": 0}
    return {
        "end_to_end": end_to_end, "attempted": len(ended), "failed": failed,
        "checks": checks, "memory_peak_bytes": memory_peak, "setup_s": setup_s,
        "workdir": tmp, "control_checks": control,
        "readings": {"records": records, "window": window, "spans": spans,
                     "profile": profile, "gaps": gaps, "ttft": ttft,
                     "config": cfg, "traffic": mix},
    }
