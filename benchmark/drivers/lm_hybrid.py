"""Driver for hybrid language models (``model_type: nemotron_h``: Mamba-2,
grouped-query attention and LatentMoE layers in one stack) served through
the same path as ``drivers/lm``: token streams through ``job.generate`` /
``job.generate_poll`` on the leader's GenRouter, from a closed loop of
clients that each use the repo's ``generate_stream``.

What differs from ``drivers/lm``, and why it is a driver of its own: the
model is registered through the program's own family file
(``models/nemotron_h.register_nemotron_h``) from the PUBLISHED keys of the
configuration file plus the experts this chip holds, not as GPT-2 sizes over
``SPTransformerLM``; the weights are drawn leaf by leaf
(``benchlib/weights_leafwise``: one flat draw would hold 9.3 GB twice); and
the engine's recurrent state is freed with its pools before the reference
runs. The closed loop, the window, ``failed`` and ``correct`` are
``drivers/lm``'s, imported from it.
"""

from __future__ import annotations

import gc
import random
import threading
import time

from benchlib import manifest, stats, system, traffic as traffic_lib, weights, weights_leafwise

lm = manifest.plugin("drivers", "lm")


def register(cfg: dict):
    """The configuration as the program's family reads it: every published
    key the family names, the router's width from ``published``, the experts
    held from ``deployment``, the serving length from ``serving_positions``."""
    from dmlc_tpu.models.nemotron_h import NemotronHConfig, register_nemotron_h

    config = NemotronHConfig.from_published(
        cfg, n_routed_experts=int(cfg["published"]["n_routed_experts"]),
        experts_held=cfg["deployment"]["experts_held"],
        max_len=int(cfg["serving_positions"]))
    if config.held[1] != int(cfg["n_routed_experts"]):
        raise SystemExit("benchmark: n_routed_experts (held here) and deployment.experts_held disagree")
    return register_nemotron_h(cfg["model"], config)


def free_engine_state(engine) -> None:
    """Pools and recurrent state back to the device before the reference runs."""
    import jax

    system.free_pools(engine)
    for leaf in jax.tree_util.tree_leaves(getattr(engine, "_r_state", ())):
        if hasattr(leaf, "delete") and not leaf.is_deleted():
            leaf.delete()


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    model = cfg["model"]
    spec = register(cfg)
    dtype = system.dtype_of(cfg["dtype"])
    flat = weights_leafwise.make(system.abstract_shapes(spec), cfg["init"], ctx.seed, dtype)
    tmp = system.workdir(ctx.cell["name"])
    with system.engine_defaults(dtype, weights.unflatten(flat)):
        nodes = system.start_cluster(tmp, cfg["cluster"])
    node = nodes[0]
    engine = node._gen_backends[model]._scheduler.engine
    if engine.dtype != dtype or engine.cache.k_pages.dtype != dtype:
        raise SystemExit(f"benchmark: the engine serves {engine.dtype}, the configuration states {dtype}")
    system.say(f"cluster up at {time.perf_counter() - ctx.t_start:.1f} s; "
               f"use_pallas={engine.use_pallas} pages={engine.cache.k_pages.shape} "
               f"resident={engine.resident_bytes() / 1e9:.2f} GB")

    from dmlc_tpu.generate.worker import generate_stream

    rpc, leader = node.rpc, node.tracker.current

    def call(req):
        return generate_stream(
            rpc, leader, model, req["prompt"], max_new_tokens=req["max_new_tokens"],
            temperature=float(mix["temperature"]), poll_timeout=float(mix["poll_timeout_s"]),
            poll_interval_s=float(mix["poll_interval_s"]))

    per_client = traffic_lib.requests(mix, ctx.seed, cfg["vocab_size"])
    records: list = []
    lock, stop = threading.Lock(), threading.Event()
    tap = system.SpanTap() if ctx.trace else None
    profile = system.Profile(tmp / "profile") if ctx.trace else None
    clients = [lm.Client(i, reqs, call, records, lock, stop) for i, reqs in enumerate(per_client)]
    for c in clients:
        c.start()

    def snapshot():
        with lock:
            return list(records)

    warm = int(mix["warm_completions"])
    t_open = system.wait_for(lambda: stats.open_instant(lm.completions(snapshot()), warm),
                             600.0, "the ramp's completions")
    compiles_at_open = ctx.compiles.count
    counters_at_open = system.counters(node)
    setup_s = t_open - ctx.t_start
    system.say(f"window open: setup_s={setup_s:.2f}")

    profiler = None
    if ctx.trace:
        profiler = profile.start_after(float(mix["profile_delay_s"]),
                                       float(mix["profile_seconds"]), stop)
    system.wait_for(lambda: stats.close_instant(lm.completions(snapshot()), t_open, ctx.seconds),
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
    window = stats.window(lm.completions(records), t_open, ctx.seconds)

    # The program's state is freed before the reference runs.
    system.stop_cluster(nodes)
    free_engine_state(engine)
    del nodes, node, engine, clients
    gc.collect()

    ended = [r for r in records if window.t_open < r["end"] <= window.t_close and not r["abandoned"]]
    short = [r for r in ended if not r["error"] and len(r["tokens"]) != r["max_new_tokens"]]
    moved = system.counter_delta(counters_at_open, counters_at_close, lm.FAILURE_COUNTERS)
    failed = sum(1 for r in ended if r["error"]) + len(short) + sum(moved.values())
    for r in ended:
        if r["error"]:
            system.say(f"failed request: {r['error']}")
    if moved:
        system.say(f"failure counters moved in the window: {moved}")

    gaps = [(r["token_t"][i], r["token_t"][i] - r["token_t"][i - 1])
            for r in records for i in range(1, len(r["token_t"]))]
    ttft = [(r["token_t"][0], r["token_t"][0] - r["submit"]) for r in records if r["token_t"]]
    end_to_end = {"tokens_per_s": window.rate, "setup_s": setup_s}
    system.say(f"window {window.seconds:.2f} s, {window.n} requests, "
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
