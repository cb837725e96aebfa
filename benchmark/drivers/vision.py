"""Driver for image classifiers: one ``job.start`` over a seed-made JPEG
corpus, shards through ``job.predict`` on the member. A shard's completion
instant is taken where its ``job.predict`` returns; ``job.report`` on the
leader is polled beside it, and its count must keep up: the leader's
``finished`` is a contiguous-prefix cursor that stalls behind the oldest
shard in flight and then jumps, so its own instants would be lumps of up to
eight shards (PERF.md, PR 24 finding 6)."""

from __future__ import annotations

import gc
import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

from benchlib import manifest, stats, system, traffic as traffic_lib, weights

FAILURE_COUNTERS = ("shed", "shed_observed", "deadline_exceeded", "breaker_open",
                    "gray_demotions")


def make_corpus(root: Path, mix: dict, seed: int):
    """``distinct_images`` JPEGs, one class directory each (the layout the
    program's jobs read), and a synset list of ``job_images`` queries over
    them. Smooth random fields, so that JPEG coding behaves as on photographs."""
    import numpy as np
    from PIL import Image

    px, n = int(mix["image_px"]), int(mix["distinct_images"])
    data_dir = root / "train"
    low = max(8, px // 8)

    def one(i: int) -> None:
        rng = np.random.default_rng([int(seed) & 0x7FFFFFFF, int(seed) >> 31, i])
        d = data_dir / f"n{i:08d}"
        d.mkdir(parents=True, exist_ok=True)
        base = rng.integers(0, 256, (low, low, 3), np.uint8)
        im = Image.fromarray(base).resize((px, px), Image.BILINEAR)
        im.save(d / "img0.jpg", quality=int(mix["jpeg_quality"]),
                subsampling=str(mix["jpeg_subsampling"]))

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(one, range(n)))
    synsets = root / "synset_words.txt"
    synsets.write_text("".join(f"n{i:08d} image {i}\n" for i in traffic_lib.image_order(mix, seed)))
    return data_dir, synsets


class Observed:
    """The member's backend, with the calls into it counted and their
    completion instants kept. The benchmark holds no lock of its own across
    the call: whatever the program's ``EngineBackend`` lets run side by side
    runs side by side here, and what it serialises waits at ITS lock. Once
    the benchmark has closed, new shards are turned away here and the ones
    already queued inside the program are turned away at the engine's entry
    (``tap_engine``), so nobody decodes seven more shards for no one."""

    def __init__(self, inner) -> None:
        self._inner = inner
        self._idle = threading.Condition()   # its lock is held around the bookkeeping only
        self.closed = False
        self.in_flight = 0
        self.errors = 0
        self.completions: list = []      # (instant, images) of each shard answered

    def __call__(self, synsets):
        if self.closed:
            raise RuntimeError("the benchmark's window has closed")
        with self._idle:
            self.in_flight += 1
        try:
            answer = self._inner(synsets)
        except BaseException:
            with self._idle:
                self.errors += 1
                self.in_flight -= 1
                self._idle.notify_all()
            raise
        done = time.perf_counter()
        with self._idle:
            self.completions.append((done, len(synsets)))
            self.in_flight -= 1
            self._idle.notify_all()
        return answer

    def close(self, timeout: float = 60.0) -> None:
        """No new shard starts; wait until the calls in flight have ended."""
        self.closed = True
        with self._idle:
            self._idle.wait_for(lambda: self.in_flight == 0, timeout=timeout)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def tap_engine(engine, sink: list, observed) -> None:
    """``job.predict`` answers with indices only, so what the compiled
    programs produced (index AND probability) is copied as it leaves the
    engine's two entry points. The result passes through untouched. A shard
    that reaches the engine after the benchmark has closed is turned away."""
    for name in ("run_paths", "run_paths_stream"):
        inner = getattr(engine, name)

        def tapped(paths, *a, _inner=inner, **kw):
            if observed.closed:
                raise RuntimeError("the benchmark's window has closed")
            result = _inner(paths, *a, **kw)
            sink.append((time.perf_counter(), [str(p) for p in paths],
                         result.top1_index, result.top1_prob))
            return result

        setattr(engine, name, tapped)


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    model = cfg["model"]
    from dmlc_tpu.models.registry import get_model
    from dmlc_tpu.scheduler.worker import EngineBackend

    dtype = system.dtype_of(cfg["dtype"])
    flat = weights.make(system.abstract_shapes(get_model(model)), cfg["init"], ctx.seed, dtype)
    tmp = system.workdir(ctx.cell["name"])
    data_dir, synsets = make_corpus(tmp / "corpus", mix, ctx.seed)
    system.say(f"corpus and weights at {time.perf_counter() - ctx.t_start:.1f} s")
    px = int(mix["image_px"])
    inner = EngineBackend(
        model, data_dir, batch_size=int(cfg["cluster"]["batch_size"]),
        variables=weights.unflatten(flat), dtype=dtype,
        device_resize_from=px if px != int(cfg["input_size"]) else None)
    observed = Observed(inner)
    nodes = system.start_cluster(
        tmp, {**cfg["cluster"], "data_dir": str(data_dir), "synset_path": str(synsets)},
        backends={model: observed})
    node = nodes[0]
    served: list = []
    tap_engine(inner._engine, served, observed)
    system.say(f"cluster up at {time.perf_counter() - ctx.t_start:.1f} s")

    tap = system.SpanTap() if ctx.trace else None
    profile = system.Profile(tmp / "profile") if ctx.trace else None
    stop = threading.Event()
    job = {"running": True, "finished": 0}

    def watch() -> None:
        while not stop.is_set():
            try:
                report = node.jobs_report()[model]
                job["finished"], job["running"] = int(report["finished"]), bool(report["running"])
            except Exception as e:  # a lost report is retried at the next poll
                system.say(f"job.report: {type(e).__name__}: {e}")
            stop.wait(float(mix["poll_interval_s"]))

    t_job = time.perf_counter()
    node.predict()
    watcher = threading.Thread(target=watch, name="bench-watch", daemon=True)
    watcher.start()

    def snapshot():
        return list(observed.completions)

    t_open = system.wait_for(lambda: stats.open_instant(snapshot(), int(mix["warm_completions"])),
                      600.0, "the ramp's shards")
    compiles_at_open = ctx.compiles.count
    counters_at_open = system.counters(node)
    errors_at_open = observed.errors
    reported_at_open = job["finished"]
    setup_s = t_open - ctx.t_start
    system.say(f"window open: setup_s={setup_s:.2f}; ramp completions at "
               f"{[round(t - t_job, 1) for t, _ in snapshot()]} s after job.start")
    profiler = None
    if ctx.trace:
        profiler = profile.start_after(float(mix["profile_delay_s"]),
                                       float(mix["profile_seconds"]), stop)

    def closed():
        done = stats.close_instant(snapshot(), t_open, ctx.seconds)
        if done is None and not job["running"]:
            return snapshot()[-1][0]  # the job ran out: close at its last shard
        return done

    system.wait_for(closed, ctx.seconds + 300.0, "the window's closing shard")
    compiles_in_window = ctx.compiles.count - compiles_at_open
    counters_at_close = system.counters(node)
    errors_in_window = observed.errors - errors_at_open
    reported_in_window = int(node.jobs_report()[model]["finished"]) - reported_at_open
    last_error = node.jobs_report()[model]["last_error"]
    stop.set()
    observed.close()
    if profiler is not None:
        profiler.join(timeout=240)
    watcher.join(timeout=30)
    memory_peak = system.memory_peak_bytes()
    spans = tap.spans() if tap else []
    if tap:
        tap.close()
    window = stats.window(snapshot(), t_open, ctx.seconds)
    shard = int(cfg["cluster"]["dispatch_shard_size"])

    system.stop_cluster(nodes)
    inner._engine = None
    del nodes, node, inner, observed
    gc.collect()

    moved = system.counter_delta(counters_at_open, counters_at_close, FAILURE_COUNTERS)
    failed = errors_in_window + sum(moved.values()) + (1 if last_error else 0)
    if moved or last_error:
        system.say(f"failures in the window: {moved} last_error={last_error!r}")
    end_to_end = {"images_per_s": window.rate, "setup_s": setup_s}
    system.say(f"window {window.seconds:.2f} s, {window.work:.0f} images in {window.n} "
               f"completions; compiles in window: {compiles_in_window}")

    # `correct`: a seed-drawn sample of files, every answer served for them
    # inside the window, against the reference.
    in_window = [s for s in served if window.t_open < s[0] <= window.t_close]
    answers: dict = {}
    for _, paths, idx, prob in in_window:
        for p, i, q in zip(paths, idx, prob):
            answers.setdefault(p, []).append((int(i), float(q)))
    rng = random.Random(int(ctx.seed) ^ 0x5EED)
    files = sorted(answers)
    sample = rng.sample(files, min(len(files), int(mix["check_images"])))
    reference = manifest.plugin("reference", cfg["reference"])
    t_ref = time.perf_counter()
    checks = reference.check(cfg, flat, sample, answers, ctx.limits)
    system.say(f"reference over {len(sample)} files: {time.perf_counter() - t_ref:.1f} s")
    control = {}
    if getattr(ctx, "control", None):
        control = reference.check(cfg, flat, sample, answers, ctx.limits, control=ctx.control)
    # the leader's cursor may trail by the shards in flight, never by more
    in_flight = int(cfg["cluster"].get("dispatch_workers", 8)) + 1
    checks["report_lag_shards"] = {
        "value": max(0.0, (window.work - reported_in_window) / shard), "limit": in_flight}
    checks["compiles_in_window"] = {"value": compiles_in_window, "limit": 0}
    checks["window_full"] = {"value": 0 if window.full else 1, "limit": 0}
    return {
        "end_to_end": end_to_end, "attempted": int(round(window.work / shard)) + failed,
        "failed": failed, "checks": checks, "memory_peak_bytes": memory_peak,
        "setup_s": setup_s, "workdir": tmp, "control_checks": control,
        "readings": {"window": window, "spans": spans, "profile": profile,
                     "served": in_window, "config": cfg, "traffic": mix,
                     "shard_images": shard},
    }
