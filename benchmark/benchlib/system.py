"""The one module that touches the program under test: it starts a one-node
in-process ``localcluster`` on this machine's chips, hands it seed-made
weights, and taps what the timed path produces. Everything it takes from
the program is the system itself, its spans, its counters and its kernels'
names."""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from pathlib import Path

from benchlib import manifest, weights


def say(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def place_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless the
    environment has placed it. Must run before jax is imported."""
    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 str(manifest.REPO / ".jax_cache"))
    return path


def require_tpu(chips: int):
    """The devices, or exit non-zero with no result line."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: this cell needs {chips} TPU chip(s); jax.devices() reports "
              f"{len(devices)} x {devices[0].platform!r} ({devices[0].device_kind!r}). "
              "The benchmark measures nothing anywhere else.", file=sys.stderr)
        raise SystemExit(1)
    return devices


def import_program():
    """Put the checkout on the path; without the program there is nothing to measure."""
    sys.path.insert(0, str(manifest.REPO))
    try:
        import dmlc_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program under test is not in this checkout ({e})",
              file=sys.stderr)
        raise SystemExit(1)


class CompileCounter:
    """Backend compiles, from ``jax.monitoring``'s duration events. The
    window must see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def abstract_shapes(spec) -> dict:
    """{path: shape} of the program's variables tree for a registry spec,
    by ``jax.eval_shape`` of its init: shapes only, nothing is allocated."""
    import jax
    import jax.numpy as jnp

    def init():
        return spec.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)[1]

    tree = jax.eval_shape(init)
    return {p: tuple(leaf.shape) for p, leaf in weights.leaf_paths(_plain(tree)).items()}


def _plain(tree):
    return {k: _plain(v) if hasattr(v, "items") else v for k, v in tree.items()}


def dtype_of(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def memory_peak_bytes() -> int:
    import jax

    # What the runtime holds is two disjoint pools: buffers (``bytes_in_use``)
    # and what it reserves for compiled programs' temporaries
    # (``bytes_reserved``); the chip's peak is the sum of their peaks. A backend
    # that keeps no statistics (the CPU the tests run on) reads 0.
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        say(f"memory_stats {d.id}: {stats}")
        peaks.append(int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def workdir(cell_name: str) -> Path:
    """Scratch for one run, under the TMPDIR the driver gives this side."""
    import tempfile

    return Path(tempfile.mkdtemp(prefix=f"bench-{cell_name}-"))


# ---------------------------------------------------------------------------
# language models: registry entry, engine defaults, cluster
# ---------------------------------------------------------------------------


def register_lm(config: dict):
    """Register the configuration's sizes as a ``kind="lm"`` registry entry
    over ``SPTransformerLM`` (LayerNorm, GELU, learned positions)."""
    from dmlc_tpu.models import registry
    from dmlc_tpu.parallel.sharding import TRANSFORMER_PARTITION_RULES
    from dmlc_tpu.parallel.sp_transformer import SPTransformerLM

    def build(dtype=None):
        import jax.numpy as jnp

        return SPTransformerLM(
            vocab=config["vocab_size"], num_layers=config["n_layer"],
            num_heads=config["n_head"], hidden=config["n_embd"],
            mlp_dim=config["n_inner"], max_len=config["n_positions"],
            schedule="dense", dtype=dtype if dtype is not None else jnp.float32)

    spec = registry.ModelSpec(
        config["model"], build, config["n_positions"], config["vocab_size"],
        classifier=False, kind="lm",
        partition_rules=TRANSFORMER_PARTITION_RULES, num_heads=config["n_head"])
    registry.register(spec)
    return spec


@contextlib.contextmanager
def engine_defaults(dtype, variables):
    """``GenerationBackend`` gives its engine neither a dtype nor weights
    (PERF.md, Open questions). While the cluster starts, the engine class the
    backend looks up is a subclass whose ONLY difference is these two
    defaults; what the program itself passes wins. The program's class is
    back in place when the block ends."""
    import dmlc_tpu.generate.engine as engine_module

    base = engine_module.GenerationEngine

    class SeededEngine(base):
        def __init__(self, model_name, **kw):
            kw.setdefault("dtype", dtype)
            kw.setdefault("variables", variables)
            super().__init__(model_name, **kw)

    engine_module.GenerationEngine = SeededEngine
    try:
        yield
    finally:
        engine_module.GenerationEngine = base


def free_port_block() -> int:
    """A base port whose gossip (UDP base), leader (TCP base+1) and member
    (TCP base+2) ports bind now, drawn BELOW the kernel's ephemeral range:
    every RPC of the program is a new connection, so after a run thousands of
    ephemeral ports linger, and ``localcluster``'s own draw (21000-52000) then
    collides with them (PR 24: a run died of EADDRINUSE after three draws)."""
    import random
    import socket

    try:
        with open("/proc/sys/net/ipv4/ip_local_port_range") as f:
            ephemeral_lo = int(f.read().split()[0])
    except (OSError, ValueError):
        ephemeral_lo = 32768
    draw = random.SystemRandom()
    for _ in range(500):
        base = draw.randrange(12000, max(12100, ephemeral_lo - 100), 10)
        held = []
        try:
            for kind, port in ((socket.SOCK_DGRAM, base), (socket.SOCK_STREAM, base + 1),
                               (socket.SOCK_STREAM, base + 2)):
                sock = socket.socket(socket.AF_INET, kind)
                held.append(sock)
                sock.bind(("127.0.0.1", port))
            return base
        except OSError:
            continue
        finally:
            for sock in held:
                sock.close()
    raise SystemExit("benchmark: no free port block on this machine")


def start_cluster(tmp: Path, overrides: dict, backends=None):
    """One localcluster node at the reference's 1 s / 3 s intervals
    (``scale=5``: compressed timers falsely FAIL a member whose compile holds
    the GIL), as chip_smoke.py starts it, on ports this module has probed."""
    from dmlc_tpu.cluster import localcluster

    base = free_port_block()
    ports = {"gossip_port": base, "leader_port": base + 1, "member_port": base + 2,
             "leader_candidates": [f"127.0.0.1:{base + 1}"]}
    return localcluster.start_local_cluster(
        tmp, n_nodes=1, n_leader_candidates=1,
        backends=backends if backends is not None else localcluster.CONFIGURED,
        scale=5.0, **{**ports, **overrides})


def stop_cluster(nodes) -> None:
    from dmlc_tpu import native
    from dmlc_tpu.cluster.localcluster import stop_local_cluster

    stop_local_cluster(nodes)
    native.pool_shutdown()


def free_pools(engine) -> None:
    """Give the KV pools back to the device now: the reference runs next, and
    a process that runs several seeds must not wait for the collector."""
    for name in ("_k_state", "_v_state"):
        pool = getattr(engine, name, None)
        if pool is not None and hasattr(pool, "delete") and not pool.is_deleted():
            pool.delete()


def wait_for(predicate, seconds: float, what: str, poll: float = 0.005):
    """The first value ``predicate`` returns that is not None."""
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        value = predicate()
        if value is not None:
            return value
        time.sleep(poll)
    raise SystemExit(f"benchmark: timed out waiting for {what}")


def run_context(m, cell, config, mix, devices, *, seed, seconds, trace, t_start, compiles,
                control=None):
    """What a driver's ``run`` is handed."""
    from types import SimpleNamespace

    from benchlib import peaks

    return SimpleNamespace(
        seed=seed, seconds=seconds, trace=trace, manifest=m, cell=cell, config=config,
        traffic=mix, devices=devices, t_start=t_start, compiles=compiles,
        limits=manifest.limits_of(config, cell["traffic"]),
        peaks=peaks.peaks(devices[0].device_kind), control=control)


def counters(node) -> dict:
    return dict(node.metrics.snapshot())


def counter_delta(before: dict, after: dict, names) -> dict:
    return {n: after.get(n, 0) - before.get(n, 0) for n in names
            if after.get(n, 0) - before.get(n, 0)}


# ---------------------------------------------------------------------------
# the program's spans, and the profiler, on one clock
# ---------------------------------------------------------------------------


class SpanTap:
    """The program's process-global tracer, switched on for a traced run."""

    def __init__(self) -> None:
        from dmlc_tpu.utils import tracing

        self.tracer = tracing.tracer
        self.tracer.max_events = 2_000_000
        self.was = self.tracer.enabled
        self.tracer.enabled = True
        self.offset = time.perf_counter() - self.tracer.now()

    def spans(self):
        from benchlib.spans import to_clock

        return to_clock(self.tracer.events_wire(), self.offset)

    def close(self) -> None:
        self.tracer.enabled = self.was


class Profile:
    """``jax.profiler`` over a few seconds of the steady state. Host tracing
    is off (a host that stages 925 MB batches writes millions of runtime
    events and the trace takes minutes to save); the clocks are aligned by
    one tiny program whose end the host times."""

    def __init__(self, directory: Path) -> None:
        import jax
        import jax.numpy as jnp

        def bench_sync(x):
            return x + 1

        self.directory = str(directory)
        self._sync = jax.jit(bench_sync)
        self._one = jnp.zeros((8, 128), jnp.float32)
        self._sync(self._one).block_until_ready()     # compiled now, in set-up
        self.sync_perf = self.t0 = self.t1 = None
        self.error: BaseException | None = None

    def run(self, seconds: float, stop: threading.Event) -> None:
        try:
            self._run(seconds, stop)
        except BaseException as e:  # re-raised by reduce() on the main thread
            self.error = e

    def _run(self, seconds: float, stop: threading.Event) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._sync(self._one).block_until_ready()
        self.sync_perf = time.perf_counter()
        self.t0 = self.sync_perf
        stop.wait(seconds)
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()
        say(f"profile of {self.t1 - self.t0:.2f} s written in {time.perf_counter() - self.t1:.1f} s")

    def start_after(self, delay: float, seconds: float, stop: threading.Event) -> threading.Thread:
        """Profile ``seconds`` of the window from ``delay`` after now, on a thread."""
        thread = threading.Thread(
            target=lambda: (stop.wait(delay), self.run(seconds, stop)),
            name="bench-profiler", daemon=True)
        thread.start()
        return thread

    def reduce(self):
        from benchlib import trace

        if self.error is not None:
            raise self.error

        return trace.load(trace.newest_xplane(self.directory), self.sync_perf,
                          self.t0, self.t1)
