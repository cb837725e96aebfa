"""Batched, sharded inference engine — the TPU replacement for the
reference's per-image forward path.

Reference behavior being replaced (capability, not mechanism): a member
receives one synset id per RPC, decodes one JPEG, runs one 224x224 forward
under a model mutex on CPU, returns top-1 (src/services.rs:475-497). That
design caps at ~2 qps. Here the unit of work is a *shard*: a fixed-size uint8
image batch laid out over the mesh's ``dp`` axis, normalized on device and
driven through one jit-compiled XLA program — softmax + top-k included, so a
single fused program produces the answer and only tiny [B] arrays return to
the host.

Static shapes everywhere: partial shards are padded to ``batch_size`` (one
compile, ever) and the pad is masked out on the host side.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextvars
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from dmlc_tpu.cluster.devicemon import CensusedJit
from dmlc_tpu.models import get_model
from dmlc_tpu.ops import preprocess as pp
from dmlc_tpu.parallel import mesh as mesh_lib
from dmlc_tpu.utils.hotpath import hot_path
from dmlc_tpu.utils.metrics import LatencyStats
from dmlc_tpu.utils.tracing import tracer

# ---- persistent decode-stage pool -----------------------------------------
# Batch-granular decode tasks for run_paths_stream (each task itself fans
# out per image through ops.preprocess's cached pool / the native library's
# persistent pool). Module-level and lazily built ONCE — the old design
# created a ThreadPoolExecutor(max_workers=1) inside every run_paths_stream
# call, which both churned threads per shard and capped the decode stage at
# one batch in flight. Width is small on purpose: the per-image fan-out
# below it owns the cores; this pool only needs enough slots to keep
# ``prefetch`` batches decoding concurrently.
_STAGE_POOL: concurrent.futures.ThreadPoolExecutor | None = None
_STAGE_POOL_LOCK = threading.Lock()


def _stage_pool() -> concurrent.futures.ThreadPoolExecutor:
    global _STAGE_POOL
    with _STAGE_POOL_LOCK:
        if _STAGE_POOL is None:
            _STAGE_POOL = concurrent.futures.ThreadPoolExecutor(
                max_workers=max(2, min(4, os.cpu_count() or 2)),
                thread_name_prefix="ingest-decode",
            )
        return _STAGE_POOL


def _submit_in(ctx: contextvars.Context | None, pool: concurrent.futures.Executor,
               fn, *args) -> concurrent.futures.Future:
    """``pool.submit`` that runs ``fn`` in a copy of ``ctx``: a pool thread
    has an empty context of its own, so a span opened there (``host/decode``)
    would be the root of a fresh trace instead of a child of the span the
    shard's thread had open when it took ``ctx``. ``None`` (the tracer is
    off) submits bare."""
    if ctx is None:
        return pool.submit(fn, *args)
    return pool.submit(ctx.copy().run, fn, *args)  # a Context runs one thread at a time


#: Stage names exported by InferenceEngine.ingest_summary(), in pipeline
#: order. "pipeline" records whole run_paths_stream walls, which is the
#: denominator for per-stage occupancy.
INGEST_STAGES = ("decode", "stage", "dispatch", "sync", "pipeline")


@dataclass
class BatchResult:
    top1_index: np.ndarray      # [N] int32 class indices (classifiers)
    top1_prob: np.ndarray       # [N] float32
    embeddings: np.ndarray | None  # [N, D] for embedding models
    # Wall seconds behind this result: the device execution for run_batch /
    # run_paths; the WHOLE pipeline (decode || transfer || compute) for
    # run_paths_stream.
    device_seconds: float


class ShardDecodes:
    """A shard's batch decodes on the persistent stage pool, handed out in
    batch order with at most ``prefetch`` started and not yet taken
    (``InferenceEngine.start_decodes``; ``run_paths_stream`` takes them).

    Each decode runs in a copy of ``ctx``, the context of the span open
    where the handle was made, so ``host/decode`` keeps the shard's trace
    and lane on a pool thread (docs/OBSERVABILITY.md §1). A decode that
    raises keeps its exception in its future: it surfaces where the shard
    waits for that batch, in the shard's own reply."""

    def __init__(self, engine: "InferenceEngine", paths: Sequence[str], workers: int | None,
                 prefetch: int, decode_source) -> None:
        self.paths = paths
        self.starts = list(range(0, len(paths), engine.batch_size))
        self.prefetch = max(1, int(prefetch))
        self.futs: collections.deque = collections.deque()
        self.submitted = 0  # batches handed to the pool so far
        self.ctx = contextvars.copy_context() if tracer.enabled else None
        self._engine = engine
        self._workers = workers
        self._decode_source = decode_source

    def _decode(self, s: int):
        engine = self._engine
        chunk = self.paths[s : s + engine.batch_size]
        t0 = time.perf_counter()
        with tracer.span("host/decode", n=len(chunk)):
            if self._decode_source is not None:
                batch = self._decode_source(chunk, engine.input_size)
            else:
                batch = pp.load_batch(chunk, size=engine.input_size, workers=self._workers)
        if len(chunk) < engine.batch_size:
            pad = np.zeros((engine.batch_size - len(chunk), *batch.shape[1:]), batch.dtype)
            batch = np.concatenate([batch, pad])
        # Statistic only: the interval is the ``host/decode`` span above.
        with engine._ingest_lock:
            engine._ingest["decode"].record(time.perf_counter() - t0)
        return len(chunk), batch

    def submit(self, leaf: str = "ingest/decode_submit") -> None:
        """Start batches until ``prefetch`` are outstanding. A leaf of its
        own: a pool thread that starts decoding may take the interpreter
        from this one before ``submit()`` returns."""
        pool = _stage_pool()
        with tracer.span(leaf, cpu=True):
            while self.submitted < len(self.starts) and len(self.futs) < self.prefetch:
                self.futs.append(_submit_in(self.ctx, pool, self._decode,
                                            self.starts[self.submitted]))
                self.submitted += 1

    def ready(self) -> int:
        """Started batches whose decode has ended."""
        return sum(f.done() for f in self.futs)

    def cancel(self) -> None:
        """Drop the batches not yet taken (a shard that will not run)."""
        for f in self.futs:
            f.cancel()


class InferenceEngine:
    """One model, one mesh, one compiled program."""

    def __init__(
        self,
        model_name: str,
        mesh: Mesh | None = None,
        variables: Any | None = None,
        dtype=jnp.bfloat16,
        batch_size: int = 256,
        seed: int = 0,
        use_pallas: bool | None = None,
        device_resize_from: int | None = None,
        device_work=None,
    ):
        self.spec = get_model(model_name)
        # Device-plane telemetry hook (cluster/devicemon.py): called with
        # (model, items, seconds) per device execution so the node's
        # DeviceMonitor can track achieved FLOP/s vs roofline. None = off.
        self.device_work = device_work
        self.mesh = mesh if mesh is not None else mesh_lib.make_mesh()
        self.batch_size = int(batch_size)
        # Optional device-side resize (ops/device_resize.py): the host ships
        # raw [B, R, R, 3] uint8 (R = device_resize_from, e.g. the corpus's
        # native/DCT-scaled size) and the chip resizes to the model's input
        # via MXU matmuls fused into the first conv — cutting the ~35% of
        # host CPU that resize costs (measured, ops/device_resize.py).
        self.device_resize_from = device_resize_from
        self.model = self.spec.module(dtype=dtype)
        if variables is None:
            _, variables = self.spec.init_params(jax.random.PRNGKey(seed), dtype=dtype)
        self.variables = mesh_lib.shard_params(self.mesh, variables)
        self._stats = LatencyStats()
        # Pallas kernels for normalize/top-1 are available but OPT-IN: XLA
        # already fuses both into the adjacent conv/readout. The kernels
        # earn their keep on the standalone preprocessing path
        # (ops/pallas_kernels.py) where there is no adjacent op to fuse into.
        self.use_pallas = bool(use_pallas)

        mean_np, std_np = pp.stats_for_model(model_name)
        mean, std = jnp.asarray(mean_np), jnp.asarray(std_np)
        data_shd = mesh_lib.batch_sharding(self.mesh)
        classifier = self.spec.classifier

        resize_from = self.device_resize_from
        input_size = self.spec.input_size

        def forward(variables, u8):
            if resize_from is not None and resize_from != input_size:
                from dmlc_tpu.ops import device_resize

                x = device_resize.resize_batch(u8, input_size) / 255.0
                x = (x - mean) / std
            elif self.use_pallas:
                from dmlc_tpu.ops import pallas_kernels as pk

                x = pk.normalize_u8(u8, mean_np, std_np, jnp.float32)
            else:
                x = u8.astype(jnp.float32) / 255.0
                x = (x - mean) / std  # fused into the first conv's input by XLA
            out = self.model.apply(variables, x, train=False)
            if classifier:
                if self.use_pallas:
                    from dmlc_tpu.ops import pallas_kernels as pk

                    return pk.softmax_top1(out)
                probs = jax.nn.softmax(out, axis=-1)
                idx = jnp.argmax(probs, axis=-1).astype(jnp.int32)
                top = jnp.max(probs, axis=-1)
                return idx, top
            return out

        param_shd = mesh_lib.param_shardings(self.mesh, self.variables)
        # Outputs are pinned batch-sharded (not left to XLA): on a
        # multi-host mesh each process reads back exactly its own rows via
        # addressable shards (run_batch_global), which requires knowing the
        # output sharding; on a single host this changes nothing.
        out_shd = (data_shd, data_shd) if classifier else data_shd
        self._data_sharding = data_shd
        # Precomputed once (mesh and process layout are fixed for the
        # engine's lifetime): does the dp axis PARTITION batch rows by
        # process, as run_batch_global's row-ownership contract requires?
        # None = fine; else the error to raise there.
        self._global_batch_error: str | None = None
        procs = jax.process_count()
        if procs > 1 and "dp" in self.mesh.axis_names:
            axis = self.mesh.axis_names.index("dp")
            me = jax.process_index()
            dp_coords = {
                idx[axis]
                for idx, dev in np.ndenumerate(self.mesh.devices)
                if dev.process_index == me
            }
            dp_size = self.mesh.devices.shape[axis]
            rows_owned = len(dp_coords) * (self.batch_size // dp_size)
            coords = sorted(dp_coords)
            if rows_owned != self.batch_size // procs:
                self._global_batch_error = (
                    f"mesh layout puts {rows_owned} batch rows on process {me} "
                    f"but run_batch_global assumes {self.batch_size // procs} "
                    "(= batch/processes): the dp axis must partition rows by "
                    "process — lay dp over processes (slowest-varying mesh "
                    "axis), tp/sp within hosts"
                )
            elif coords != list(range(coords[0], coords[0] + len(coords))):
                # Non-contiguous dp coords would make local_rows' sort-by-
                # global-start disagree with the row order
                # make_array_from_process_local_data packed the local batch
                # in — results would come back silently permuted. Refuse.
                self._global_batch_error = (
                    f"process {me} owns non-contiguous dp coordinates {coords}: "
                    "run_batch_global requires each process's dp slice to be "
                    "one contiguous run so local row order matches global row "
                    "order — build the mesh with an unpermuted device list"
                )
        # Compile-census wrappers (cluster/devicemon.py): every jit site
        # carries a stable program label so steady-state recompiles are
        # attributable per program, not just per process.
        self._forward = CensusedJit(
            f"infer/{model_name}",
            jax.jit(forward, in_shardings=(param_shd, data_shd), out_shardings=out_shd),
        )
        # Stream-pipeline variant: donates the staged input buffer so XLA may
        # reuse its HBM while the pipeline stages the NEXT batch — the
        # double-buffered staging ring (run_paths_stream) owns each buffer
        # for exactly one dispatch. The shared _forward cannot donate: its
        # callers (run_batch, bench) re-dispatch the same device arrays.
        # CPU's PJRT backend doesn't implement donation (jax would warn on
        # every batch), so there the stream path reuses the plain program.
        if self.mesh.devices.flat[0].platform == "cpu":
            self._forward_stream = self._forward
        else:
            self._forward_stream = CensusedJit(
                f"infer/{model_name}/stream",
                jax.jit(
                    forward,
                    in_shardings=(param_shd, data_shd),
                    out_shardings=out_shd,
                    donate_argnums=(1,),
                ),
            )
        # Per-stage ingest pipeline counters (INGEST_STAGES): decode/stage/
        # dispatch record from pool threads too, hence the lock.
        self._ingest_lock = threading.Lock()
        self._ingest = {k: LatencyStats() for k in INGEST_STAGES}

    @property
    def input_size(self) -> int:
        """Host-side staging size: what decoded batches must be shaped as.
        With device resize active this is the RAW size; the model's input
        size is reached on the chip."""
        return self.device_resize_from or self.spec.input_size

    def load_variables(self, variables) -> None:
        """Hot-swap the model weights (the member side of the `train` verb,
        reference services.rs:139-144 + 513-524). The new tree must match the
        compiled program's structure; it is re-sharded onto the mesh with the
        same rules, so the jitted forward is reused without recompilation."""
        old = jax.tree_util.tree_flatten_with_path(self.variables)
        new = jax.tree_util.tree_flatten_with_path(variables)
        if old[1] != new[1]:
            raise ValueError(f"variables tree mismatch: {new[1]} != compiled {old[1]}")
        for (path, cur), (_, nxt) in zip(old[0], new[0]):
            if tuple(cur.shape) != tuple(np.shape(nxt)):
                raise ValueError(
                    f"shape mismatch at {jax.tree_util.keystr(path)}: "
                    f"got {tuple(np.shape(nxt))}, compiled {tuple(cur.shape)}"
                )
        self.variables = mesh_lib.shard_params(self.mesh, variables)

    def warmup(self) -> float:
        """Compile both serving paths with a zero batch; returns
        compile+first-run seconds. Each path is warmed with the argument
        form it serves with, because jit keys its executable on it:
        ``run_batch`` passes an uncommitted batch, ``run_paths_stream`` one
        already committed to the batch sharding (a different lowering, and
        off CPU the donating program besides). Left to the first
        multi-batch shard, that second compile would hold the GIL
        mid-serving (the false-FAILED hazard EngineBackend.warmup exists to
        avoid) and be charged to that shard's deadline. The batches are
        device-side constants (jnp, not np): a host zeros array would ship
        batch_size full images over the host->device link just to warm up."""
        t0 = time.perf_counter()
        shape = (self.batch_size, self.input_size, self.input_size, 3)
        jax.block_until_ready(self._forward(self.variables, jnp.zeros(shape, jnp.uint8)))
        staged = jax.device_put(jnp.zeros(shape, jnp.uint8), self._data_sharding)
        jax.block_until_ready(self._forward_stream(self.variables, staged))
        return time.perf_counter() - t0

    def run_batch(self, batch_u8: np.ndarray) -> BatchResult:
        """Classify/embed up to ``batch_size`` images (uint8 NHWC)."""
        n = batch_u8.shape[0]
        if n == 0:
            raise ValueError("empty batch")
        if n > self.batch_size:
            raise ValueError(f"batch {n} exceeds engine batch_size {self.batch_size}")
        if n < self.batch_size:  # pad to the one compiled shape
            t0 = time.perf_counter()
            pad = np.zeros((self.batch_size - n, *batch_u8.shape[1:]), batch_u8.dtype)
            batch_u8 = np.concatenate([batch_u8, pad])
            tracer.record("ingest/stage", time.perf_counter() - t0,
                          model=self.spec.name, batch=int(n))
        t0 = time.perf_counter()
        out = self._forward(self.variables, batch_u8)
        out = jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        tracer.record("device/forward", dt, model=self.spec.name, batch=int(n))
        self._stats.record(dt)
        if self.device_work is not None:
            self.device_work(self.spec.name, int(n), dt)
        with tracer.span("ingest/collect", cpu=True):
            if self.spec.classifier:
                idx, top = (np.asarray(o) for o in out)
                return BatchResult(idx[:n], top[:n], None, dt)
            emb = np.asarray(out)[:n]
            return BatchResult(np.zeros(n, np.int32), np.zeros(n, np.float32), emb, dt)

    def run_batch_global(self, local_u8: np.ndarray) -> BatchResult:
        """Multi-host SPMD inference: every process calls this with its OWN
        sub-batch; together they form one global batch over the mesh's dp
        axis, one XLA program runs across all hosts (collectives over
        ICI/DCN), and each process gets back results for the rows IT
        contributed. Single-host this degenerates to run_batch.

        The global batch shape stays static: each process pads its shard to
        ``batch_size / process_count`` (so ``batch_size`` must divide evenly
        by the process count). Row ownership follows
        ``jax.make_array_from_process_local_data``: the global array is this
        process's rows at its mesh positions, so the output's addressable
        shards are exactly the answers to this process's inputs.
        """
        procs = jax.process_count()
        local_cap = self.batch_size // procs
        if self.batch_size % procs:
            raise ValueError(
                f"batch_size {self.batch_size} not divisible by {procs} processes"
            )
        if self._global_batch_error is not None:  # precomputed in __init__
            raise ValueError(self._global_batch_error)
        n = local_u8.shape[0]
        if n > local_cap:
            raise ValueError(f"local batch {n} exceeds per-process share {local_cap}")
        if n < local_cap:
            # Pads even an EMPTY shard (dataset tail): every process must
            # enter the collective forward or the others deadlock in it.
            pad = np.zeros((local_cap - n, *local_u8.shape[1:]), local_u8.dtype)
            local_u8 = np.concatenate([local_u8, pad])
        t0 = time.perf_counter()
        global_u8 = jax.make_array_from_process_local_data(self._data_sharding, local_u8)
        out = jax.block_until_ready(self._forward(self.variables, global_u8))
        dt = time.perf_counter() - t0
        self._stats.record(dt)
        tracer.record("device/forward_global", dt, model=self.spec.name, batch=int(n))
        if self.device_work is not None:
            self.device_work(self.spec.name, int(n), dt)

        def local_rows(x) -> np.ndarray:
            # Dedupe on batch index: with a tp axis this process addresses
            # REPLICAS of its rows on several devices; concatenating them
            # all would silently double rows.
            seen: set = set()
            rows = []
            for s in sorted(x.addressable_shards, key=lambda s: (s.index[0].start or 0)):
                key = s.index[0].start or 0
                if key not in seen:
                    seen.add(key)
                    rows.append(np.asarray(s.data))
            return np.concatenate(rows)

        if self.spec.classifier:
            idx, top = (local_rows(o) for o in out)
            return BatchResult(idx[:n], top[:n], None, dt)
        emb = local_rows(out)[:n]
        return BatchResult(np.zeros(n, np.int32), np.zeros(n, np.float32), emb, dt)

    def run_paths(self, paths: Sequence[str], workers: int | None = None) -> BatchResult:
        """Decode + resize on host threads, then one device batch."""
        with tracer.span("host/decode", n=len(paths)):
            batch = pp.load_batch(paths, size=self.input_size, workers=workers)
        return self.run_batch(batch)

    def start_decodes(
        self,
        paths: Sequence[str],
        workers: int | None = None,
        prefetch: int = 2,
        decode_source=None,
        leaf: str = "ingest/decode_submit",
    ) -> ShardDecodes:
        """Start a shard's first ``prefetch`` batch decodes on the stage
        pool, under the span open here, and hand back what
        ``run_paths_stream(paths, started=...)`` takes. ``leaf`` names the
        submit's span."""
        decodes = ShardDecodes(self, paths, workers, prefetch, decode_source)
        decodes.submit(leaf)
        return decodes

    @hot_path
    def run_paths_stream(
        self,
        paths: Sequence[str],
        workers: int | None = None,
        prefetch: int = 2,
        decode_source=None,
        started: ShardDecodes | None = None,
    ) -> BatchResult:
        """Decode overlapped with h2d transfer and device compute (SURVEY §7
        hard part b) — the three-stage ingest pipeline (docs/INGEST.md).

        1. **decode** — up to ``prefetch`` batches decode concurrently on the
           persistent stage pool (each batch itself fanning out per image
           via the native/PIL pool).
        2. **stage** — a double-buffered staging ring moves decoded batches
           onto the device (``jax.device_put`` with the batch sharding)
           ahead of dispatch, so the host->HBM transfer of batch i+1 rides
           under batch i's execution instead of inside its dispatch.
        3. **dispatch/compute** — staged buffers feed the jitted forward
           (input-donated off CPU, so the ring's HBM recycles), dispatched
           asynchronously and materialized two batches behind.

        Equivalent results to calling ``run_paths`` per batch, at up to
        min(decode_rate, device_rate) instead of their series combination.
        Every stage records into ingest_summary()/the tracer so bench.py's
        e2e leg can attribute wall time to decode vs stage vs compute vs
        sync.

        ``decode_source`` (optional) replaces the LOCAL per-batch decode
        with an external producer — ``decode_source(paths_chunk, size) ->
        uint8 [n, size, size, 3]`` — which is how the fleet decode tier
        (cluster/decodetier.py) plugs in: the prefetch stage still runs on
        the persistent stage pool and the staging ring/donation path below
        is untouched; only where the pixels come from changes.

        ``started`` (optional) is this shard's decodes as
        ``start_decodes(paths, ...)`` already began them, e.g. while another
        shard held the engine; ``workers``, ``prefetch`` and
        ``decode_source`` are then the handle's own.
        """
        if not paths:
            raise ValueError("empty path list")
        t_all = time.perf_counter()
        if started is None:
            # Decodes run under the span open HERE (the shard's engine/run),
            # not under whichever leaf span is open when one is submitted.
            decodes = self.start_decodes(paths, workers, prefetch, decode_source)
        else:
            if started.paths is not paths:
                raise ValueError("started decodes are another shard's")
            decodes = started
            # The batches submitted from here on run under this span.
            decodes.ctx = contextvars.copy_context() if tracer.enabled else None
        starts, futs = decodes.starts, decodes.futs
        outs: list[tuple[int, Any]] = []
        staged: collections.deque = collections.deque()
        inflight: collections.deque = collections.deque()
        for _ in starts:
            # Fill the staging ring (depth 2): block on decode only when the
            # ring is empty; opportunistically stage a second batch when its
            # decode already finished, so the next dispatch finds its input
            # device-resident.
            while futs and len(staged) < 2 and (not staged or futs[0].done()):
                fut = futs.popleft()
                with tracer.span("ingest/decode_wait", cpu=True, ready=fut.done()):
                    n, batch = fut.result()
                if decodes.submitted < len(starts):
                    decodes.submit()
                t0 = time.perf_counter()
                buf = jax.device_put(batch, self._data_sharding)
                self._record_stage("stage", time.perf_counter() - t0, batch=int(n))
                staged.append((n, buf))
            n, buf = staged.popleft()
            t0 = time.perf_counter()
            out = self._forward_stream(self.variables, buf)  # async dispatch
            self._record_stage("dispatch", time.perf_counter() - t0, batch=int(n))
            inflight.append((n, out))
            if len(inflight) > 2:  # sync two batches behind
                outs.append(self._materialize(*inflight.popleft()))
        while inflight:
            outs.append(self._materialize(*inflight.popleft()))
        total_dt = time.perf_counter() - t_all
        with self._ingest_lock:
            self._ingest["pipeline"].record(total_dt)
        if self.device_work is not None:
            # Pipeline wall, not isolated device time: on the stream path
            # the honest achieved-FLOP/s figure includes ingest stalls (a
            # decode-bound pipeline SHOULD read low MFU — that is the
            # signal that the host, not the chip, is the bottleneck).
            self.device_work(self.spec.name, len(paths), total_dt)

        with tracer.span("ingest/collect", cpu=True):
            if self.spec.classifier:
                idx = np.concatenate([np.asarray(o[0])[:n] for n, o in outs])
                top = np.concatenate([np.asarray(o[1])[:n] for n, o in outs])
                return BatchResult(idx, top, None, total_dt)
            emb = np.concatenate([np.asarray(o)[:n] for n, o in outs])
            return BatchResult(
                np.zeros(len(emb), np.int32), np.zeros(len(emb), np.float32), emb, total_dt
            )

    def _materialize(self, n: int, out):
        """Block on one in-flight device result. The recorded span is the
        SYNC WAIT — time the host stalls for the device — not the device's
        execution time: in a decode-bound pipeline the device finishes while
        the host decodes and this goes to ~0, which is exactly the signal
        that the host, not the device, is the bottleneck. (run_batch records
        true per-batch device latency into latency_summary.)"""
        t0 = time.perf_counter()
        # dmlc-lint: disable=A7 -- designed sync: _materialize IS the stream pipeline's two-behind backpressure barrier, and the wait is measured and exported as device/sync_wait rather than hidden
        out = jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        # The leaf record ends where it is made: before the statistics' lock.
        tracer.record("device/sync_wait", dt, model=self.spec.name, batch=int(n))
        with self._ingest_lock:
            self._ingest["sync"].record(dt)
        return n, out

    # ---- ingest pipeline observability ---------------------------------

    def _record_stage(self, stage: str, dt: float, **attrs) -> None:
        """A feeding-thread stage (``stage``, ``dispatch``) that ended just
        now: a leaf span, recorded before the statistics' lock so that it
        ends where the work ended."""
        tracer.record(f"ingest/{stage}", dt, model=self.spec.name, **attrs)
        with self._ingest_lock:
            self._ingest[stage].record(dt)

    def ingest_summary(self) -> dict[str, dict[str, float]]:
        """Per-stage pipeline counters since construction (or the last
        reset): count, total busy seconds, mean, and occupancy — the stage's
        busy time over the summed run_paths_stream wall time, i.e. how much
        of the pipeline's life the stage spent working. The bottleneck stage
        reads near 1.0; in a well-overlapped pipeline the others still show
        substantial occupancy instead of summing to 1.0 (that sum-to-one
        shape is the serial-pipeline signature)."""
        with self._ingest_lock:
            wall = self._ingest["pipeline"]
            wall_total = wall.mean * wall.n if wall.n else 0.0
            out: dict[str, dict[str, float]] = {}
            for name, st in self._ingest.items():
                total = st.mean * st.n if st.n else 0.0
                entry = {
                    "count": float(st.n),
                    "total_s": total,
                    "mean_s": st.mean if st.n else 0.0,
                }
                if name != "pipeline":
                    entry["occupancy"] = total / wall_total if wall_total > 0 else 0.0
                out[name] = entry
            return out

    def reset_ingest_stats(self) -> None:
        with self._ingest_lock:
            self._ingest = {k: LatencyStats() for k in INGEST_STAGES}

    def latency_summary(self) -> dict[str, float]:
        return self._stats.summary()

    def resident_bytes(self) -> int:
        """Analytic device residency: the sharded weights pytree (this
        engine keeps no persistent activation state) — the per-model
        attribution behind the ``resident_bytes_<model>`` gauge."""
        from dmlc_tpu.cluster.devicemon import pytree_nbytes

        return pytree_nbytes(self.variables)
