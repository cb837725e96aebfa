"""Decode/compute overlap: run_paths_stream parity + the synthetic corpus.

SURVEY §7 hard part (b): at >10k img/s the JPEG decode must overlap with
device transfer/compute. These tests pin the overlapped pipeline's
*correctness* (identical results to the serial per-batch path, tail-batch
padding, embedding models, pipeline really interleaves) on the CPU mesh;
its throughput is measured by bench.py's e2e mode on hardware.
"""

import time

import numpy as np
import pytest

from dmlc_tpu.utils import corpus
from tiny_model import N_CLASSES  # registers "tinynet"


@pytest.fixture(scope="module")
def small_corpus(tmp_path_factory):
    root = tmp_path_factory.mktemp("corpus")
    data_dir, synset_path = corpus.generate(
        root, n_classes=12, images_per_class=2, size=48
    )
    paths = sorted(p for d in sorted(data_dir.iterdir()) for p in d.iterdir())
    return data_dir, synset_path, paths


def test_corpus_layout(small_corpus):
    from dmlc_tpu.ops.preprocess import class_image_path, load_synset_words

    data_dir, synset_path, paths = small_corpus
    assert len(paths) == 24
    words = load_synset_words(synset_path)
    assert len(words) == 12
    first = class_image_path(data_dir, words[0][0])
    assert first.suffix == ".jpg"
    # Regeneration is a no-op on an existing corpus...
    again_dir, _ = corpus.generate(data_dir.parent, n_classes=12, images_per_class=2)
    assert again_dir == data_dir
    # ...but a request for MORE images per class must regenerate, not
    # silently hand back the smaller corpus.
    grown_dir, _ = corpus.generate(data_dir.parent, n_classes=12, images_per_class=3, size=48)
    grown = [p for d in sorted(grown_dir.iterdir()) for p in d.iterdir()]
    assert len(grown) == 36


def test_stream_matches_serial(small_corpus):
    from dmlc_tpu.parallel.inference import InferenceEngine

    _, _, paths = small_corpus
    engine = InferenceEngine("tinynet", batch_size=8, seed=1)
    # 24 images / batch 8 = 3 full batches; also slice to force a ragged tail.
    for subset in (paths, paths[:19]):
        serial_idx, serial_top = [], []
        for s in range(0, len(subset), 8):
            r = engine.run_paths(subset[s : s + 8])
            serial_idx.extend(r.top1_index)
            serial_top.extend(r.top1_prob)
        stream = engine.run_paths_stream(subset)
        assert len(stream.top1_index) == len(subset)
        np.testing.assert_array_equal(stream.top1_index, serial_idx)
        np.testing.assert_allclose(stream.top1_prob, serial_top, rtol=1e-6)


def test_stream_embedding_model(small_corpus):
    from dmlc_tpu.models import registry
    from dmlc_tpu.parallel.inference import InferenceEngine
    from tiny_model import TinyEmbed  # noqa: F401  (registers tinyembed)

    _, _, paths = small_corpus
    engine = InferenceEngine("tinyembed", batch_size=8, seed=2)
    stream = engine.run_paths_stream(paths[:19])
    assert stream.embeddings.shape == (19, 16)
    serial = engine.run_paths(paths[:8])
    np.testing.assert_allclose(stream.embeddings[:8], serial.embeddings, rtol=1e-6)


def test_stream_actually_overlaps(small_corpus, monkeypatch):
    """The decode of batch i+1 must start before batch i's result is
    materialized — observed via span ordering on a slowed-down fake."""
    import threading

    from dmlc_tpu.parallel.inference import InferenceEngine
    from dmlc_tpu.ops import preprocess as pp

    _, _, paths = small_corpus
    engine = InferenceEngine("tinynet", batch_size=8, seed=3)

    events = []
    lock = threading.Lock()
    real_load = pp.load_batch

    def traced_load(ps, **kw):
        with lock:
            events.append("decode_start")
        out = real_load(ps, **kw)
        with lock:
            events.append("decode_end")
        return out

    real_materialize = engine._materialize

    def traced_materialize(n, out):
        with lock:
            events.append("materialize")
        return real_materialize(n, out)

    monkeypatch.setattr(pp, "load_batch", traced_load)
    engine._materialize = traced_materialize
    engine.run_paths_stream(paths)  # 3 batches
    # With prefetch=2 the second decode starts before the first materialize.
    assert events.index("decode_start", 1) < events.index("materialize")


# ---------------------------------------------------------------------------
# One shard decodes ahead: EngineBackend starts the next shard's decodes while
# the current one holds the engine, and no more than one (ROADMAP B3).
# ---------------------------------------------------------------------------

AHEAD_BATCH = 8  # the CPU test mesh is dp=8
AHEAD_SHARD = 2 * AHEAD_BATCH  # two batches: both decode ahead (prefetch 2)
DECODE_S = 0.15  # a slowed decode; the GIL is released while it sleeps


@pytest.fixture(scope="module")
def ahead_backend(tmp_path_factory):
    from dmlc_tpu.scheduler.worker import EngineBackend

    root = tmp_path_factory.mktemp("ahead_corpus")
    data_dir, _ = corpus.generate(root, n_classes=24, images_per_class=1, size=32)
    synsets = sorted(d.name for d in data_dir.iterdir())
    be = EngineBackend("tinynet", data_dir, batch_size=AHEAD_BATCH)
    be.warmup()
    be(synsets[:AHEAD_SHARD])  # first use builds the stage pool's threads
    rng = np.random.default_rng(7)
    shards = [[synsets[k] for k in rng.permutation(len(synsets))[:AHEAD_SHARD]]
              for _ in range(4)]
    return be, shards


def serial_answer(be, synsets):
    """What run_paths gives batch by batch: the shard's reply, served alone."""
    from dmlc_tpu.ops import preprocess as pp

    paths = pp.class_image_paths(be.data_dir, synsets)[0]
    return [int(x) for s in range(0, len(paths), AHEAD_BATCH)
            for x in be._engine.run_paths(paths[s : s + AHEAD_BATCH]).top1_index]


class Recorder:
    """A slowed ``load_batch`` and ``run_paths_stream`` that note, per shard,
    when each decode started and ended and when the shard held the engine.
    A shard is told apart by its batches (each shard is another order)."""

    def __init__(self, be, shards, monkeypatch, fail=None):
        import threading

        from dmlc_tpu.ops import preprocess as pp

        self.lock = threading.Lock()
        self.decodes = []  # (shard, start, end)
        self.runs = {}  # shard -> (start, end) of run_paths_stream
        self.raised = {}  # shard -> instant its failing decode raised
        self.busy = {}  # shard -> decodes in progress
        self.most_shards = 0  # most shards with a decode in progress at once
        self.run_started = threading.Event()
        self.owner = {}
        for i, synsets in enumerate(shards):
            paths = pp.class_image_paths(be.data_dir, synsets)[0]
            for s in range(0, len(paths), AHEAD_BATCH):
                self.owner[tuple(map(str, paths[s : s + AHEAD_BATCH]))] = i
        assert len(self.owner) == 2 * len(shards)
        real_load, engine = pp.load_batch, be._engine
        real_stream = engine.run_paths_stream

        def load(paths, **kw):
            shard = self.owner[tuple(map(str, paths))]
            t0 = time.perf_counter()
            with self.lock:
                self.busy[shard] = self.busy.get(shard, 0) + 1
                self.most_shards = max(self.most_shards, sum(1 for v in self.busy.values() if v))
            try:
                if shard == fail:
                    with self.lock:
                        self.raised[shard] = time.perf_counter()
                    raise OSError("a corrupt JPEG")
                time.sleep(DECODE_S)
                return real_load(paths, **kw)
            finally:
                with self.lock:
                    self.busy[shard] -= 1
                    self.decodes.append((shard, t0, time.perf_counter()))

        def stream(paths, *a, **kw):
            shard = self.owner[tuple(map(str, paths[:AHEAD_BATCH]))]
            t0 = time.perf_counter()
            self.run_started.set()
            try:
                return real_stream(paths, *a, **kw)
            finally:
                self.runs[shard] = (t0, time.perf_counter())

        monkeypatch.setattr(pp, "load_batch", load)
        monkeypatch.setattr(engine, "run_paths_stream", stream)


def serve(be, shards, starts=None):
    """Each shard on its own thread; ``starts`` gates a thread's start."""
    import threading

    replies, errors = {}, {}

    def one(i):
        try:
            replies[i] = be(shards[i])
        except Exception as e:  # noqa: BLE001 -- the test reads it
            errors[i] = e

    threads = []
    for i in range(len(shards)):
        if starts is not None:
            starts(i)
        threads.append(threading.Thread(target=one, args=(i,)))
        threads[-1].start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    return replies, errors


@pytest.fixture(scope="module")
def ahead_run(ahead_backend):
    be, shards = ahead_backend
    want = [serial_answer(be, s) for s in shards]
    with pytest.MonkeyPatch.context() as mp:
        rec = Recorder(be, shards, mp)
        replies, errors = serve(be, shards)
    return be, shards, want, rec, replies, errors


def test_next_shards_decode_starts_while_the_holder_runs(ahead_run):
    """The second shard to hold the engine started decoding before the first
    shard's hold ended: its decode overlapped the holder's, not followed it."""
    _, _, _, rec, _, errors = ahead_run
    assert not errors
    first, second = sorted(rec.runs, key=lambda i: rec.runs[i][0])[:2]
    second_start = min(t0 for shard, t0, _ in rec.decodes if shard == second)
    assert second_start < rec.runs[first][1]
    # ... and its decodes were done, or under way, when it took the engine
    assert second_start < rec.runs[second][0]


def test_no_more_than_one_shard_decodes_ahead(ahead_run):
    """With four shards at one engine, decodes of two shards (the holder's
    and one ahead) were in progress at once, never of three."""
    _, _, _, rec, _, _ = ahead_run
    assert rec.most_shards == 2
    assert len(rec.decodes) == 4 * 2  # every batch decoded once


def test_every_reply_equals_the_serial_answer(ahead_run):
    _, shards, want, _, replies, errors = ahead_run
    assert not errors
    assert [replies[i] for i in range(len(shards))] == want


def test_a_decode_that_fails_ahead_fails_its_own_shard_only(ahead_backend, monkeypatch):
    """Shard 1 decodes ahead while shard 0 holds the engine, and its decode
    raises: shard 1's reply is the error, shard 0's and shard 2's are right,
    and the ahead slot is free afterwards."""
    be, shards = ahead_backend
    shards = shards[:3]
    want = [serial_answer(be, s) for s in shards]
    rec = Recorder(be, shards, monkeypatch, fail=1)

    def starts(i):
        if i == 1:  # shard 0 holds the engine
            assert rec.run_started.wait(timeout=30)
        if i == 2:  # shard 1's decode has raised, ahead
            deadline = time.perf_counter() + 30
            while 1 not in rec.raised and time.perf_counter() < deadline:
                time.sleep(0.001)

    replies, errors = serve(be, shards, starts)
    assert sorted(errors) == [1] and isinstance(errors[1], OSError)
    assert replies == {0: want[0], 2: want[2]}
    assert rec.raised[1] < rec.runs[0][1]  # it raised while shard 0 held the engine
    assert be._ahead.acquire(blocking=False)
    be._ahead.release()
