"""The pretrained-weights loop: publish -> SDFS -> `train` -> live engine.

The reference's ML story is loading real weights and measuring accuracy
(src/services.rs:513-524, 139-144); round 1 left the serving path on random
init. These tests close the loop end to end:

- blob round-trip + validation (models/weights.py)
- InferenceEngine.load_variables measurably changes predictions
- a real 2-node cluster: put crafted weights, run the `train` verb, and the
  jobs report's accuracy afterwards is exactly what those weights predict.

A tiny registered model ("tinynet") keeps the real-JAX path fast on CPU.
"""

import random

import jax
import numpy as np
import pytest

from dmlc_tpu.models import registry
from dmlc_tpu.models import weights as weights_lib
from tiny_model import N_CLASSES

TARGET_CLASS = 7


def constant_prediction_variables(target: int = TARGET_CLASS):
    """Weights that predict ``target`` for EVERY input: zero everything,
    put a spike in the head bias. Deterministic regardless of image bytes."""
    template = weights_lib.variables_template("tinynet")
    variables = jax.tree_util.tree_map(lambda s: np.zeros(s.shape, s.dtype), template)
    variables["params"]["head"]["bias"][target] = 5.0
    return variables


# ---------------------------------------------------------------------------
# Serialization + validation
# ---------------------------------------------------------------------------


def test_weights_roundtrip():
    _, variables = registry.get_model("tinynet").init_params(jax.random.PRNGKey(0))
    blob = weights_lib.weights_to_bytes("tinynet", variables)
    name, restored = weights_lib.weights_from_bytes(blob)
    assert name == "tinynet"
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_flatten_with_path(variables)[0],
        jax.tree_util.tree_flatten_with_path(restored)[0],
    ):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_weights_validation_errors():
    _, variables = registry.get_model("tinynet").init_params(jax.random.PRNGKey(0))
    blob = weights_lib.weights_to_bytes("tinynet", variables)

    with pytest.raises(ValueError, match="magic"):
        weights_lib.weights_from_bytes(b"garbage" + blob)
    with pytest.raises(ValueError, match="expected"):
        weights_lib.weights_from_bytes(blob, expect_model="resnet18")

    bad = jax.tree_util.tree_map(np.asarray, variables)
    bad["params"]["head"]["bias"] = np.zeros((N_CLASSES + 1,), np.float32)
    with pytest.raises(ValueError, match="shape mismatch"):
        weights_lib.weights_to_bytes("tinynet", bad)

    del bad["params"]["head"]
    with pytest.raises(ValueError, match="tree mismatch"):
        weights_lib.weights_to_bytes("tinynet", bad)


def test_engine_load_variables_changes_predictions():
    from dmlc_tpu.parallel.inference import InferenceEngine

    engine = InferenceEngine("tinynet", batch_size=8, seed=3)
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 256, (8, 32, 32, 3), np.uint8)
    engine.load_variables(constant_prediction_variables())
    result = engine.run_batch(batch)
    assert list(result.top1_index) == [TARGET_CLASS] * 8

    with pytest.raises(ValueError, match="tree mismatch"):
        engine.load_variables({"params": {"wrong": np.zeros((1,), np.float32)}})


# ---------------------------------------------------------------------------
# Full cluster: put -> train -> hot-load -> accuracy reflects the weights
# ---------------------------------------------------------------------------


from dmlc_tpu.cluster.localcluster import wait_until  # shared harness


@pytest.fixture
def corpus(tmp_path):
    """Synthetic fixture corpus: one 32x32 JPEG per synthetic synset, plus
    the synset_words file (the reference's test_files/imagenet_1k shape)."""
    from PIL import Image

    synsets = tmp_path / "synsets.txt"
    synsets.write_text("".join(f"n{i:08d} label {i}\n" for i in range(N_CLASSES)))
    data = tmp_path / "train"
    rng = np.random.default_rng(7)
    for i in range(N_CLASSES):
        d = data / f"n{i:08d}"
        d.mkdir(parents=True)
        arr = rng.integers(0, 256, (32, 32, 3), np.uint8)
        Image.fromarray(arr).save(d / "img0.jpg")
    return synsets, data


def test_train_verb_loads_real_weights(corpus, tmp_path):
    from dmlc_tpu.cluster.node import ClusterNode
    from dmlc_tpu.scheduler.worker import EngineBackend
    from dmlc_tpu.utils.config import ClusterConfig

    synset_path, data_dir = corpus
    base = random.randint(21000, 32000) // 10 * 10  # below the kernel's ephemeral range (32768+)
    leader_candidates = [f"127.0.0.1:{base + 1}"]
    nodes = []
    try:
        for i in range(2):
            cfg = ClusterConfig(
                host="127.0.0.1",
                gossip_port=base + 10 * i,
                leader_port=base + 10 * i + 1,
                member_port=base + 10 * i + 2,
                leader_candidates=leader_candidates,
                storage_dir=str(tmp_path / f"node{i}" / "storage"),
                synset_path=str(synset_path),
                data_dir=str(data_dir),
                job_models=["tinynet"],
                batch_size=8,
                replication_factor=2,
                dispatch_shard_size=8,
                heartbeat_interval_s=0.1,
                failure_timeout_s=1.0,
                rereplication_interval_s=0.2,
                assignment_interval_s=0.2,
                leader_probe_interval_s=0.2,
            )
            node = ClusterNode(
                cfg,
                backends={"tinynet": EngineBackend("tinynet", data_dir, batch_size=8)},
            )
            node.start()
            nodes.append(node)
        nodes[1].join(nodes[0].gossip.address)
        wait_until(
            lambda: all(len(n.membership.active_ids()) == 2 for n in nodes),
            msg="membership convergence",
        )
        wait_until(lambda: nodes[0].standby.is_leader, msg="leader promotion")

        # Publish crafted weights and run the train verb from the non-leader.
        version = weights_lib.publish_weights(
            nodes[1].sdfs, "tinynet", constant_prediction_variables()
        )
        assert version == 1
        results = nodes[1].train()
        entry = results["models/tinynet"]
        assert sorted(entry["loaded"]) == sorted(n.self_member_addr for n in nodes)
        # The broadcast pulls are in the leader directory (visible to ls).
        listing = nodes[1].sdfs.ls("models/tinynet")
        assert len(listing["models/tinynet"]) == 2

        # Every member now predicts TARGET_CLASS: accuracy is exactly 1/N.
        nodes[1].predict()
        leader = nodes[0]
        wait_until(
            lambda: all(j.done for j in leader.scheduler.jobs.values()),
            msg="job completion",
        )
        report = nodes[1].jobs_report()["tinynet"]
        assert report["finished"] == N_CLASSES
        assert report["correct"] == 1  # only the TARGET_CLASS synset matches
        assert abs(report["accuracy"] - 1.0 / N_CLASSES) < 1e-9
    finally:
        for n in nodes:
            n.stop()
