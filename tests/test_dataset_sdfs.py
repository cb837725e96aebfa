"""The BASELINE "4-node SDFS shard" configuration, hermetic: a 4-node
cluster where NO member has a local corpus — class images are published
once into the replicated store and members pull + cache them through the
ordinary SDFS get path to serve predict shards (north star: "stages
batches from the SDFS get path straight into HBM")."""

import random

import numpy as np
import pytest

from dmlc_tpu.cluster.node import ClusterNode
from dmlc_tpu.scheduler.dataset import SdfsImageSource, publish_corpus, sdfs_image_name
from dmlc_tpu.scheduler.worker import EngineBackend
from dmlc_tpu.utils.config import ClusterConfig
from tiny_model import N_CLASSES


from dmlc_tpu.cluster.localcluster import wait_until  # shared harness


def make_corpus(tmp_path, n):
    from PIL import Image

    synsets = tmp_path / "synsets.txt"
    synsets.write_text("".join(f"n{i:08d} label {i}\n" for i in range(n)))
    data = tmp_path / "seed_corpus"
    rng = np.random.default_rng(5)
    for i in range(n):
        d = data / f"n{i:08d}"
        d.mkdir(parents=True)
        Image.fromarray(rng.integers(0, 256, (32, 32, 3), np.uint8)).save(d / "x.jpg")
    return synsets, data


def test_sdfs_image_source_pull_and_cache(tmp_path):
    from dmlc_tpu.cluster.rpc import SimRpcNetwork
    from dmlc_tpu.cluster.sdfs import MemberStore, SdfsClient, SdfsLeader, SdfsMember

    _, data = make_corpus(tmp_path, 4)
    net = SimRpcNetwork()
    stores = {}
    for m in ("m0", "m1"):
        stores[m] = MemberStore(tmp_path / m)
        net.serve(m, SdfsMember(stores[m], net.client(m)).methods())
    net.serve(
        "L", SdfsLeader(net.client("L"), lambda: ["m0", "m1"], replication_factor=2).methods()
    )
    client = SdfsClient(net.client("m0"), "L", stores["m0"], "m0")

    assert publish_corpus(client, data) == 4
    source = SdfsImageSource(client, tmp_path / "cache")
    paths = source([f"n{i:08d}" for i in range(4)])
    assert all(p.exists() for p in paths)
    assert paths[0].read_bytes() == (data / "n00000000" / "x.jpg").read_bytes()

    # Cache hit: a second resolve must not touch the network.
    calls_before = len(net.calls)
    again = source(["n00000000"])
    assert again[0] == paths[0]
    assert len(net.calls) == calls_before


def test_four_node_sdfs_sharded_inference(tmp_path):
    """4 nodes, zero local corpora, tinynet engines: publish -> predict ->
    every shard served from SDFS-pulled images, full accuracy."""
    synset_path, seed_data = make_corpus(tmp_path, N_CLASSES)
    base = random.randint(21000, 32000) // 10 * 10  # below the kernel's ephemeral range (32768+)
    leader_candidates = [f"127.0.0.1:{base + 1}"]
    nodes = []
    try:
        for i in range(4):
            cfg = ClusterConfig(
                host="127.0.0.1",
                gossip_port=base + 10 * i,
                leader_port=base + 10 * i + 1,
                member_port=base + 10 * i + 2,
                leader_candidates=leader_candidates,
                storage_dir=str(tmp_path / f"node{i}" / "storage"),
                synset_path=str(synset_path),
                data_dir=str(tmp_path / f"node{i}" / "no_such_corpus"),
                data_from_sdfs=True,
                job_models=["tinynet"],
                batch_size=8,
                replication_factor=2,
                dispatch_shard_size=8,
                dispatch_workers=4,
                heartbeat_interval_s=0.1,
                failure_timeout_s=1.0,
                rereplication_interval_s=0.2,
                assignment_interval_s=0.2,
                leader_probe_interval_s=0.2,
            )
            node = ClusterNode(
                cfg,
                backends={
                    "tinynet": EngineBackend(
                        "tinynet", cfg.data_dir, batch_size=8
                    )
                },
            )
            node.start()
            nodes.append(node)
        for n in nodes[1:]:
            n.join(nodes[0].gossip.address)
        wait_until(
            lambda: all(len(n.membership.active_ids()) == 4 for n in nodes),
            msg="4-node membership",
        )
        wait_until(lambda: nodes[0].standby.is_leader, msg="leader promotion")

        # Publish the corpus into SDFS from one node; no member has it locally.
        assert publish_corpus(nodes[2].sdfs, seed_data) == N_CLASSES
        listing = nodes[1].sdfs.ls(sdfs_image_name("n00000000"))
        assert len(listing[sdfs_image_name("n00000000")]) == 2  # rf=2

        nodes[1].predict()
        leader = nodes[0]
        wait_until(
            lambda: all(j.done for j in leader.scheduler.jobs.values()),
            timeout=60.0,
            msg="sharded jobs complete",
        )
        report = nodes[3].jobs_report()["tinynet"]
        assert report["finished"] == N_CLASSES
        # Random-init tinynet on noise images: accuracy is whatever it is,
        # but every query was answered from SDFS-pulled bytes.
        assert len(report["assigned"]) == 4  # all members served
        pulled_any = any(
            any((tmp_path / f"node{i}" / "data_cache").glob("*.img")) for i in range(4)
        )
        assert pulled_any
    finally:
        for n in nodes:
            n.stop()
