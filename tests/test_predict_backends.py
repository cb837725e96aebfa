"""Which backend serves a job model, and what a shard gets back from it.

``cluster/node.build_backends`` is the one place that decides: a registry
``kind="lm"`` model is served by ``LmBackend``, every other model by
``EngineBackend``. ``EngineBackend`` then chooses by the shard's size between
the serial ``run_paths`` (one device batch or less) and the overlapped
``run_paths_stream``; both sides must answer every image, in the shard's order.
"""

import numpy as np
import pytest
from PIL import Image

from dmlc_tpu.cluster.node import build_backends
from dmlc_tpu.scheduler.worker import EngineBackend, LmBackend
from dmlc_tpu.utils.config import ClusterConfig
from tiny_model import N_CLASSES  # registers "tinynet"

SERVED_BY = {
    "resnet18": EngineBackend,
    "resnet34": EngineBackend,
    "resnet50": EngineBackend,
    "alexnet": EngineBackend,
    "vit_b16": EngineBackend,
    "vit_l14": EngineBackend,
    "clip_vit_l14": EngineBackend,
    "clip_vit_b32": EngineBackend,
    "lm_small": LmBackend,
    "lm_wide": LmBackend,
    "nemotron_h_tiny": LmBackend,
}


@pytest.mark.parametrize("model", sorted(SERVED_BY))
def test_backend_of_a_registry_model(model, tmp_path):
    config = ClusterConfig(job_models=[model], data_dir=str(tmp_path), batch_size=24)
    seen = []
    backends = build_backends(config, seen.append)
    assert list(backends) == [model]
    backend = backends[model]
    assert type(backend) is SERVED_BY[model]
    assert backend.model_name == model and backend.device_work == seen.append
    if type(backend) is EngineBackend:
        assert backend.batch_size == 24 and backend.data_dir == tmp_path
        assert backend.image_source is None  # the node hands it over later


def test_every_registry_model_is_decided_by_its_kind():
    from dmlc_tpu.models.registry import get_model, list_models

    names = list_models()
    assert set(SERVED_BY) <= set(names)
    backends = build_backends(ClusterConfig(job_models=names), None)
    for name in names:
        want = LmBackend if get_model(name).kind == "lm" else EngineBackend
        assert type(backends[name]) is want, name


# ---------------------------------------------------------------------------
# A shard through EngineBackend: n answers, in order, on both sides of the
# choice between run_paths and run_paths_stream
# ---------------------------------------------------------------------------

BATCH = 8  # the CPU test mesh is dp=8


def colour(k: int) -> np.ndarray:
    return np.array([64 * (k % 4) + 32, 64 * (k // 4 % 4) + 32, 64 * (k // 16) + 32])


@pytest.fixture(scope="module")
def colour_backend(tmp_path_factory):
    """Class k holds one image of a colour of its own, and tinynet's weights
    are set so that it answers k for that colour (nearest centroid: the conv
    passes the normalised pixel through, +x and -x so that relu loses
    nothing; logit k is 2 c_k.x - |c_k|^2). An answer then names its image,
    so a missing, repeated or misplaced one shows."""
    import jax
    import jax.numpy as jnp

    from dmlc_tpu.models import weights as weights_lib
    from dmlc_tpu.ops import preprocess as pp

    n = 2 * BATCH + 1
    data_dir = tmp_path_factory.mktemp("colours") / "train"
    for k in range(n):
        d = data_dir / f"n{k:08d}"
        d.mkdir(parents=True)
        Image.fromarray(np.broadcast_to(colour(k).astype(np.uint8), (32, 32, 3)).copy()).save(
            d / "img0.jpg", quality=95
        )
    variables = jax.tree_util.tree_map(
        lambda s: np.zeros(s.shape, s.dtype), weights_lib.variables_template("tinynet")
    )
    conv, head = variables["params"]["conv1"], variables["params"]["head"]
    head["bias"][:] = -1e4  # classes with no image never win
    for c in range(3):
        conv["kernel"][1, 1, c, c] = 1.0
        conv["kernel"][1, 1, c, 3 + c] = -1.0
    for k in range(n):
        centroid = (colour(k) / 255.0 - pp.IMAGENET_MEAN) / pp.IMAGENET_STD
        head["kernel"][:3, k] = 2 * centroid
        head["kernel"][3:6, k] = -2 * centroid
        head["bias"][k] = -(centroid**2).sum()
    backend = EngineBackend(
        "tinynet", data_dir, batch_size=BATCH, variables=variables, dtype=jnp.float32
    )
    backend.warmup()
    return backend


@pytest.mark.parametrize(
    "n", [1, BATCH - 1, BATCH, BATCH + 1, 2 * BATCH, 2 * BATCH + 1]
)
def test_engine_backend_answers_every_image_in_shard_order(n, colour_backend, monkeypatch):
    assert n <= N_CLASSES
    engine = colour_backend._engine
    calls = []

    def counted(name, real):
        def call(paths, **kw):
            calls.append(name)
            return real(paths, **kw)

        return call

    for name in ("run_paths", "run_paths_stream"):
        monkeypatch.setattr(engine, name, counted(name, getattr(engine, name)))
    # Not the directory order: a shard's order is the caller's.
    shard = np.random.default_rng(n).permutation(n).tolist()
    got = colour_backend([f"n{k:08d}" for k in shard])
    assert got == shard
    assert calls == ["run_paths" if n <= BATCH else "run_paths_stream"]
