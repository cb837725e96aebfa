"""Preprocessing tests: decode/resize/normalize semantics and label parsing."""

import os
import shutil
import sys
import threading
import time

import numpy as np
import pytest

from dmlc_tpu.ops import preprocess as pp


@pytest.fixture(scope="module")
def fixture_dataset(tmp_path_factory):
    """Tiny generated imagenet-style fixture: <root>/<synset>/img.jpg per class,
    plus a synset_words file — same shape as the reference's
    test_files/imagenet_1k/train + synset_words.txt corpus (SURVEY.md C21)."""
    from PIL import Image

    root = tmp_path_factory.mktemp("imagenet_fixture")
    data = root / "train"
    rng = np.random.RandomState(0)
    lines = []
    for i in range(8):
        synset = f"n{i:08d}"
        label = f"class {i}, fake"
        lines.append(f"{synset} {label}")
        d = data / synset
        d.mkdir(parents=True)
        arr = rng.randint(0, 255, (64 + i, 48 + i, 3), np.uint8)
        Image.fromarray(arr).save(d / "img.jpg", quality=95)
    (root / "synset_words.txt").write_text("\n".join(lines) + "\n")
    return root


def test_load_synset_words(fixture_dataset):
    pairs = pp.load_synset_words(fixture_dataset / "synset_words.txt")
    assert len(pairs) == 8
    assert pairs[0] == ("n00000000", "class 0, fake")
    assert pairs[3][0] == "n00000003"


def test_class_image_path(fixture_dataset):
    p = pp.class_image_path(fixture_dataset / "train", "n00000002")
    assert p.name == "img.jpg"
    with pytest.raises(FileNotFoundError):
        pp.class_image_path(fixture_dataset / "train", "n99999999")


def test_decode_resize_shape_dtype(fixture_dataset):
    p = pp.class_image_path(fixture_dataset / "train", "n00000000")
    img = pp.decode_resize(p, 224)
    assert img.shape == (224, 224, 3) and img.dtype == np.uint8
    img96 = pp.decode_resize(p, 96)
    assert img96.shape == (96, 96, 3)


def test_load_batch_matches_single(fixture_dataset):
    paths = [pp.class_image_path(fixture_dataset / "train", f"n{i:08d}") for i in range(8)]
    batch = pp.load_batch(paths, size=64, backend="pil")
    assert batch.shape == (8, 64, 64, 3)
    single = pp.decode_resize(paths[3], 64)
    np.testing.assert_array_equal(batch[3], single)


def test_load_batch_backends_agree(fixture_dataset):
    from dmlc_tpu import native

    if not native.available():
        pytest.skip("native pipeline not built")
    paths = [pp.class_image_path(fixture_dataset / "train", f"n{i:08d}") for i in range(8)]
    a = pp.load_batch(paths, size=64, backend="native").astype(np.int16)
    b = pp.load_batch(paths, size=64, backend="pil").astype(np.int16)
    diff = np.abs(a - b)
    assert diff.mean() < 1.0  # JPEG-noise tolerance; resample kernels match
    assert np.percentile(diff, 99) <= 16


def test_load_batch_auto_falls_back_for_non_jpeg(tmp_path):
    from PIL import Image

    p = tmp_path / "img.png"  # libjpeg can't decode PNG; auto must fall back
    rng = np.random.RandomState(1)
    Image.fromarray(rng.randint(0, 255, (40, 40, 3), np.uint8)).save(p)
    batch = pp.load_batch([p], size=32, backend="auto")
    assert batch.shape == (1, 32, 32, 3)
    assert batch.any()  # real pixels, not the native path's zero fill


def test_load_batch_unknown_backend():
    with pytest.raises(ValueError, match="unknown backend"):
        pp.load_batch(["x"], backend="cuda")


def test_normalize_values():
    u8 = np.zeros((1, 2, 2, 3), np.uint8)
    out = np.asarray(pp.normalize(u8))
    # 0 -> (0 - mean)/std exactly
    expect = (0.0 - pp.IMAGENET_MEAN) / pp.IMAGENET_STD
    np.testing.assert_allclose(out[0, 0, 0], expect, rtol=1e-6)
    u8 = np.full((1, 1, 1, 3), 255, np.uint8)
    out = np.asarray(pp.normalize(u8, pp.CLIP_MEAN, pp.CLIP_STD))
    expect = (1.0 - pp.CLIP_MEAN) / pp.CLIP_STD
    np.testing.assert_allclose(out[0, 0, 0], expect, rtol=1e-5)


def test_empty_batch():
    assert pp.load_batch([], size=32).shape == (0, 32, 32, 3)


# ---------------------------------------------------------------------------
# class_image_paths: the memoised lookup answers what class_image_path answers
# ---------------------------------------------------------------------------


def settle(*dirs, age_s=3600.0):
    """Date the directories an hour back, as a corpus at rest is: one changed
    in the last two seconds is answered but not remembered."""
    then = time.time() - age_s
    for d in dirs:
        os.utime(d, (then, then))


@pytest.fixture
def class_dirs(tmp_path):
    """Six settled class directories of three files each (bytes, not JPEGs:
    the lookup reads names only)."""
    data = tmp_path / "train"
    for i in range(6):
        d = data / f"n{i:08d}"
        d.mkdir(parents=True)
        for name in ("b.jpg", "c.jpg", "d.jpg"):
            (d / name).write_bytes(b"x")
        (d / "a_subdir").mkdir()  # sorts first, is no file
    settle(*data.iterdir())
    return data, [f"n{i:08d}" for i in range(6)]


def plain(data, synsets):
    return [pp.class_image_path(data, s) for s in synsets]


@pytest.mark.parametrize("call", ("first", "second"))
def test_class_image_paths_equal_the_plain_lookup(fixture_dataset, call):
    data = fixture_dataset / "train"
    settle(*data.iterdir())
    synsets = [f"n{i:08d}" for i in range(8)] * 3
    if call == "second":
        pp.class_image_paths(data, synsets)
    paths, _ = pp.class_image_paths(str(data) if call == "second" else data, synsets)
    assert paths == plain(data, synsets)
    assert [str(p) for p in paths] == [str(p) for p in plain(data, synsets)]
    assert all(type(p) is type(data) for p in paths)


def test_class_image_paths_counts_listings(class_dirs):
    data, synsets = class_dirs
    paths, misses = pp.class_image_paths(data, synsets * 2)
    assert misses == len(synsets)            # each directory listed once, then hit
    assert all(p.name == "b.jpg" for p in paths)
    assert pp.class_image_paths(data, synsets * 2) == (paths, 0)
    assert pp.class_image_paths(data, iter(synsets)) == (paths[:6], 0)   # any iterable
    assert pp.class_image_paths(data, []) == ([], 0)


def test_class_image_paths_hit_is_one_stat_a_directory_and_no_listing(class_dirs, monkeypatch):
    """What a remembered shard costs, as a count: one stat per DISTINCT class
    directory, relative to data_dir held open, and no listing."""
    data, synsets = class_dirs
    expect, _ = pp.class_image_paths(data, synsets * 3)
    stats, real_stat = [], os.stat

    def counting_stat(path, **kw):
        stats.append((path, "dir_fd" in kw))
        return real_stat(path, **kw)

    def no_listing(*a, **kw):
        raise AssertionError("a remembered directory was listed")

    monkeypatch.setattr(pp.os, "stat", counting_stat)
    monkeypatch.setattr(pp.os, "scandir", no_listing)
    assert pp.class_image_paths(data, synsets * 3) == (expect, 0)
    assert sorted(stats) == [(s, True) for s in synsets]


def test_class_image_paths_missing_data_dir(tmp_path):
    with pytest.raises(FileNotFoundError):
        pp.class_image_path(tmp_path / "nowhere", "n00000000")
    with pytest.raises(FileNotFoundError):
        pp.class_image_paths(tmp_path / "nowhere", ["n00000000"])


def _add(d):
    (d / "a.jpg").write_bytes(b"x")
    return "a.jpg"


def _remove(d):
    (d / "b.jpg").unlink()
    return "c.jpg"


def _rename(d):
    (d / "b.jpg").rename(d / "z.jpg")
    return "c.jpg"


@pytest.mark.parametrize("edit", (_add, _remove, _rename), ids=("add", "remove", "rename"))
def test_class_image_paths_see_an_edit_between_two_calls(class_dirs, edit):
    data, synsets = class_dirs
    assert pp.class_image_paths(data, synsets)[1] == len(synsets)
    assert pp.class_image_paths(data, synsets)[1] == 0          # remembered
    expect = edit(data / synsets[2])
    paths, misses = pp.class_image_paths(data, synsets)
    assert paths[2].name == expect and paths == plain(data, synsets)
    assert misses == 1                                          # only the edited directory
    # the edit is seconds old: answered from disk until the directory has settled
    paths, misses = pp.class_image_paths(data, synsets)
    assert paths == plain(data, synsets) and misses == 1
    settle(data / synsets[2])
    assert pp.class_image_paths(data, synsets) == (paths, 1)
    assert pp.class_image_paths(data, synsets) == (paths, 0)


def test_class_image_paths_never_remember_a_directory_dated_ahead(class_dirs):
    """Within a file system's timestamp granule a second change leaves the
    mtime where it was, so a directory not yet two seconds old (here: dated
    an hour ahead, whatever the test's pace) is listed at every call."""
    data, synsets = class_dirs
    ahead = time.time() + 3600.0
    os.utime(data / synsets[0], (ahead, ahead))
    for _ in range(3):
        paths, misses = pp.class_image_paths(data, synsets[:2])
        assert paths == plain(data, synsets[:2])
    assert misses == 1
    (data / synsets[0] / "a.jpg").write_bytes(b"x")
    os.utime(data / synsets[0], (ahead, ahead))               # an edit that leaves the mtime alone
    assert pp.class_image_paths(data, synsets[:1])[0][0].name == "a.jpg"


def _missing(d):
    shutil.rmtree(d)


def _empty(d):
    for f in d.iterdir():
        if f.is_file():
            f.unlink()


@pytest.mark.parametrize("when", ("on_a_miss", "after_a_hit"))
@pytest.mark.parametrize("fault", (_missing, _empty), ids=("missing", "empty"))
def test_class_image_paths_raise_like_the_plain_lookup(class_dirs, fault, when):
    data, synsets = class_dirs
    if when == "after_a_hit":
        pp.class_image_paths(data, synsets)
        assert pp.class_image_paths(data, synsets)[1] == 0
    fault(data / synsets[1])
    for _ in range(2):
        with pytest.raises(FileNotFoundError) as plain_err:
            pp.class_image_path(data, synsets[1])
        with pytest.raises(FileNotFoundError) as memo_err:
            pp.class_image_paths(data, synsets)
        assert str(memo_err.value) == str(plain_err.value)
    # the directory comes back: so does the answer
    (data / synsets[1]).mkdir(exist_ok=True)
    (data / synsets[1] / "q.jpg").write_bytes(b"x")
    assert pp.class_image_paths(data, synsets)[0] == plain(data, synsets)


def test_class_image_paths_eight_threads_agree(class_dirs):
    data, synsets = class_dirs
    rng = np.random.RandomState(7)
    shard = [synsets[i] for i in rng.randint(0, len(synsets), 3072)]
    gate = threading.Barrier(8)
    results, errors = [None] * 8, []

    def resolve(i):
        try:
            gate.wait(timeout=30)
            results[i] = pp.class_image_paths(data, shard)
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=resolve, args=(i,)) for i in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # threads change places inside the lookup, not around it
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(t.is_alive() for t in threads)
    expect = plain(data, shard)
    assert all(paths == expect for paths, _ in results)
    assert all(0 <= misses <= len(synsets) for _, misses in results)
    assert sum(misses for _, misses in results) >= len(synsets)   # someone listed each directory
    assert pp.class_image_paths(data, shard) == (expect, 0)
