"""Image preprocessing: decode, resize, ImageNet-normalize, batch.

Capability parity with the reference's
``tch::vision::imagenet::load_image_and_resize(path, 224, 224)`` +
normalization (reference: src/services.rs:492): decode a JPEG, resize to the
model's input size, scale to [0,1], normalize with the ImageNet mean/std, and
also the label utilities around ``synset_words.txt`` (src/services.rs:170-184)
and per-class fixture lookup (src/services.rs:485-490).

Design split, TPU-first:
- **Host side** (numpy/PIL): decode + resize, returns uint8 HWC. JPEG decode
  cannot run on the TPU; at >10k img/s it must be overlapped with device
  compute, which the batch loader does with a thread pool.
- **Device side** (jax, fused into the model's first conv by XLA, or the
  Pallas kernel in ops/pallas_kernels.py): uint8 -> float, /255, (x-mean)/std.
  Shipping uint8 to the device cuts host->HBM transfer bytes 4x vs fp32.
"""

from __future__ import annotations

import concurrent.futures
import os
import threading
import time
from pathlib import Path
from typing import Iterable, Sequence

import jax.numpy as jnp
import numpy as np

from dmlc_tpu.utils.hotpath import hot_path

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)
CLIP_MEAN = np.array([0.48145466, 0.4578275, 0.40821073], np.float32)
CLIP_STD = np.array([0.26862954, 0.26130258, 0.27577711], np.float32)

# ---- cached host decode pool ----------------------------------------------
# One module-level pool shared by every load_batch call. The original design
# built (and tore down) a fresh ThreadPoolExecutor per batch — at serving
# steady state that is thread spawn/join churn on every shard, the exact
# pattern lint rule H1 now forbids on hot paths. Grow-only: a bigger
# ``workers`` request replaces the pool; the abandoned smaller pool's idle
# threads are reclaimed at interpreter exit (same rationale as
# JobScheduler._ensure_gang_pool).
_HOST_POOL: concurrent.futures.ThreadPoolExecutor | None = None
_HOST_POOL_WORKERS = 0
_HOST_POOL_LOCK = threading.Lock()


def _host_pool(workers: int) -> concurrent.futures.ThreadPoolExecutor:
    global _HOST_POOL, _HOST_POOL_WORKERS
    with _HOST_POOL_LOCK:
        if _HOST_POOL is None or _HOST_POOL_WORKERS < workers:
            _HOST_POOL = concurrent.futures.ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="pp-decode"
            )
            _HOST_POOL_WORKERS = workers
        return _HOST_POOL


def load_synset_words(path: str | Path) -> list[tuple[str, str]]:
    """Parse synset_words.txt lines 'n01440764 tench, Tinca tinca' ->
    [(synset_id, label), ...] in file order. The file order defines the class
    index order (reference: src/services.rs:170-184), and the list doubles as
    the query workload for the scheduler."""
    out: list[tuple[str, str]] = []
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        synset, _, label = line.partition(" ")
        out.append((synset, label))
    return out


def _first_file(d: str) -> str:
    """Name of the first regular file (by name) in directory ``d``: one
    ``scandir`` pass, file type from the dirent, no ``Path`` per entry."""
    with os.scandir(d) as entries:
        first = min((e.name for e in entries if e.is_file()), default=None)
    if first is None:
        raise FileNotFoundError(f"no images under {d}")
    return first


def class_image_path(data_dir: str | Path, synset: str) -> Path:
    """First image in the per-class fixture directory
    (reference: src/services.rs:485-490 picks the first dir entry).

    The plain definition: it lists the directory at every call and remembers
    nothing. A serving shard goes through :func:`class_image_paths`."""
    d = Path(data_dir) / synset
    return d / _first_file(str(d))


# ---- memoised class-directory lookup ---------------------------------------
# (data_dir, synset) -> (the class directory's st_mtime_ns when it was
# listed, its first file). One entry per class directory ever asked for
# (1,000 for ImageNet), never evicted. A plain dict: reads and writes are
# atomic under the interpreter lock and two threads that miss on one key
# write the same value, so the hit path takes no lock.
_CLASS_PATHS: dict[tuple[str, str], tuple[int, Path]] = {}

#: A directory changed this recently is answered but not remembered: a
#: second change within the file system's timestamp granule (a scheduler
#: tick on ext4/tmpfs, 1-2 s on older ones) would leave st_mtime_ns where
#: it was, and the memo would then serve a stale first file.
_MTIME_SETTLE_NS = 2_000_000_000


def _checked_class_path(root: str, fd: int, synset: str, settled_before: int) -> tuple[Path, bool]:
    """One class directory's first file, and whether it had to be listed."""
    try:
        mtime = os.stat(synset, dir_fd=fd).st_mtime_ns
    except FileNotFoundError as e:  # name the whole path, as the plain lookup does
        raise FileNotFoundError(e.errno, e.strerror, os.path.join(root, synset)) from None
    key = (root, synset)
    hit = _CLASS_PATHS.get(key)
    if hit is not None and hit[0] == mtime:
        return hit[1], False
    # The stat came BEFORE this listing: a change that lands between the two
    # leaves the remembered mtime behind the directory's, and the next call
    # lists again.
    d = os.path.join(root, synset)
    path = Path(d, _first_file(d))
    if mtime < settled_before:
        _CLASS_PATHS[key] = (mtime, path)
    else:
        _CLASS_PATHS.pop(key, None)
    return path, True


def class_image_paths(data_dir: str | Path, synsets: Iterable[str]) -> tuple[list[Path], int]:
    """``[class_image_path(data_dir, s) for s in synsets]`` and the number
    of class directories that had to be listed from disk for it.

    A class directory's first file is remembered with the directory's
    ``st_mtime_ns`` and served again while that stands. Adding, removing or
    renaming an entry moves the directory's mtime, so the next call lists it
    again: the answer is ``class_image_path``'s at every call, and a missing
    or empty directory raises the same ``FileNotFoundError``. A call checks
    each distinct directory once, by one ``os.stat`` relative to ``data_dir``
    held open (on a network file system a stat by full path revalidates
    every component: 90 us against 30). Safe from any number of threads."""
    root = str(data_dir)
    settled_before = time.time_ns() - _MTIME_SETTLE_NS  # read before any stat below
    checked: dict[str, Path] = {}  # this call's answers
    paths: list[Path] = []
    misses = 0
    fd = os.open(root, os.O_RDONLY | os.O_DIRECTORY)
    try:
        for synset in synsets:
            path = checked.get(synset)
            if path is None:
                path, listed = _checked_class_path(root, fd, synset, settled_before)
                checked[synset] = path
                misses += listed
            paths.append(path)
    finally:
        os.close(fd)
    return paths, misses


def decode_resize(path: str | Path, size: int = 224) -> np.ndarray:
    """JPEG/PNG -> uint8 [size, size, 3] RGB, bilinear resize.

    Matches tch's load_image_and_resize semantics: direct resize to the target
    square (not resize-shortest-side + center-crop)."""
    from PIL import Image

    with Image.open(path) as im:
        im = im.convert("RGB")
        if im.size != (size, size):  # already-staged sizes skip the resample
            im = im.resize((size, size), Image.BILINEAR)
        return np.asarray(im, dtype=np.uint8)


@hot_path
def load_batch(
    paths: Sequence[str | Path],
    size: int = 224,
    workers: int | None = None,
    backend: str = "auto",
) -> np.ndarray:
    """Decode+resize a batch -> uint8 [N, size, size, 3] (fresh array).

    Thin wrapper over :func:`load_batch_into`; callers that run batches in a
    loop should preallocate the output once and use ``load_batch_into``
    directly so steady-state decode allocates nothing per batch.
    """
    out = np.empty((len(paths), size, size, 3), np.uint8)
    return load_batch_into(out, paths, size=size, workers=workers, backend=backend)


@hot_path
def load_batch_into(
    out: np.ndarray,
    paths: Sequence[str | Path],
    size: int = 224,
    workers: int | None = None,
    backend: str = "auto",
) -> np.ndarray:
    """Decode+resize a batch into the caller-owned arena ``out`` (returned).

    This is the stage that must keep up with the TPU (SURVEY.md §7 hard part
    b). ``out`` must be C-contiguous uint8 [len(paths), size, size, 3]; both
    the native and the PIL path fill it in place, so a caller that reuses one
    buffer per pipeline slot pays zero allocations per batch. ``workers`` is
    a concurrency hint — the cached pools (module-level here, persistent
    in-library for native) grow to the largest ever requested and are never
    rebuilt per call. ``backend``:

    - "native" — the C++ pipeline (dmlc_tpu.native): libjpeg with DCT-domain
      downscaling + a persistent thread-pooled triangle resample, GIL-free.
    - "pil" — PIL decode on the cached thread pool (decode releases the GIL).
    - "auto" — native when the library is built, else PIL. The two resize
      paths agree to within JPEG-noise tolerance (mean |diff| < 0.5/255 on
      the fixture corpus); a native decode failure falls back per-batch.
    """
    n = len(paths)
    shape = (n, size, size, 3)
    if (
        not isinstance(out, np.ndarray)
        or out.shape != shape
        or out.dtype != np.uint8
        or not out.flags["C_CONTIGUOUS"]
    ):
        raise ValueError(f"out must be a C-contiguous uint8 array of shape {shape}")
    if not n:
        return out
    if backend not in ("auto", "native", "pil"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend in ("auto", "native"):
        from dmlc_tpu import native

        if native.available():
            _, status = native.decode_resize_batch(
                paths, size, workers=workers or 0, out=out
            )
            if not status.any():
                return out
            if backend == "native":
                bad = [str(paths[i]) for i in np.nonzero(status)[0][:3]]
                raise ValueError(f"native decode failed for {bad}")
            # auto: a non-JPEG (e.g. PNG) snuck in — redo the batch via PIL.
        elif backend == "native":
            raise RuntimeError("native image pipeline not built")
    workers = workers or min(32, (os.cpu_count() or 8))
    if n == 1 or workers == 1:
        for i, p in enumerate(paths):
            out[i] = decode_resize(p, size)
        return out
    pool = _host_pool(workers)

    def fill(i: int) -> None:
        out[i] = decode_resize(paths[i], size)

    list(pool.map(fill, range(n)))  # list() re-raises worker exceptions
    return out


def decode_blob(data: bytes, size: int = 224) -> np.ndarray:
    """One encoded image's raw BYTES -> uint8 [size, size, 3] RGB. Same
    resize semantics as :func:`decode_resize`, but sourced from memory — the
    decode tier ships blobs over RPC, never paths (docs/INGEST.md §Decode
    tier). Raises on undecodable bytes; batch callers map that to a status
    slot instead of failing the batch."""
    from io import BytesIO

    from PIL import Image

    with Image.open(BytesIO(data)) as im:
        im = im.convert("RGB")
        if im.size != (size, size):
            im = im.resize((size, size), Image.BILINEAR)
        return np.asarray(im, dtype=np.uint8)


@hot_path
def decode_blobs(
    blobs: Sequence[bytes],
    size: int = 224,
    workers: int | None = None,
    backend: str = "auto",
) -> tuple[np.ndarray, np.ndarray]:
    """Decode a batch of raw encoded-image bytes (the decode tier's wire
    unit) -> ``(uint8 [N, size, size, 3], status uint8 [N])``.

    Per-blob failure is DATA, not an exception: a nonzero status slot marks
    an undecodable blob (its tensor rows are zeros) so the member's
    ``job.decode`` handler can answer with a typed ``DecodeError`` naming
    the poison indices while the caller keeps every good tensor it can
    still get locally. Backend selection mirrors :func:`load_batch_into`:
    the native path lands blobs in a throwaway tmpdir so the PERSISTENT
    C++ DecodePool (path-based ABI) does the GIL-free work; the PIL path
    decodes from memory on the cached host pool.
    """
    n = len(blobs)
    out = np.zeros((n, size, size, 3), np.uint8)
    status = np.zeros(n, np.uint8)
    if not n:
        return out, status
    if backend not in ("auto", "native", "pil"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend in ("auto", "native"):
        from dmlc_tpu import native

        if native.available():
            import tempfile

            with tempfile.TemporaryDirectory(prefix="dmlc-blobs-") as td:
                paths = []
                for i, b in enumerate(blobs):
                    p = Path(td) / f"{i}.img"
                    p.write_bytes(b)
                    paths.append(p)
                _, st = native.decode_resize_batch(
                    paths, size, workers=workers or 0, out=out
                )
            bad = np.nonzero(st)[0]
            if not bad.size:
                return out, status
            # Redo only the refused slots via PIL (a PNG snuck in, or the
            # blob really is poison — PIL gets the final word in "auto").
            for i in bad:
                try:
                    out[i] = decode_blob(blobs[i], size)
                except Exception:
                    out[i] = 0
                    status[i] = 1
            return out, status
        if backend == "native":
            raise RuntimeError("native image pipeline not built")

    def fill(i: int) -> None:
        try:
            out[i] = decode_blob(blobs[i], size)
        except Exception:
            out[i] = 0
            status[i] = 1

    workers = workers or min(32, (os.cpu_count() or 8))
    if n == 1 or workers == 1:
        for i in range(n):
            fill(i)
        return out, status
    pool = _host_pool(workers)
    list(pool.map(fill, range(n)))
    return out, status


# Device-resident normalization constants, keyed by value: jnp.asarray on a
# host constant is an upload (and a tracer-cache miss) — the standalone
# normalize path was re-staging mean/std on EVERY call. The cache holds a
# handful of 3-float arrays, so unbounded-by-key is bounded in practice.
_DEVICE_CONSTS: dict[tuple, "jnp.ndarray"] = {}


def _device_const(arr: np.ndarray):
    arr = np.asarray(arr, np.float32)
    key = (arr.tobytes(), arr.shape)
    cached = _DEVICE_CONSTS.get(key)
    if cached is None:
        cached = _DEVICE_CONSTS[key] = jnp.asarray(arr)
    return cached


def normalize(batch_u8, mean: np.ndarray = IMAGENET_MEAN, std: np.ndarray = IMAGENET_STD):
    """Device-side: uint8 NHWC -> normalized float32 NHWC. Under jit, XLA fuses
    this into the consumer; the Pallas variant exists for the standalone path.
    mean/std ride the device-constant cache, so repeated standalone calls
    re-upload nothing."""
    x = jnp.asarray(batch_u8).astype(jnp.float32) / 255.0
    return (x - _device_const(mean)) / _device_const(std)


def stats_for_model(model_name: str) -> tuple[np.ndarray, np.ndarray]:
    """Host-side (numpy) normalization stats — always the same module-level
    constant objects, never rebuilt, so callers may key caches on identity."""
    if model_name.startswith("clip"):
        return CLIP_MEAN, CLIP_STD
    return IMAGENET_MEAN, IMAGENET_STD


def device_stats_for_model(model_name: str):
    """Device-resident (jnp) normalization stats, cached across calls."""
    mean, std = stats_for_model(model_name)
    return _device_const(mean), _device_const(std)
