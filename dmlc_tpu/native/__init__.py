"""ctypes bindings for the native (C++) data-plane library.

``decode_resize_batch`` is the high-throughput replacement for the PIL path
in ops/preprocess.py — libjpeg DCT-domain downscaling + thread-pooled
triangle resampling (PIL BILINEAR semantics), one call per shard. The
library builds from native/ via make; on a host with no toolchain the
callers fall back to PIL, and ``available()`` (surfaced as ``node.info``'s
``decode_backend``) says which path is serving.

The Makefile compiles with ``-march=native`` and the working tree travels
between machines with its ignored files, so a library is only ever loaded
when its sidecar stamp says it was built on THIS host's CPU from THESE
sources (``_stamp``). File mtimes survive a copy and prove neither.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import logging
import subprocess
from pathlib import Path

import numpy as np

log = logging.getLogger(__name__)

_LIB_PATH = Path(__file__).parent / "libdmlc_native.so"
_STAMP_PATH = _LIB_PATH.with_name(_LIB_PATH.name + ".stamp")
_SRC_DIR = Path(__file__).parent.parent.parent / "native"
# v2: persistent decode pool (dmlc_pool_size/dmlc_pool_shutdown) replacing
# the spawn-and-join-per-call threading of v1.
_ABI_VERSION = 2

_lib = None
_load_failed = False


def _load():
    """Bind to an ALREADY-BUILT library. Never compiles: _load sits on the
    serving hot path (load_batch -> available()), and a surprise g++ run
    there would stall the first inference shard. Compilation happens only
    through ensure_built()/build(), called from node startup and bench."""
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    if _stale():
        return None
    try:
        lib = ctypes.CDLL(str(_LIB_PATH))
        if lib.dmlc_native_abi_version() != _ABI_VERSION:
            log.warning("native library ABI mismatch; rebuild with native.build()")
            return None
        lib.dmlc_decode_resize_batch.restype = ctypes.c_int
        lib.dmlc_decode_resize_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int),
            ctypes.c_int,
        ]
        lib.dmlc_pool_size.restype = ctypes.c_int
        lib.dmlc_pool_size.argtypes = []
        lib.dmlc_pool_shutdown.restype = None
        lib.dmlc_pool_shutdown.argtypes = []
        _lib = lib
    except Exception as e:
        log.warning("native image pipeline unavailable (%s); using PIL", e)
        _load_failed = True
    return _lib


@functools.lru_cache(maxsize=None)
def _stamp() -> str:
    """What a library built here, now, is stamped with: a digest of the
    sources that go into it and of this host's CPU code-generation surface
    (the Makefile's ``-march=native`` target). Computed once per process:
    ``_load`` asks on the serving path while PIL is serving."""
    from dmlc_tpu.utils.compile_cache import machine_fingerprint

    h = hashlib.sha256(machine_fingerprint().encode())
    for src in (_SRC_DIR / "image_pipeline.cpp", _SRC_DIR / "Makefile"):
        h.update(src.read_bytes())
    return h.hexdigest()


def _stale() -> bool:
    """Is the .so missing, built from other sources, or built for another
    CPU? A prebuilt library whose stamp matches never spawns make (and
    fresh libraries are never needlessly re-linked under a
    concurrently-starting fleet)."""
    try:
        return not _LIB_PATH.exists() or _STAMP_PATH.read_text() != _stamp()
    except OSError:
        return True


def build() -> None:
    """Compile the library (g++ via make) and stamp it. Raises on failure.
    ``-B``: make's own mtime comparison is exactly what a copied tree
    fools."""
    global _lib, _load_failed
    _STAMP_PATH.unlink(missing_ok=True)
    subprocess.run(
        ["make", "-s", "-B"], cwd=_SRC_DIR, check=True, capture_output=True, text=True
    )
    _STAMP_PATH.write_text(_stamp())
    _lib, _load_failed = None, False  # rebind on next use


def ensure_built() -> bool:
    """Build on this host if the library is missing or stale and report
    availability. A failed build (no toolchain) leaves PIL serving — loudly;
    callers that require native check the return value. Call at node
    startup / bench setup — never from the per-shard path."""
    if not _load_failed and _stale():
        try:
            build()
        except (OSError, subprocess.CalledProcessError) as e:
            detail = getattr(e, "stderr", "") or e
            log.warning("native build failed (%s); decode serves through PIL", detail)
    return available()


def available() -> bool:
    return _load() is not None


def decode_resize_batch(
    paths,
    size: int = 224,
    workers: int = 0,
    out: np.ndarray | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Decode+resize JPEGs -> (uint8 [N, size, size, 3], status int32 [N]).

    ``out``, when given, is a caller-owned reusable arena the batch decodes
    into (C-contiguous uint8 [N, size, size, 3]) — repeated batches then
    allocate nothing per call; None allocates fresh. status[i] != 0 marks a
    failed decode (that slot is zeros). ``workers`` sizes the library's
    persistent worker pool (grow-only; 0 = hardware concurrency). Raises
    RuntimeError if the native library is unavailable — callers that want
    the automatic PIL fallback go through ops.preprocess.load_batch.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native image pipeline not available")
    n = len(paths)
    shape = (n, size, size, 3)
    if out is None:
        out = np.empty(shape, np.uint8)
    elif (
        not isinstance(out, np.ndarray)
        or out.shape != shape
        or out.dtype != np.uint8
        or not out.flags["C_CONTIGUOUS"]
    ):
        raise ValueError(f"out must be a C-contiguous uint8 array of shape {shape}")
    status = np.zeros(n, np.int32)
    if n == 0:
        return out, status
    c_paths = (ctypes.c_char_p * n)(*[str(p).encode() for p in paths])
    lib.dmlc_decode_resize_batch(
        c_paths,
        n,
        size,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        status.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        int(workers),
    )
    return out, status


def pool_size() -> int:
    """Worker count of the library's persistent decode pool (0 before the
    first batch or when the library is absent)."""
    lib = _load()
    return int(lib.dmlc_pool_size()) if lib is not None else 0


def pool_shutdown() -> None:
    """Join the persistent pool's workers (no-op without the library).
    Restartable: the next decode call re-grows the pool."""
    lib = _load()
    if lib is not None:
        lib.dmlc_pool_shutdown()
