"""Test harness: force an 8-device virtual CPU platform BEFORE jax import.

Multi-chip hardware is not available in CI; all sharding/collective tests run
on a virtual 8-device CPU mesh (jax's xla_force_host_platform_device_count),
which exercises the same pjit/shard_map partitioning logic the TPU pod path
uses. Real-TPU execution is covered by chip_smoke.py, run on the chip.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Make the repo root importable regardless of how pytest was invoked.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# Tests run hermetically on the virtual CPU mesh whatever the caller's
# environment selected (a TPU host exports JAX_PLATFORMS=tpu,cpu, which the
# setdefault above leaves alone), so select cpu at the config level too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite's dominant cost is XLA compiles,
# and a warm cache cuts reruns from minutes to seconds.
from dmlc_tpu.utils import compile_cache  # noqa: E402

compile_cache.enable()

# Build the native data-plane library once (best effort) so its tests run
# against the real .so; the library is a gitignored build artifact.
try:
    from dmlc_tpu import native as _native  # noqa: E402

    _native.ensure_built()
except Exception:  # dmlc-lint: disable=E1 -- best-effort: tests that need the .so skip on native.available(), everything else must still collect
    pass
