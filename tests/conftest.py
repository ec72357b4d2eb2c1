"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Multi-chip TPU hardware is unavailable in CI; sharding code is validated on
XLA's host platform with 8 virtual devices (the same path the driver's
``dryrun_multichip`` uses). Must run before any ``import jax`` resolves a
backend.
"""

import os

# Force CPU even when the session env points JAX at an accelerator: tests
# must be hermetic and multi-device.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache, shared between the pytest process and
# every drill-CLI subprocess the smokes spawn (they recompile the same
# scorer programs from scratch otherwise — the cache is content-addressed
# over HLO + compile options, so code changes miss safely). Placed by the
# one helper every entry point uses (an outside JAX_COMPILATION_CACHE_DIR
# wins, else <checkout>/.jax_cache) and exported via env so subprocesses
# inherit; min-compile-time 0 because the suite is dominated by many
# sub-second compiles, not a few large ones.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
# The entry points key the cache on each program's metadata too
# (utils/compile_cache.py): right where a profile is read by scope name,
# costly here, where the xdist workers and the drill subprocesses reach the
# same program from different call stacks and should share one entry. No
# test reads a cached program's metadata, so keep JAX's default.
os.environ.setdefault("JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY", "0")

from realtime_fraud_detection_tpu.utils.compile_cache import (  # noqa: E402
    configure_compile_cache,
)

configure_compile_cache()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def mesh8():
    from realtime_fraud_detection_tpu.core import build_mesh

    return build_mesh()


@pytest.fixture
def rng():
    return np.random.default_rng(0)
