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


@pytest.fixture(scope="session")
def experts_through_both_forms():
    """``check(layer, top_k=, rung=, atol=, ...)``: one sparse layer's
    ``models.olmoe.apply_experts`` with its kernels asked for (Pallas,
    interpreted: the fused gate + up + SiLU kernel, down's, the combine)
    (or, of a layer without a gate, ``relu2_gmm``)
    against its XLA form, at one of the two capacities of a launch of 4,096
    slots (``text_split.capacities``: rung 0 three quarters, 1 every slot) on
    random rows of which the first 2,800 are real (a rung's ``token_slots``),
    each with ``top_k`` distinct experts of the router's width. The rows the
    kernels never wrote (interpret mode leaves them NaN) must reach nothing.
    Returns the group sizes."""
    import jax.numpy as jnp

    from realtime_fraud_detection_tpu.models.olmoe import apply_experts
    from realtime_fraud_detection_tpu.ops import (
        combine_supported,
        grouped_matmul_supported,
    )
    from realtime_fraud_detection_tpu.ops.grouped_matmul import gmm_tiling
    from realtime_fraud_detection_tpu.scoring.text_split import capacities

    real_tokens = 2800

    def check(layer, *, top_k, rung, atol, router_width=None,
              expert_offset=0):
        slots = capacities(4096)[rung]
        held, hidden, wide = layer["up_proj"].shape
        assert grouped_matmul_supported(slots * top_k, hidden, wide)
        assert combine_supported(slots, top_k, hidden)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((slots, hidden)), jnp.float32)
        experts = jnp.asarray(np.argsort(rng.random(
            (slots, router_width or held)), axis=-1)[:, :top_k], jnp.int32)
        weights = jnp.asarray(rng.random((slots, top_k)), jnp.float32)
        real = jnp.arange(slots) < real_tokens
        share = dict(router_width=router_width, expert_offset=expert_offset)
        want, (sizes, no_tiles) = apply_experts(
            layer, x, experts, weights, real=real, **share)
        got, (sizes_k, tile_rows) = apply_experts(
            layer, x, experts, weights, real=real, use_pallas=True,
            kernel_interpret=True, **share)
        np.testing.assert_array_equal(sizes, sizes_k)
        # the XLA form visits no tile; the kernel's grid whole row tiles,
        # at least the groups' rows and under a tile more a group
        tm = gmm_tiling(slots * top_k, hidden, wide, held, gated=True,
                        matrices=1 + ("gate_proj" in layer))[0]
        assert int(no_tiles) == 0 and int(tile_rows) % tm == 0
        assert 0 <= int(tile_rows) - int(sizes.sum()) < (
            2 * tm * np.count_nonzero(sizes))
        assert int(sizes.sum()) <= real_tokens * top_k < slots * top_k
        assert np.isfinite(np.asarray(got)).all()
        assert not np.asarray(got)[real_tokens:].any()
        scale = float(np.abs(np.asarray(want)).max())
        assert scale > 0
        np.testing.assert_allclose(got, want, atol=atol * scale, rtol=0)
        return np.asarray(sizes)

    return check
