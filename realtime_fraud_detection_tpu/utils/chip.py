"""The no-fallback rule of the measurement entry points, in one place."""

from __future__ import annotations


def require_tpu(who: str):
    """Return ``jax.devices()[0]`` if it is a TPU; otherwise exit non-zero
    naming the platform JAX found. ``chip_smoke.py`` calls this before
    anything else: a proof from it is about the chip, so a process that
    finds no chip stops — it never carries on elsewhere."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"{who} needs a TPU and JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); there is no CPU fallback")
    return dev
