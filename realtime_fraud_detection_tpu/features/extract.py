"""The 64-feature contract, vectorized for TPU.

Reimplements ``FeatureExtractor.extractAllFeatures``
(reference FeatureExtractor.java:50-87) as a single jittable function
``TransactionBatch -> f32[B, 64]``. The canonical ordering below is this
framework's contract (the reference stores features in a Java HashMap whose
iteration order is unspecified — the 64-wide vector the serving side builds,
ensemble_predictor.py:221-250, was therefore never deterministic; we fix
that defect by pinning the order).

Null semantics: where the reference omits a key (profile missing, no
geolocation, ...), the dense vector holds the documented default — 0.0 for
everything except ``within_merchant_hours`` (default 1.0: "no operating-hours
info" must not look like "outside operating hours") and the unknown-profile
defaults applied at encode time (schema.py).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from realtime_fraud_detection_tpu.features.schema import TransactionBatch
from realtime_fraud_detection_tpu.obs.profiling import compile_ledger

# the host feature program is the first an entry point compiles: the
# process's compile ledger listens from here (scoring/pipeline.py says the
# same for the device programs)
compile_ledger()

# Canonical feature ordering — 8 categories, 64 names, matching the union of
# FeatureExtractor.java:92-382 emissions (amount 12, temporal 8, geographic 8,
# user 10, merchant 8, device/network 5, velocity 8, contextual 5).
FEATURE_NAMES: tuple[str, ...] = (
    # amount (12) — FeatureExtractor.java:92-131
    "amount", "amount_log", "amount_sqrt", "is_round_amount", "is_round_10",
    "is_round_100", "amount_to_user_avg_ratio", "amount_deviation_zscore",
    "is_large_for_user", "amount_to_merchant_avg_ratio", "is_large_for_merchant",
    "amount_category",
    # temporal (8) — :136-168
    "hour_of_day", "day_of_week", "day_of_month", "is_weekend", "time_period",
    "is_business_hours", "is_night_time", "in_user_preferred_time",
    # geographic (8) — :173-211
    "has_geolocation", "has_merchant_location", "latitude", "longitude",
    "is_high_risk_country", "distance_to_merchant_km", "user_intl_preference",
    "unexpected_intl_transaction",
    # user behavior (10) — :216-252
    "account_age_days", "is_new_account", "is_very_new_account",
    "user_risk_score", "is_kyc_verified", "kyc_status",
    "weekend_activity_factor", "online_preference", "user_avg_amount",
    "user_transaction_frequency",
    # merchant risk (8) — :257-296
    "merchant_risk_level", "merchant_fraud_rate", "is_blacklisted_merchant",
    "merchant_category", "is_high_risk_category", "within_merchant_hours",
    "merchant_risk_multiplier", "suspicious_merchant_name",
    # device / network (5) — :301-325
    "is_known_device", "is_new_device", "is_private_ip", "ip_risk_score",
    "suspicious_user_agent",
    # velocity (8) — :330-363
    "velocity_5min_count", "velocity_5min_amount", "velocity_1hour_count",
    "velocity_1hour_amount", "velocity_24hour_count", "velocity_24hour_amount",
    "high_velocity_5min", "high_velocity_1hour",
    # contextual (5) — :368-382
    "payment_method", "is_high_risk_payment", "transaction_type", "is_refund",
    "card_type",
)
NUM_FEATURES = len(FEATURE_NAMES)
assert NUM_FEATURES == 64

_INDEX = {name: i for i, name in enumerate(FEATURE_NAMES)}


def feature_index(name: str) -> int:
    return _INDEX[name]


def _haversine_km(lat1, lon1, lat2, lon2):
    """Haversine distance (FeatureExtractor.java:407-417)."""
    rad = jnp.pi / 180.0
    dlat = (lat2 - lat1) * rad
    dlon = (lon2 - lon1) * rad
    a = (
        jnp.sin(dlat / 2) ** 2
        + jnp.cos(lat1 * rad) * jnp.cos(lat2 * rad) * jnp.sin(dlon / 2) ** 2
    )
    return 6371.0 * 2.0 * jnp.arctan2(jnp.sqrt(a), jnp.sqrt(1.0 - a))


def top_feature_importances(importances, k: int = 10):
    """Top-k {feature name: score} from a per-feature importance vector.

    The reference surfaces this in prediction explanations
    (ensemble_predictor.py:371-435). Length must match the 64-name
    contract — a trainer fit on a different feature matrix must not get
    its indices silently mislabeled with canonical names.
    """
    import numpy as np

    arr = np.asarray(importances, np.float32)
    if arr.shape != (len(FEATURE_NAMES),):
        raise ValueError(
            f"importances shape {arr.shape} != ({len(FEATURE_NAMES)},) — "
            "not the canonical feature contract")
    order = np.argsort(arr)[::-1][:k]
    return {FEATURE_NAMES[i]: round(float(arr[i]), 6)
            for i in order if arr[i] > 0}


@functools.lru_cache(maxsize=None)
def host_cpu_device():
    """The host CPU device ``extract_features_host`` runs on.

    The process must have JAX's CPU backend initialised next to the
    accelerator: a ``JAX_PLATFORMS`` that lists only the accelerator leaves
    it out. ``FraudScorer`` calls this at construction so a missing backend
    fails at start-up with this message, not as all-ERROR results inside
    the stream job's degradation path.
    """
    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "feature extraction runs on JAX's host CPU backend, which this "
            f"process did not initialise (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); list the CPU next to "
            "the accelerator, e.g. JAX_PLATFORMS=tpu,cpu") from e


def extract_features_host(b: TransactionBatch):
    """``extract_features`` pinned to the host CPU backend. Returns f32[B, 64]
    as a NumPy array.

    The streaming assembler needs the feature rows host-side anyway (history
    store, feature-topic fan-out), so the same jitted program runs on the
    CPU backend and the hot loop has no blocking device round trip; the
    device program still consumes the rows as part of the packed ScoreBatch
    transfer. What the round trip would cost on local hardware is not
    measured.
    """
    import numpy as np

    with jax.default_device(host_cpu_device()):
        return np.asarray(extract_features(b))


@jax.jit
def extract_features(b: TransactionBatch) -> jax.Array:
    """Vectorized 64-feature extraction. Returns f32[B, 64]."""
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    amount = f32(b.amount)
    hour = b.hour_of_day

    # --- amount (12)
    cents = jnp.round(amount * 100.0).astype(jnp.int32)
    has_user_avg = b.has_user & (b.user_avg_amount > 0)
    user_ratio = jnp.where(has_user_avg, amount / jnp.maximum(b.user_avg_amount, 1e-9), 0.0)
    user_z = jnp.where(
        has_user_avg, (amount - b.user_avg_amount) / jnp.maximum(b.user_avg_amount, 1e-9), 0.0
    )
    has_merch_avg = b.has_merchant & (b.merchant_avg_amount > 0)
    merch_ratio = jnp.where(
        has_merch_avg, amount / jnp.maximum(b.merchant_avg_amount, 1e-9), 0.0
    )
    amount_category = (
        (amount >= 10).astype(jnp.int32)
        + (amount >= 100)
        + (amount >= 1000)
        + (amount >= 10000)
    )

    # --- temporal (8); time_period: morning 0 / afternoon 1 / evening 2 / night 3
    time_period = jnp.where(
        (hour >= 6) & (hour < 12), 0,
        jnp.where((hour >= 12) & (hour < 18), 1, jnp.where((hour >= 18) & (hour < 22), 2, 3)),
    )
    in_preferred = (
        b.has_user & b.has_preferred_hours
        & (hour >= b.preferred_start) & (hour <= b.preferred_end)
    )

    # --- geographic (8)
    high_risk_loc = b.has_geo & (
        (jnp.abs(b.lat) > 60) | ((jnp.abs(b.lat) < 10) & (jnp.abs(b.lon) < 10))
    )
    both_geo = b.has_geo & b.has_merchant_geo
    dist = jnp.where(
        both_geo, _haversine_km(b.lat, b.lon, b.merchant_lat, b.merchant_lon), 0.0
    )
    intl_pref = jnp.where(b.has_user & b.has_intl_ratio, b.intl_ratio, 0.0)
    unexpected_intl = b.has_user & b.has_intl_ratio & (b.intl_ratio < 0.1)

    # --- user (10); unknown users: is_new/is_very_new true, risk 0.8 set at
    # encode (FeatureExtractor.java:244-251)
    is_new_account = jnp.where(b.has_user, b.account_age_days < 30, True)
    is_very_new = jnp.where(b.has_user, b.account_age_days < 7, True)

    # --- merchant (8)
    within_hours = jnp.where(
        b.has_merchant & b.has_op_hours,
        (hour >= b.merchant_op_start) & (hour <= b.merchant_op_end),
        True,
    )
    risk_mult = jnp.where(
        b.has_merchant & (b.merchant_risk_code == 0), 1.0,
        jnp.where(b.has_merchant & (b.merchant_risk_code == 1), 1.5, 2.0),
    )

    # --- velocity flags (FeatureExtractor.java:353-354)
    high_vel_5m = b.velocity_5min_count > 5
    high_vel_1h = b.velocity_1hour_count > 20

    cols = [
        # amount
        amount,
        jnp.log1p(jnp.maximum(amount, 0.0)),
        jnp.sqrt(jnp.maximum(amount, 0.0)),
        f32(cents % 100 == 0),
        f32(cents % 1000 == 0),
        f32(cents % 10000 == 0),
        user_ratio,
        user_z,
        f32(has_user_avg & (user_ratio > 3.0)),
        merch_ratio,
        f32(has_merch_avg & (amount > b.merchant_avg_amount * 2.0)),
        f32(amount_category),
        # temporal
        f32(hour),
        f32(b.day_of_week),
        f32(b.day_of_month),
        f32(b.is_weekend),
        f32(time_period),
        f32((hour >= 9) & (hour <= 17)),
        f32((hour <= 6) | (hour >= 22)),
        f32(in_preferred),
        # geographic
        f32(b.has_geo),
        f32(b.has_merchant_geo),
        jnp.where(b.has_geo, b.lat, 0.0),
        jnp.where(b.has_geo, b.lon, 0.0),
        f32(high_risk_loc),
        dist,
        intl_pref,
        f32(unexpected_intl),
        # user
        f32(b.account_age_days),
        f32(is_new_account),
        f32(is_very_new),
        f32(b.user_risk_score),
        f32(b.has_user & b.user_verified),
        f32(b.kyc_code),
        f32(b.weekend_activity),
        f32(b.online_preference),
        f32(b.user_avg_amount),
        f32(b.user_txn_frequency),
        # merchant
        f32(b.merchant_risk_code),
        f32(b.merchant_fraud_rate),
        f32(b.merchant_blacklisted),
        f32(b.merchant_category_code),
        f32(b.merchant_high_risk_category),
        f32(within_hours),
        f32(risk_mult),
        f32(b.suspicious_merchant_name),
        # device / network
        f32(b.known_device),
        f32(~b.known_device),
        f32(b.private_ip),
        f32(b.ip_risk),
        f32(b.suspicious_user_agent),
        # velocity
        f32(b.velocity_5min_count),
        f32(b.velocity_5min_amount),
        f32(b.velocity_1hour_count),
        f32(b.velocity_1hour_amount),
        f32(b.velocity_24hour_count),
        f32(b.velocity_24hour_amount),
        f32(high_vel_5m),
        f32(high_vel_1h),
        # contextual
        f32(b.payment_method_code),
        f32(b.high_risk_payment),
        f32(b.transaction_type_code),
        f32(b.transaction_type_code == 1),  # refund (TRANSACTION_TYPES[1])
        f32(b.card_type_code),
    ]
    return jnp.stack(cols, axis=-1)
