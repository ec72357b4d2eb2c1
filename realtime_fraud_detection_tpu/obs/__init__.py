"""Observability plane: metrics, structured logs, profiling, drift detection.

TPU-native replacement for the reference's L5 (SURVEY.md §5.1/§5.5): the
Prometheus registry in metrics.py:62-124, the dictConfig logging in
logging_config.py:11-93, coarse timing (ensemble_predictor.py:185-215), and
the configured-but-unimplemented drift detection (config.py:110-116).
"""

from realtime_fraud_detection_tpu.obs.drift import (
    DriftConfig,
    DriftReport,
    FeatureDriftMonitor,
)
from realtime_fraud_detection_tpu.obs.logs import (
    JsonFormatter,
    log_batch_scored,
    log_model_event,
    log_prediction_result,
    setup_logging,
)
from realtime_fraud_detection_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsCollector,
    Registry,
)
from realtime_fraud_detection_tpu.obs.profiling import (
    GcSpans,
    SpanTimer,
)
from realtime_fraud_detection_tpu.obs.tracing import (
    SloTracker,
    TraceBatch,
    TraceContext,
    Tracer,
)

__all__ = [
    "Counter",
    "DriftConfig",
    "DriftReport",
    "FeatureDriftMonitor",
    "Gauge",
    "GcSpans",
    "Histogram",
    "JsonFormatter",
    "MetricsCollector",
    "Registry",
    "SloTracker",
    "SpanTimer",
    "TraceBatch",
    "TraceContext",
    "Tracer",
    "log_batch_scored",
    "log_model_event",
    "log_prediction_result",
    "setup_logging",
]
