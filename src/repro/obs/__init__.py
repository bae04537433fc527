"""Observability: metrics registry, trace spans, and the GEMM ledger.

Import-light by design — ``repro.obs`` pulls in nothing beyond stdlib at
import time (jax, the tuning registry, and the program grammar are
deferred to the call sites that need them), so hot paths can hook in
unconditionally.
"""

from repro.obs.ledger import (GemmLedger, GemmRecord, enable_ledger,
                              get_ledger, planned_gemm_bytes, reset_ledger,
                              set_ledger)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               get_metrics, reset_metrics, set_metrics)
from repro.obs.trace import (DEFAULT_TRACE_PATH, disable_tracing,
                             enable_tracing, flush, kernel_scope, read_trace,
                             span, tracing_enabled)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "get_metrics", "set_metrics", "reset_metrics",
    "DEFAULT_TRACE_PATH", "span", "kernel_scope", "enable_tracing",
    "disable_tracing", "tracing_enabled", "flush",
    "read_trace",
    "GemmLedger", "GemmRecord", "get_ledger", "set_ledger",
    "enable_ledger", "reset_ledger", "planned_gemm_bytes",
]
