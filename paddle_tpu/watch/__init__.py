"""paddle_tpu.watch: the analysis layer over the telemetry stack.

``observability`` + ``tracing`` collect; ``watch`` interprets:

- :mod:`~paddle_tpu.watch.detectors` — shared online anomaly detector
  cores (EWMA z-score, rolling quantile, spatial/temporal skew);
- :mod:`~paddle_tpu.watch.alerts` — structured alert fan-out (runlog,
  ``watch.alert.*`` metrics, warn-once, ``/alerts``, actions);
- :mod:`~paddle_tpu.watch.slo` — declarative SLOs with multi-window
  burn rates and error budgets, served at ``/slo``;
- :mod:`~paddle_tpu.watch.watcher` — registry-subscription glue binding
  detectors and SLO engines to live metric streams.
"""

from paddle_tpu.watch.alerts import (  # noqa: F401
    Alert,
    AlertHub,
    CRITICAL,
    WARNING,
    default_hub,
)
from paddle_tpu.watch.detectors import (  # noqa: F401
    DetectorResult,
    EwmaDetector,
    RollingQuantileDetector,
    SkewDetector,
)
from paddle_tpu.watch.slo import (  # noqa: F401
    SLO,
    SloEngine,
    disagg_slos,
    install,
    installed_engines,
    serving_slos,
    uninstall,
)
from paddle_tpu.watch.watcher import (  # noqa: F401
    MetricWatcher,
    WatchConfig,
    WatchRule,
    build,
    default_rules,
)

__all__ = [
    "Alert",
    "AlertHub",
    "WARNING",
    "CRITICAL",
    "default_hub",
    "DetectorResult",
    "EwmaDetector",
    "RollingQuantileDetector",
    "SkewDetector",
    "SLO",
    "SloEngine",
    "disagg_slos",
    "install",
    "installed_engines",
    "serving_slos",
    "uninstall",
    "MetricWatcher",
    "WatchConfig",
    "WatchRule",
    "build",
    "default_rules",
]
