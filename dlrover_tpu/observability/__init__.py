from dlrover_tpu.observability.events import (  # noqa: F401
    EventLogger,
    TimelineAggregator,
    compute_ledger,
    export_chrome_trace,
    get_event_logger,
    read_events,
)
from dlrover_tpu.observability.metrics import (  # noqa: F401
    MetricsExporter,
    MetricsRegistry,
)
from dlrover_tpu.observability.health import HealthEngine  # noqa: F401
from dlrover_tpu.observability.status_server import (  # noqa: F401
    StatusServer,
)
