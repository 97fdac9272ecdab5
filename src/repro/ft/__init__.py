from repro.ft.failures import (  # noqa: F401
    HeartbeatMonitor,
    ElasticPlan,
    plan_elastic_remesh,
)
from repro.ft.faults import (  # noqa: F401
    CORRUPT,
    FAULT_SITES,
    FaultInjector,
    FaultSpec,
    parse_fault_spec,
)
from repro.ft.recovery import (  # noqa: F401
    CircuitBreaker,
    RetryPolicy,
)

