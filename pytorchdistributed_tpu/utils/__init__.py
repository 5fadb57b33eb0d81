from pytorchdistributed_tpu.utils.metrics import (  # noqa: F401
    StepTimer,
    ThroughputMeter,
    scaling_efficiency,
)
from pytorchdistributed_tpu.utils.guards import (  # noqa: F401
    NaNWatchdog,
    assert_finite,
    assert_replicas_consistent,
)
