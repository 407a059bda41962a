from .ann_server import AnnServer, ServeStats  # noqa: F401
from .resilience import (  # noqa: F401
    CircuitBreaker,
    DegradationLadder,
    ResilienceConfig,
    ResilientAnnServer,
    Response,
    ShardedResilientAnnServer,
    TierCompileError,
    validate_query,
)
