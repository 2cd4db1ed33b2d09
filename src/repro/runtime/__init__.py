from repro.runtime.chaos import FaultInjector, FaultSpec, InjectedFault
from repro.runtime.fault_tolerance import (
    ElasticPlan, HeartbeatMonitor, RunState, resume_or_init,
)
from repro.runtime.fleet import FleetRequest, FleetStats, LRUCache, PixieFleet
from repro.runtime.resilience import (
    BreakerBoard, CircuitBreaker, DispatchError, JobTimeout,
    PlanBuildError, PoisonedOutputError, QuarantinedError, RetryPolicy,
    ServiceError, TransientError,
)

__all__ = [
    "ElasticPlan", "HeartbeatMonitor", "RunState", "resume_or_init",
    "FleetRequest", "FleetStats", "LRUCache", "PixieFleet",
    "FaultInjector", "FaultSpec", "InjectedFault",
    "BreakerBoard", "CircuitBreaker", "RetryPolicy",
    "ServiceError", "DispatchError", "QuarantinedError", "JobTimeout",
    "PlanBuildError", "PoisonedOutputError", "TransientError",
]
