from repro.serve.engine import ServeConfig, ServeEngine, SlotServer
from repro.serve.fleet_frontend import FleetFrontend
from repro.serve.service import (
    AdmissionError, DispatchError, ImageJob, ImageService, JobHandle,
    JobTimeout, LatencyStats, PlanBuildError, QuarantinedError,
    ServiceError,
)
from repro.serve.streaming import StreamingFrontend

__all__ = [
    "ServeConfig", "ServeEngine", "SlotServer",
    "FleetFrontend", "StreamingFrontend",
    "ImageService", "ImageJob", "JobHandle",
    "LatencyStats", "AdmissionError",
    "ServiceError", "DispatchError", "QuarantinedError", "JobTimeout",
    "PlanBuildError",
]
