from .compile_cache import enable_compile_cache
from .fault_tolerance import (ElasticPlan, ElasticScaler, HeartbeatMonitor,
                              StragglerDetector, run_with_restarts)

__all__ = ["enable_compile_cache", "ElasticPlan", "ElasticScaler",
           "HeartbeatMonitor", "StragglerDetector", "run_with_restarts"]
