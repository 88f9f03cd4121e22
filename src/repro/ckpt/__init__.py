from repro.core.engine import (
    CheckpointEngine,
    FragmentIndex,
    HandleCache,
    default_engine,
)
from .manager import CheckpointManager, RestoreInfo
from .policy import CheckpointPolicy
from .restore import (
    build_param_arrays,
    params_from_source,
    read_region_from_dist,
    read_region_from_source,
    state_from_dist,
    state_from_source,
    state_from_stream,
    state_from_ucp,
)
from .saver import AsyncSaver, SaveResult, snapshot_state, write_distributed
__all__ = [
    "CheckpointEngine", "FragmentIndex", "HandleCache", "default_engine",
    "CheckpointManager", "CheckpointPolicy", "RestoreInfo", "build_param_arrays",
    "params_from_source", "read_region_from_dist",
    "read_region_from_source", "state_from_dist", "state_from_source",
    "state_from_stream", "state_from_ucp", "AsyncSaver", "SaveResult",
    "snapshot_state", "write_distributed",
]
