# Packed-weight serving: offline prequantization to M2XFP streams, a
# continuous-batching slot scheduler, and the batched decode engine
# (paper Sec. 5 deployment path — weights stay 4.5 bits/elem in HBM).
# Fault tolerance (guard): poison sentinels, quarantine, deadlines,
# backpressure — see docs/robustness.md.
from .engine import ServeEngine, ServeStats, tree_nbytes  # noqa: F401
from .guard import (  # noqa: F401
    DEGRADED, FAILED, HEALTHY, EngineFailedError, EngineGuard, GuardConfig,
    StreamIntegrityError, TransientStepError, verify_packed_tree,
)
from .prequant import (  # noqa: F401
    init_packed_params, load_packed_checkpoint, packed_template,
    prequantize_checkpoint, prequantize_params, save_packed_checkpoint,
)
from .scheduler import (  # noqa: F401
    AdmissionError, Request, SlotScheduler,
)

__all__ = [
    "AdmissionError", "DEGRADED", "EngineFailedError", "EngineGuard",
    "FAILED", "GuardConfig", "HEALTHY", "Request", "ServeEngine",
    "ServeStats", "SlotScheduler", "StreamIntegrityError",
    "TransientStepError", "init_packed_params", "load_packed_checkpoint",
    "packed_template", "prequantize_checkpoint", "prequantize_params",
    "save_packed_checkpoint", "tree_nbytes", "verify_packed_tree",
]
