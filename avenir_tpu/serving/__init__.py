"""ServeGraft — the device-resident online scoring plane.

Layers (docs/architecture.md "Serving"): a :class:`ModelRegistry` loads any
trained artifact the batch jobs produce and holds its parameters device-
resident; a :class:`BucketedMicrobatcher` folds concurrent requests into
pre-compiled padded batch buckets (zero steady-state recompiles); HTTP and
RESP-list front ends expose it; ``ScoringPlane`` replays files through it
as a pipeline stage.
"""

from avenir_tpu.serving.batcher import (
    BucketedMicrobatcher,
    PendingBlock,
    PendingRequest,
)
from avenir_tpu.serving.errors import (
    ReplicaDownError,
    RequestError,
    RequestTimeout,
    ServingError,
    ShedError,
    UnknownModelError,
)
from avenir_tpu.serving.frontend import (
    QueueScoreFrontend,
    ScoreHTTPServer,
    redis_score_frontend,
)
from avenir_tpu.serving.pool import PoolRequest, ReplicaPool
from avenir_tpu.serving.registry import FAMILIES, ModelRegistry, ServableModel
from avenir_tpu.serving.replay import ScoringPlane

__all__ = [
    "BucketedMicrobatcher", "PendingRequest", "PendingBlock",
    "ServingError", "UnknownModelError", "ShedError", "RequestTimeout",
    "RequestError", "ReplicaDownError",
    "QueueScoreFrontend", "ScoreHTTPServer", "redis_score_frontend",
    "FAMILIES", "ModelRegistry", "ServableModel",
    "ReplicaPool", "PoolRequest",
    "ScoringPlane",
]
