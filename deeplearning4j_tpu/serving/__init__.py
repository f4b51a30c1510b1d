"""Inference serving: compiled engines, dynamic micro-batching,
continuous-batching paged-KV decode, and multi-replica dispatch behind
a stdlib HTTP front end.

The training side compiles one program per shape bucket and keeps the
host off the critical path (datasets/device_feed.py); this package
applies the same discipline to the inference workload: an
`InferenceEngine` holds one jitted forward per bucket, a `MicroBatcher`
coalesces concurrent `/predict` requests into those buckets, `KVCache`
makes autoregressive decode O(1) per token, and a `DecodeLoop`
slot-schedules concurrent generate streams over a paged KV block pool
(`PagedKVPool`) under ONE compiled decode step — requests join/leave at
token boundaries, KV memory scales with written tokens, `/generate`
streams tokens as they emit. A `ReplicaSet` spreads engines across
local devices (least-outstanding dispatch). Above the single process,
a `Fleet` + router tier (`serving/fleet.py`, `serving/router.py`)
dispatches over N out-of-process replica servers with health-based
eviction/readmission, load shedding, rolling checkpoint reload and an
autoscaling hook. See docs/SERVING.md and docs/FLEET.md.
"""

from deeplearning4j_tpu.serving.batcher import MicroBatcher  # noqa: F401
from deeplearning4j_tpu.serving.errors import (  # noqa: F401
    Deadline,
    DeadlineExceededError,
    OverloadedError,
)
from deeplearning4j_tpu.serving.fleet import (  # noqa: F401
    Autoscaler,
    CircuitBreaker,
    Fleet,
    FleetReplica,
    NoReadyReplicas,
    ReplicaSpawner,
)
from deeplearning4j_tpu.serving.router import (  # noqa: F401
    FleetHandle,
    ReplicaClient,
    serve_fleet,
)
from deeplearning4j_tpu.serving.decode_loop import (  # noqa: F401
    DecodeLoop,
    GenerationStream,
)
from deeplearning4j_tpu.serving.engine import (  # noqa: F401
    EngineStats,
    InferenceEngine,
)
from deeplearning4j_tpu.serving.kv_cache import (  # noqa: F401
    KVCache,
    decode_step,
    generate_cached,
    init_cache,
    kv_cache_bytes,
    prefill,
)
from deeplearning4j_tpu.serving.paged_kv import (  # noqa: F401
    PagedKVPool,
    init_paged_pool,
    paged_kv_bytes,
)
from deeplearning4j_tpu.serving.replicas import ReplicaSet  # noqa: F401
from deeplearning4j_tpu.serving.server import serve_network  # noqa: F401
