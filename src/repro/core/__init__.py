"""SerPyTor core: context-aware durable computational-graph execution.

The paper's contribution, realized for JAX/TPU clusters. See DESIGN.md §2-3.
"""

from .context import EMPTY_CONTEXT, Context, ContextEntry, canonical_digest
from .durable import (
    KNOWN_KINDS,
    Interrupted,
    Journal,
    JournalRecord,
    ReplayCache,
    atomic_task,
    decode_payload,
    encode_payload,
    interrupt,
    payload_digest,
)
from .executor import ClusterExecutor, ExecutionReport, LocalExecutor, WithContext
from .failure import FailureKind, LivenessDetector, RetryPolicy, StragglerWatch, Verdict
from .gateway import (
    AllocationError,
    Gateway,
    TaskCancelled,
    TaskRequest,
    WorkerHandle,
    context_affinity,
    least_loaded,
    power_of_two,
    round_robin,
)
from .graph import ContextGraph, CycleError, Node, UnionNode, toposort_levels
from .heartbeat import HeartbeatServer, check_heartbeat, telemetry
from .server import (
    FlakyWorker,
    InProcWorker,
    TaskRegistry,
    WorkerClient,
    WorkerServer,
    WorkerStreamError,
)
from .shards import ShardedGateway

__all__ = [
    "Context",
    "ContextEntry",
    "EMPTY_CONTEXT",
    "canonical_digest",
    "Journal",
    "JournalRecord",
    "KNOWN_KINDS",
    "ReplayCache",
    "Interrupted",
    "interrupt",
    "atomic_task",
    "encode_payload",
    "decode_payload",
    "payload_digest",
    "LocalExecutor",
    "ClusterExecutor",
    "ExecutionReport",
    "WithContext",
    "FailureKind",
    "Verdict",
    "LivenessDetector",
    "RetryPolicy",
    "StragglerWatch",
    "Gateway",
    "TaskRequest",
    "WorkerHandle",
    "AllocationError",
    "TaskCancelled",
    "round_robin",
    "least_loaded",
    "power_of_two",
    "context_affinity",
    "ContextGraph",
    "Node",
    "UnionNode",
    "CycleError",
    "toposort_levels",
    "HeartbeatServer",
    "check_heartbeat",
    "telemetry",
    "ShardedGateway",
    "TaskRegistry",
    "WorkerServer",
    "WorkerClient",
    "InProcWorker",
    "FlakyWorker",
    "WorkerStreamError",
]
