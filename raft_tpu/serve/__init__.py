"""raft_tpu.serve — multi-tenant batched KV/lease serving frontend on the
fused raft fabric (ROADMAP item 3).

Layers, client to device:

  session.py    per-tenant sessions, dedup seq, static hash placement
  admission.py  token buckets + in-flight cap -> typed Rejected(reason)
  coalescer.py  client queues -> ONE record of the round's touched lanes
  inject.py     that record -> per-block LocalOps, expanded on the device
  router.py     egress bundles -> commit watermarks -> KV apply -> notify
  kv.py         host-side applied materialization + scalar twin replay
  loop.py       ServeLoop: the per-round pipeline over a (Blocked)FusedCluster
  http.py       stdlib Prometheus scrape endpoint (/metrics, /healthz)
"""

from raft_tpu.serve.admission import (
    REJECT_INFLIGHT_CAP,
    REJECT_NO_LEADER,
    REJECT_QUEUE_FULL,
    REJECT_READ_BATCH_FULL,
    REJECT_SESSION_CLOSED,
    REJECT_TENANT_RATE,
    AdmissionController,
    Rejected,
    TokenBucket,
)
from raft_tpu.serve.coalescer import (
    ProposalCoalescer,
    ProposeTicket,
    ReadTicket,
)
from raft_tpu.serve.http import MetricsHTTPServer
from raft_tpu.serve.kv import (
    OP_DELETE,
    OP_LEASE,
    OP_PUT,
    Command,
    GroupStore,
    KVStore,
    replay,
)
from raft_tpu.serve.loop import ServeLoop, ServeMetrics
from raft_tpu.serve.router import CompletionRouter, GroupView
from raft_tpu.serve.session import Session, SessionManager, place

__all__ = [
    "AdmissionController",
    "Command",
    "CompletionRouter",
    "GroupStore",
    "GroupView",
    "KVStore",
    "MetricsHTTPServer",
    "OP_DELETE",
    "OP_LEASE",
    "OP_PUT",
    "ProposalCoalescer",
    "ProposeTicket",
    "ReadTicket",
    "Rejected",
    "REJECT_INFLIGHT_CAP",
    "REJECT_NO_LEADER",
    "REJECT_QUEUE_FULL",
    "REJECT_READ_BATCH_FULL",
    "REJECT_SESSION_CLOSED",
    "REJECT_TENANT_RATE",
    "ServeLoop",
    "ServeMetrics",
    "Session",
    "SessionManager",
    "TokenBucket",
    "place",
    "replay",
]
