"""TPU-native inference serving: continuous batching + replica routing.

The serving stack opens the inference workload the ROADMAP's north star
implies ("serves heavy traffic from millions of users") on top of the
training framework's existing layers:

* :mod:`~horovod_tpu.serve.engine` — jitted, length-bucketed prefill +
  slot-batched single-token decode over ``models.transformer.GPT``
  (preallocated KV cache, greedy/temperature/top-k sampling,
  spans ``hvd_tpu_engine_prefill``/``hvd_tpu_engine_decode``)
* :mod:`~horovod_tpu.serve.batcher` — continuous-batching scheduler
  (bounded admission queue, per-request deadlines, reject-when-full
  backpressure)
* :mod:`~horovod_tpu.serve.server` — replica endpoint on the runner's
  HMAC-authenticated RPC stack
* :mod:`~horovod_tpu.serve.router` — spreads requests across
  data-parallel replica groups (``process_sets``), task-agent-style
  strike/probation health, and drains a dead replica's in-flight
  requests back through :class:`~horovod_tpu.utils.retry.RetryPolicy`
* :mod:`~horovod_tpu.serve.metrics` — TTFT/TPOT/occupancy snapshots
* :mod:`~horovod_tpu.serve.kv` — paged block-pool KV cache: refcounted
  fixed-size token blocks with copy-on-write prefix sharing (radix
  trie over token IDs), LRU eviction, and speculative decoding
  (drafter + one-forward batched verification, token-identical to
  plain greedy decode)
* :mod:`~horovod_tpu.serve.fleet` — the disaggregated prefill/decode
  tier: role-split replicas with live KV migration over the HMAC wire
  (per-block digests, token-identical continuation), a router-tier
  global prefix directory, and a :class:`FleetController` driving
  per-role elastic scale-out / drain-and-retire from queue-depth and
  TTFT signals
* :mod:`~horovod_tpu.serve.swap` — zero-downtime weight hot-swap from
  the checkpoint store (``ckpt/``): a :class:`WeightSubscriber` per
  replica diff-pulls only changed shards (digest-verified), stages
  them beside the live params, and flips atomically at the batcher's
  swap barrier; rolling fleet swaps + instant journaled rollback ride
  the ``SwapRequest``/``RollbackRequest`` frames (docs/hot_swap.md)

* :mod:`~horovod_tpu.serve.qos` — SLO-aware multi-tenant QoS
  scheduling (docs/qos.md): service classes with per-tenant
  token-bucket budgets, weighted-fair (stride) admission replacing the
  FIFO queue, deadline-aware preemption of batch generations to the
  paged-KV prefix cache (token-identical resumption), and router-level
  rate limits with a graceful-brownout shed ladder (batch first, then
  standard, never interactive)

Chaos: the ``serve`` fault site (``HVD_TPU_FAULT_SPEC``) drops/delays
requests at the endpoint, kills a replica mid-decode or mid-migration,
and damages KV transfers at the migration boundary; the ``qos`` site
drills priority inversion and budget floods (docs/serving.md and
docs/qos.md have recipes).
"""

from .batcher import (  # noqa: F401
    ContinuousBatcher, QueueFullError, ReplicaDrainingError,
    ReplicaKilledError, ServeRequest,
)
from .engine import (  # noqa: F401
    InferenceEngine, PromptTooLongError, SamplingParams,
)
from .fleet import (  # noqa: F401
    FleetController, MigrationError, PrefixDirectory, ReplicaLauncher,
)
from .kv import (  # noqa: F401
    BlockPool, KVPoolExhaustedError, PrefixIndex,
)
from .metrics import ServingStats, percentile  # noqa: F401
from .qos import (  # noqa: F401
    BrownoutController, BudgetExhaustedError, QosGate, QosPolicy,
    QosQueue, RequestShedError,
)
from .router import (  # noqa: F401
    NoHealthyReplicasError, ReplicaSpec, ReplicaUnavailableError, Router,
    register_replica_process_sets, replica_slot_groups,
)
from .server import (  # noqa: F401
    CancelRequest, GenerateRequest, GenerateResponse, InferenceServer,
    RollbackRequest, StatsRequest, StatsResponse, SwapRequest,
    SwapResponse,
)
from .swap import (  # noqa: F401
    SwapAbandonedError, SwapFailedError, SwapRejectedError,
    WeightSubscriber,
)
from .tp import (  # noqa: F401
    ShardFollower, ShardLockstepError, ShardServer, ShardStepRequest,
    ShardStepResponse,
)
