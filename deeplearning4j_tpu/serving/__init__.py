"""Continuous-batching model server + the sequence/fleet tier.

The serving tier that amortizes XLA dispatches across concurrent
requests (the classic throughput lever of large-scale serving systems,
arXiv:1605.08695, applied on top of the one-executable-per-bucket
compilation model of arXiv:1810.09868):

* ``queue``    — bounded request queue + dynamic micro-batcher: coalesce
  waiting requests up to the nearest batch bucket (or a max-wait
  deadline), pad, run ONE dispatch through the per-bucket AOT
  executable cache, slice results back per request. Injectable clock
  so latency-path tests run deterministically without sleeps.
* ``sequence`` — iteration-level continuous batching for STATEFUL
  models: a slot table of active sequences with carried hidden/cell
  state, the batch re-formed every decode step (early-exit slots
  refilled from the queue mid-sequence), one executable per slot
  bucket, per-step deadlines. The KV-slot twin
  (``PagedSequenceScheduler``) serves token-prompt transformer models
  over the paged KV cache, interleaving bounded prefill chunks with
  the decode batch.
* ``kvcache``  — the paged KV cache itself: fixed-size KV blocks in a
  device-resident pool, per-slot block tables, allocation/free at
  step boundaries, copy-on-write prefix sharing; pool exhaustion is
  the typed ``KVCacheFullError`` (429).
* ``sampling`` — decode samplers (greedy, temperature/top-k) with
  deterministic per-(seed, stream) RNG streams: host callables over a
  logits row, of which the greedy one marks itself so that the paged
  scheduler takes the decode step's own argmax in its place.
* ``host``     — multi-model host: model name -> (network, dtype policy,
  optional weight-only int8, batch buckets), each precompiled at
  registration, with a rolling model swap that warms the new version's
  executables while the old one keeps serving; sequence models ride in
  a parallel table behind the same contract.
* ``fleet``    — N ModelHost replicas behind a least-loaded router:
  per-model SLOs, queue-depth-driven autoscale DECISIONS (callback
  surface), fleet-wide zero-5xx rolling swaps, load scenarios.
* ``breaker``  — the failure-domain primitives the fleet composes:
  per-replica circuit breaker (closed/open/half-open), quarantine +
  probe re-admission, ratio-capped retry budget, brownout admission
  control. Proven against the deterministic chaos harness
  (runtime/chaos.py).
* ``server``   — the HTTP front (``InferenceServer``): /healthz-gated
  readiness, queue-full backpressure as 429, per-request deadlines as
  504, ``:predict`` (one-shot) and ``:generate`` (sequence) routes.
* ``loadgen``  — open-loop (Poisson-arrival) and closed-loop (blocking
  clients + think time) load generators recording requests/sec,
  p50/p99 latency, per-error-class counts and batch occupancy.

See docs/SERVING.md.
"""

from deeplearning4j_tpu.serving.breaker import (  # noqa: F401
    BrownoutController, CircuitBreaker, ReplicaHealth, RetryBudget,
)
from deeplearning4j_tpu.serving.queue import (  # noqa: F401
    DeadlineExceededError, InferenceRequest, ManualClock, MicroBatcher,
    QueueFullError, RequestCancelledError, ServingClosedError,
)
from deeplearning4j_tpu.serving.kvcache import (  # noqa: F401
    KVCacheFullError, PagedKVCache,
)
from deeplearning4j_tpu.serving.sampling import (  # noqa: F401
    greedy_sampler, sampled_onehot_feedback, stream_rng,
    temperature_sampler,
)
from deeplearning4j_tpu.serving.sequence import (  # noqa: F401
    GenerationRequest, PagedSequenceScheduler, SequenceRequest,
    SequenceScheduler, greedy_onehot_feedback,
)
from deeplearning4j_tpu.serving.host import (  # noqa: F401
    ModelHost, ServedModel, ServedSequenceModel,
)
from deeplearning4j_tpu.serving.fleet import (  # noqa: F401
    FleetRouter, ModelSLO,
)
from deeplearning4j_tpu.serving.server import InferenceServer  # noqa: F401

__all__ = [
    "DeadlineExceededError", "InferenceRequest", "ManualClock",
    "MicroBatcher", "QueueFullError", "RequestCancelledError",
    "ServingClosedError",
    "SequenceRequest", "SequenceScheduler", "greedy_onehot_feedback",
    "GenerationRequest", "PagedSequenceScheduler",
    "KVCacheFullError", "PagedKVCache",
    "greedy_sampler", "temperature_sampler", "stream_rng",
    "sampled_onehot_feedback",
    "ModelHost", "ServedModel", "ServedSequenceModel",
    "FleetRouter", "ModelSLO", "InferenceServer",
    "BrownoutController", "CircuitBreaker", "ReplicaHealth",
    "RetryBudget",
]
