"""Serving — dynamic micro-batching inference behind admission control.

The subsystem the reference keeps as AnalysisPredictor-plus-your-own-
server, grown into a first-class layer (ROADMAP: "serving heavy traffic
from millions of users"):

* ``engine.ServingEngine`` — coalesces concurrent requests into padded,
  shape-bucketed batches over one frozen AnalysisPredictor; responses
  are bitwise-identical to unbatched runs;
* ``admission`` — bounded queue, typed backpressure
  (``ServerOverloadedError``), per-request deadlines, graceful drain;
* ``server`` — stdlib HTTP JSON front end + in-process ``LocalClient``,
  with every-bucket warmup.

Generative decode plane (ROADMAP item 1):

* ``decode.DecodeEngine`` — continuous-batching autoregressive
  generation with a prefill/decode phase split, slot-recycled decode
  state and per-request deadlines checked at step granularity;
  continuous-batched output is bitwise-identical to sequential decode;
* ``kv_cache.KVPagePool`` — the preallocated paged KV cache whose bytes
  book into the HBM ledger as ``mem.serving.kv_*``; a request that
  could never fit is refused with ``KVCacheExhaustedError`` at submit
  instead of OOMing mid-generation;
* int8 weight-only serving (``DecodeConfig(weight_quant="int8")``) via
  ops/quant_ops.py ``dequantize_weight``.

Cluster control plane (ROADMAP item 2):

* ``health`` — the liveness/readiness state machine behind ``/healthz``
  (503 while starting/swapping/draining) and ``/livez``;
* ``router`` — health-checked queue-depth load balancing with
  retry/failover on the shared core/retry.py schedule and request-id
  dedup (exactly-once under retries);
* ``cluster`` — ``ClusterController`` launches/supervises N replica
  processes (serving/replica.py) and rolls the fleet onto newly
  published model versions (checkpoint.publish_model COMMIT manifests)
  with zero downtime.

Load harness: tools/bench_serving.py (``--replicas N`` drives the
cluster). Chaos: ``serving.handler`` (engine loop), ``router.dispatch``
(router), ``replica.swap`` (model swap) fault sites;
tools/chaos_check.py --serving / --cluster.
"""

from .admission import (AdmissionQueue, DeadlineExceededError,
                        EngineClosedError, InferenceRequest,
                        KVCacheExhaustedError, ServerOverloadedError,
                        ServingError)
from .cluster import ClusterController, ClusterError, InprocReplica, \
    ReplicaProcess
from .decode import (DecodeConfig, DecodeEngine, GenerationRequest,
                     ShipPrefillRequest, decode_engine_from_dir,
                     demo_engine)
from .disagg import (ShipmentCRCError, ShipmentError, fetch_prefill,
                     pack_shipment, unpack_shipment)
from .engine import ServingConfig, ServingEngine
from .health import HealthState
from .kv_cache import KVPagePool, LayerCache, PagedKVCache
from .prefix_store import PrefixStore, prefix_chain_hash
from .router import (NoReplicaAvailableError, ReplicaHandle, Router,
                     RouterHTTPServer)
from .server import LocalClient, ServingHTTPServer, serve, serve_decode

__all__ = [
    "AdmissionQueue", "ClusterController", "ClusterError",
    "DeadlineExceededError", "DecodeConfig", "DecodeEngine",
    "EngineClosedError", "GenerationRequest", "HealthState",
    "InferenceRequest", "InprocReplica", "KVCacheExhaustedError",
    "KVPagePool", "LayerCache", "LocalClient", "NoReplicaAvailableError",
    "PagedKVCache", "PrefixStore", "ReplicaHandle", "ReplicaProcess", "Router",
    "RouterHTTPServer", "ServerOverloadedError", "ServingConfig",
    "ServingEngine", "ServingError", "ServingHTTPServer",
    "ShipPrefillRequest", "ShipmentCRCError", "ShipmentError",
    "decode_engine_from_dir", "demo_engine", "fetch_prefill",
    "pack_shipment", "prefix_chain_hash", "serve", "serve_decode",
    "unpack_shipment",
]
