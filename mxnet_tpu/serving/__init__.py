"""Serving subsystem — multi-model registry, SLA-aware dynamic batching,
bucketed AOT program caches, and zero-downtime rollover
(docs/faq/serving.md).

The TPU-native analog of the reference dependency engine's op bulking
(MXNet paper §4) and of TF-Serving's compiled-graph serving layer
(arXiv:1605.08695): request shapes round up into a small set of batch
buckets, each bucket's XLA program compiles once (ahead of time at warmup,
persisted across restarts via MXNET_TPU_COMPILE_CACHE), a dynamic
micro-batcher coalesces concurrent requests earliest-deadline-first —
shedding requests whose deadline budget queue wait already consumed
(`DeadlineExceeded`) so served-request p99 stays bounded under overload —
and a `ModelServer` hosts many named model/version entries with
least-loaded replica fan-out and live weight rollover.

Cross-process serving (ISSUE 11): `ServingFrontDoor` hosts a ModelServer
behind a TCP port (`serving/frontdoor.py` — deadline propagation,
request-level tracing, graceful drain) and `ServingClient`
(`serving/client.py`) is the pooled-connection caller; both speak the
length-prefixed framing in `serving/wire.py` shared with the dist_async
transport.

Cross-HOST serving (ISSUE 12): `ReplicaWorker` processes host replicas
behind their own front doors and register with a gateway's `FleetPool`
(`serving/pool.py` — heartbeat supervision with SUSPECT/DEAD states,
resolve-by-id recovery of a dead host's in-flight work, warmup +
half-open-probe readmission), `RemoteReplica` adapts them onto the
ModelServer's unchanged dispatch surface, tail-latency hedging
duplicates straggler dispatches (`MXNET_SERVING_HEDGE_MS`), and
`Autoscaler` polls `health()` to drive a pluggable worker launcher.
Optional HMAC frame auth: ``MXNET_SERVING_AUTH_KEY``.

Untrusted-network wire (ISSUE 13): every serving socket defaults to the
safe NON-EXECUTABLE codec (`serving/codec.py`,
``MXNET_SERVING_WIRE=safe`` — tagged plain-data encodings, allowlisted
array dtypes, every cap enforced before allocation), with per-connection
protocol/codec negotiation and rolling-upgrade tolerance for
previous-protocol pickle peers (``MXNET_SERVING_WIRE_COMPAT``);
`serving/wire_fuzz.py` + ``ci/run.py wire_fuzz_smoke`` keep the decoder
total over seeded mutational fuzz.

Stateful decode (ISSUE 18): `DecodeEngine` (`serving/decode.py`) runs
iteration-level continuous batching for autoregressive models over a
`PagedKVCache` (`serving/kvcache.py` — block-allocated device-resident
KV state, HBM bounded by LIVE tokens; allocation failure is the typed
`CacheOverflow` shed). Exactly two programs per (model, prefill-bucket)
family through the unified ProgramBuilder, AOT-warmed. The front door
streams replies (``stok``/``sdone`` frames) and `ClientStream` resumes
a broken stream by id with zero token loss or duplication; fleet
dispatch pins sequences to the replica holding their cache and never
hedges them.

    from mxnet_tpu.serving import InferenceEngine, ModelServer
"""
from .program_cache import BucketedProgramCache, DEFAULT_BUCKETS, bucket_for
from .batcher import (DynamicBatcher, DeadlineExceeded, pad_to_bucket,
                      default_max_batch)
from .engine import InferenceEngine
from .server import ModelServer
from .frontdoor import ServingFrontDoor
from .client import ServingClient, ClientStream
from .pool import FleetPool, RemoteReplica
from .worker import ReplicaWorker
from .autoscaler import Autoscaler, LocalProcessLauncher
from .kvcache import PagedKVCache, CacheOverflow, NULL_BLOCK
from .decode import DecodeEngine, DecodeStream

__all__ = ["InferenceEngine", "ModelServer", "ServingFrontDoor",
           "ServingClient", "ClientStream", "FleetPool", "RemoteReplica",
           "ReplicaWorker", "Autoscaler", "LocalProcessLauncher",
           "BucketedProgramCache",
           "DynamicBatcher", "DeadlineExceeded", "DEFAULT_BUCKETS",
           "bucket_for", "pad_to_bucket", "default_max_batch",
           "DecodeEngine", "DecodeStream", "PagedKVCache",
           "CacheOverflow", "NULL_BLOCK"]
