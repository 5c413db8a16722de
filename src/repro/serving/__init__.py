"""Serving runtime: the hybrid tier per request (`HybridServer`) and on
the streaming flow table (`StreamingHybridServer`, sharded:
`ShardedStreamingServer`)."""

from repro.serving.hybrid_serving import HybridServer
from repro.serving.stream_serving import StreamingHybridServer, StreamStats
from repro.serving.shard_serving import ShardedStreamingServer
