"""TPU LM serving: slot-based continuous batching (engine.py), the
prefix-reusable paged KV block pool it admits from (kv_cache.py), and
the fleet-facing replica server (replica.py) the elastic gateway
(``edl_tpu.gateway``) routes to."""

from edl_tpu.serving.engine import ContinuousBatcher

__all__ = ["ContinuousBatcher", "ReplicaServer", "publish_engine_stats"]


def __getattr__(name):
    # ReplicaServer pulls in the RPC/coord layers; keep `import
    # edl_tpu.serving` light for engine-only users (serve_lm)
    if name in ("ReplicaServer", "publish_engine_stats"):
        from edl_tpu.serving import replica
        return getattr(replica, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
