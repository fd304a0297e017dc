"""The native JAX/TPU inference engine: paged KV allocator, KV manager,
continuous batching scheduler, and the AsyncEngine facade the serving
stack links to.

This replaces the reference's wrapped engines (vLLM/SGLang/TRT-LLM,
lib/llm/src/engines/*) with a first-party TPU engine.
"""

from .allocator import BlockAllocator

__all__ = ["BlockAllocator", "EngineConfig", "JaxEngine"]


def __getattr__(name):
    # the scheduler (and JAX with it) loads when it is asked for: the
    # allocator, the KV manager and the hash functions are host-side
    # modules that the router and unit tests import without a device
    if name in ("EngineConfig", "JaxEngine"):
        from . import engine

        return getattr(engine, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
