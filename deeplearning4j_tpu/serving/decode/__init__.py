"""Autoregressive decode plane (ISSUE 16): KV-cache generation beside
the stateless serving plane.

  * `cache`     — the paged KV-block arena + host-side block-pool
                  allocator (vLLM/PagedAttention-style block tables).
  * `engine`    — the KV-cache forward: AOT-compiled prefill and
                  decode-tick steps over TransformerBlock's decode mode.
  * `scheduler` — Orca-style token-granularity continuous batching:
                  sequences join and leave the decode batch between
                  ticks.
"""
from .cache import BlockPool, KvCacheSpec, OutOfBlocksError
from .engine import DecodeEngine
from .scheduler import GenerationError, GenerationScheduler

__all__ = ["BlockPool", "KvCacheSpec", "OutOfBlocksError", "DecodeEngine",
           "GenerationScheduler", "GenerationError"]
