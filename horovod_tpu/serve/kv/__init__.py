"""Paged KV-cache serving: block pool, prefix sharing, COW.

The vLLM-style order-of-magnitude lever on serving occupancy (ROADMAP
item 3): instead of one dense ``[slots, S, H, D]`` row per request,
every layer keeps ONE preallocated ``[num_blocks, block, H * D]`` pool
and each request maps its sequence onto a chain of fixed-size token
blocks through a host-side block table.  Identical prompt prefixes
resolve to the same physical blocks (radix-trie prefix index),
divergent writes copy-on-write, and unreferenced prefix blocks are
LRU-evicted under pressure.

Device-side layout and the jitted paged programs live in
:mod:`horovod_tpu.serve.engine`; :class:`BlockPool` (allocation,
refcounts, COW, eviction) and :class:`PrefixIndex` (token-trie lookup)
here are pure host bookkeeping — no jax imports, so the allocator unit
tests run in microseconds.

Knobs: ``HVD_TPU_SERVE_KV`` (``paged``/``dense``),
``HVD_TPU_SERVE_KV_BLOCK`` (tokens per block),
``HVD_TPU_SERVE_KV_BLOCKS`` (pool budget; 0 = auto),
``HVD_TPU_SERVE_SPEC_K`` (speculative draft length) — docs/serving.md.
"""

from .pool import BlockPool, KVPoolExhaustedError, TRASH_BLOCK  # noqa: F401
from .prefix import PrefixIndex  # noqa: F401
from .ring import RingPool  # noqa: F401
