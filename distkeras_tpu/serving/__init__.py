"""Serving tier: continuous-batching generation over a block-paged KV cache.

The decode stack (KV cache, GQA/MQA, sliding-window, beam, speculative,
int8) served one request at a time through
``GeneratorPredictor``; this package is the millions-of-users front end on
top of it:

- :mod:`~distkeras_tpu.serving.paged_cache` — the block pool
  (:class:`BlockAllocator`, :class:`PagedKVCache`): sequences of different
  lengths share ONE preallocated static-shape cache through per-sequence
  block tables (PagedAttention, Kwon et al. SOSP '23); the table-indexed
  addressing lives in ``models/lm.py :: DecoderBlock.paged_extend`` and is
  bit-identical to dense-cache decode.
- :mod:`~distkeras_tpu.serving.scheduler` — :class:`GenerationEngine`,
  iteration-level continuous batching (Orca, Yu et al. OSDI '22): FIFO
  admission into free slots/blocks, mixed prefill+decode across in-flight
  requests, per-row sampling params, per-step retirement, optional greedy
  speculative decoding with per-row advancement.
- :mod:`~distkeras_tpu.serving.frontdoor` — the admission/reuse layer
  (ISSUE 17): :class:`RadixPrefixCache`, a content-hash radix tree over
  full KV blocks (vLLM-lineage automatic prefix caching with
  copy-on-write), and :class:`TenantQueues`, per-tenant SLO-class
  priority queues with preemption-by-recompute — switched on per engine
  via ``prefix_cache=`` / ``prefill_chunk=`` / ``admission="slo"``.
- :mod:`~distkeras_tpu.serving.server` — :class:`GenerationServer` /
  :class:`GenerationClient` / :class:`ResilientGenerationClient` on the
  hardened ``networking.py`` framing, with bounded-queue backpressure
  (``ServerBusyError``), mid-stream death detection that frees the dead
  client's blocks, and graceful drain.

Benchmark: ``benchmark/drivers/serve.py`` drives client -> server -> engine
under a closed-loop mix; its cell is not in ``BENCHMARK.json`` yet (PERF.md
section 7), so this tier has no number on the chip.
"""

from distkeras_tpu.serving.frontdoor import (  # noqa: F401
    SLO_PRIORITY,
    PrefixMatch,
    RadixPrefixCache,
    TenantQueues,
    slo_priority,
)
from distkeras_tpu.serving.paged_cache import (  # noqa: F401
    BlockAllocator,
    BlockPoolExhausted,
    PagedKVCache,
    slot_map,
)
from distkeras_tpu.serving.scheduler import (  # noqa: F401
    GenerationEngine,
    Request,
    per_row_new_token_counts,
)
from distkeras_tpu.serving.server import (  # noqa: F401
    GenerationClient,
    GenerationServer,
    ResilientGenerationClient,
)

__all__ = [
    "SLO_PRIORITY",
    "PrefixMatch",
    "RadixPrefixCache",
    "TenantQueues",
    "slo_priority",
    "BlockAllocator",
    "BlockPoolExhausted",
    "PagedKVCache",
    "slot_map",
    "GenerationEngine",
    "Request",
    "per_row_new_token_counts",
    "GenerationClient",
    "GenerationServer",
    "ResilientGenerationClient",
]
