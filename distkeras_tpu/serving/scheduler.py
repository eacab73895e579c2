"""Iteration-level continuous batching over the block-paged KV cache.

Orca's scheduling insight (Yu et al., OSDI '22): batch at the granularity
of one decode ITERATION, not one request. A static batch drains before
admitting anyone new, so a 512-token generation holds 31 finished slots
hostage; iteration-level scheduling retires a row the step its request
finishes and admits a queued request into the freed slot at the very next
step — the batch composition changes every iteration, the compiled step
never does (fixed ``max_batch`` rows; free rows write the scratch block
and are ignored).

:class:`GenerationEngine` is that scheduler plus the device programs:

- **admit** — strictly FIFO (the head of the queue is never skipped, so
  long prompts cannot starve behind a stream of short ones) whenever a
  batch slot AND enough pool blocks for the request's full budget
  (``ceil((Lp + max_new [+ spec])/block_size)``) are free. Reserving the
  whole budget up front keeps the pool overcommit-free: an admitted
  request can never die of block exhaustion mid-flight, so there is no
  preemption machinery to get wrong.
- **prefill** — one BATCHED forward per admission burst and padded-length
  group (prompts padded to a block multiple, group row count bucketed to
  powers of two: compile count is ``O(maxlen/block_size · log
  max_batch)``), scattered into the rows' allocated blocks through
  ``TransformerLM.prefill_raw``. Pad K/V beyond a real prompt is masked
  until decode overwrites it; dummy bucket rows write the scratch block.
- **decode** — ONE jitted fixed-shape step for all in-flight rows, each at
  its own position with its own sampling params
  (:func:`~distkeras_tpu.serving.paged_cache.sample_rows`), pools updated
  in place via buffer donation.
- **retire** — host-side per step: EOS, budget exhaustion, or client
  cancellation frees the row's blocks immediately (a dead connection
  releases its memory before its request would have finished).

With a ``draft`` model the engine runs greedy speculative decoding INSIDE
the continuous batch: each iteration the draft proposes ``spec_tokens``
greedily through its own paged pools (same block tables — the allocator is
shared), the target verifies all rows in one ``paged_extend_rows`` pass,
and each row advances by its OWN accepted length — no batch-minimum
lockstep, because per-row positions are native here (the dense
``speculative_generate`` must advance uniformly; the paged batch never
had that constraint).
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.model import ModelSpec
from distkeras_tpu.networking import ServerBusyError
from distkeras_tpu.observability import trace as _trace
from distkeras_tpu.serving.frontdoor import (
    RadixPrefixCache,
    TenantQueues,
    slo_priority,
)
from distkeras_tpu.serving.paged_cache import (
    BlockAllocator,
    PagedKVCache,
    sample_rows,
    slot_map,
)

_req_ids = itertools.count()


def per_row_new_token_counts(new_tokens, eos_id: int | None):
    """Real tokens per row of a ``[B, T]`` generated block: everything up to
    and INCLUDING the first ``eos_id`` (or all ``T`` when none appears /
    ``eos_id`` is None). This is the batch form of the serving tier's
    per-step retire rule — ``GeneratorPredictor(per_row_new_tokens=True)``
    and the tests share it instead of re-deriving eos semantics."""
    new_tokens = np.asarray(new_tokens)
    B, T = new_tokens.shape
    if eos_id is None:
        return np.full((B,), T, np.int32)
    hit = new_tokens == int(eos_id)
    first = np.argmax(hit, axis=1)
    return np.where(hit.any(axis=1), first + 1, T).astype(np.int32)


def summarize_latencies(records, window_s: float | None = None,
                        now: float | None = None) -> dict:
    """Per-SLO-class latency summary over retired-request records (the
    engine's ``_retired`` ring — or any iterable of dicts with ``t``,
    ``slo_class``, ``state``, ``total_s``, ``queue_s``, ``prefill_s``,
    ``decode_s``): p50/p99 end-to-end plus mean queue/prefill/decode
    breakdown, in ms, over COMPLETED requests only — a cancelled
    request's lifetime is how long its client waited before giving up,
    not a served latency, and pooling it in would let a storm of fast
    cancels mask a real SLO breach of the requests that finished.
    ``window_s`` restricts to records retired within the trailing
    window (None = everything in the ring). Pure function so the
    watchdog tests feed it synthetic records."""
    recs = [r for r in records if r.get("state", "done") == "done"]
    if window_s is not None:
        t_end = now if now is not None else (
            max(r["t"] for r in recs) if recs else 0.0)
        recs = [r for r in recs if r["t"] >= t_end - window_s]
    out: dict[str, dict] = {}
    by_cls: dict[str, list] = {}
    for r in recs:
        by_cls.setdefault(r.get("slo_class", "default"), []).append(r)
    for cls, rs in sorted(by_cls.items()):
        total = np.asarray([r["total_s"] for r in rs], np.float64) * 1e3
        rec = {
            "count": len(rs),
            "p50_ms": float(np.percentile(total, 50)),
            "p99_ms": float(np.percentile(total, 99)),
        }
        for key, out_key in (("queue_s", "queue_ms"),
                             ("prefill_s", "prefill_ms"),
                             ("decode_s", "decode_ms")):
            vals = [r[key] for r in rs if r.get(key) is not None]
            if vals:
                rec[out_key] = float(np.mean(vals)) * 1e3
        out[cls] = rec
    return out


class Request:
    """One generation request moving through the engine.

    States: ``queued`` → ``running`` → ``done`` | ``cancelled`` |
    ``failed``; ``rejected`` never enters the queue. ``result()`` blocks
    on completion and returns the NEW tokens (prompt excluded) as int32."""

    def __init__(self, prompt: np.ndarray, *, max_new_tokens: int,
                 temperature: float, top_k: int | None,
                 top_p: float | None, seed: int, eos_id: int | None,
                 request_id: str | None = None,
                 slo_class: str = "default", tenant: str = "default"):
        self.id = request_id if request_id is not None \
            else f"req-{next(_req_ids)}"
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.seed = int(seed)
        self.eos_id = eos_id
        # SLO class (ISSUE 13): a latency-telemetry label always; under
        # admission="slo" (ISSUE 17) ALSO the admission priority — see
        # frontdoor.SLO_PRIORITY
        self.slo_class = str(slo_class)
        # multi-tenant admission (ISSUE 17): the fairness bucket — one
        # tenant's backlog round-robins against its class siblings'
        # instead of occupying the whole queue. Scheduling metadata only
        # under admission="fifo".
        self.tenant = str(tenant)
        # the engine's model version this request was ADMITTED under
        # (stamped at admission; re-stamped when a hot swap re-prefills
        # it) — the version its served stream is bit-identical to
        self.model_version: int | None = None
        self.new_tokens: list[int] = []
        self.state = "queued"
        self.error: str | None = None
        self.t_submit = time.perf_counter()
        self.t_admit: float | None = None
        self.t_done: float | None = None
        self.prefill_s: float | None = None
        self._cancelled = False
        self._event = threading.Event()

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0

    def wait(self, timeout: float | None = None) -> bool:
        return self._event.wait(timeout)

    def result(self, timeout: float | None = None) -> np.ndarray:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.id} still {self.state}")
        if self.state != "done":
            raise RuntimeError(
                f"request {self.id} {self.state}"
                + (f": {self.error}" if self.error else "")
            )
        return np.asarray(self.new_tokens, np.int32)

    @property
    def latency_s(self) -> float | None:
        if self.t_done is None:
            return None
        return self.t_done - self.t_submit


class _Slot:
    """Host bookkeeping for one occupied batch row.

    ``blocks`` are the row's PRIVATE pool blocks (freed at retire);
    under a prefix cache the row may additionally reference shared
    tree blocks through ``pinned`` (released, never freed, at retire).
    ``phase`` is ``"decode"`` for legacy rows; front-door rows start in
    ``"prefill"`` and feed ``feed[next_pos:feed_len]`` in chunks before
    flipping to decode."""

    __slots__ = ("request", "blocks", "next_pos", "last_tok",
                 "phase", "feed", "feed_len", "pinned", "cow",
                 "sample_first", "resume_tok")

    def __init__(self, request: Request, blocks: list[int]):
        self.request = request
        self.blocks = blocks
        self.next_pos = 0   # absolute position of the token being FED
        self.last_tok = 0
        self.phase = "decode"
        self.feed: np.ndarray | None = None   # tokens still to prefill
        self.feed_len = 0
        self.pinned: list = []                # pinned radix-tree nodes
        self.cow: tuple | None = None         # (node, m, dst_block)
        self.sample_first = True   # sample at prefill end (fresh request)
        self.resume_tok = 0        # pending token of a preempted request


class GenerationEngine:
    """Continuous-batching generation over a block-paged KV cache.

    ``model``/``params`` as accepted by :func:`models.lm.generate`
    (``ModelSpec`` or bare ``TransformerLM`` — int8 specs from
    ``quantize_lm`` drop in unchanged). ``draft``/``draft_params`` switch
    on greedy speculative serving with ``spec_tokens`` proposals per
    iteration. ``num_blocks`` defaults to enough for ``max_batch`` rows of
    ``maxlen`` each (+ the scratch block) — shrink it to oversubscribe and
    let admission apply backpressure through the bounded queue instead.
    """

    def __init__(self, model, params, *, max_batch: int = 8,
                 block_size: int = 16, num_blocks: int | None = None,
                 max_queue: int = 64, draft=None, draft_params=None,
                 spec_tokens: int = 4, model_version: int = 0,
                 prefix_cache: bool = False,
                 prefill_chunk: int | None = None,
                 admission: str = "fifo"):
        from distkeras_tpu.models.lm import TransformerLM

        module = model.module if isinstance(model, ModelSpec) else model
        if not isinstance(module, TransformerLM):
            raise TypeError(
                f"GenerationEngine needs a TransformerLM (or its "
                f"ModelSpec), got {type(module)}"
            )
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if block_size < 1 or block_size > module.maxlen:
            raise ValueError(
                f"block_size must be in [1, maxlen={module.maxlen}], "
                f"got {block_size}"
            )
        self._module = module
        self._params = params
        # live-deployment version gate (distkeras_tpu/deploy): _params is
        # ONLY ever replaced at the top of step(), on the scheduler
        # thread, under the lock — swap_params from any other thread just
        # STAGES (params, version, policy) here. One decode_step can
        # therefore never see two weight sets: the atomic-swap invariant.
        self.model_version = int(model_version)
        self._staged_swap: tuple | None = None
        self.max_batch = int(max_batch)
        self.block_size = int(block_size)
        self.max_queue = int(max_queue)
        self._nb_per_seq = math.ceil(module.maxlen / self.block_size)
        self._L = self._nb_per_seq * self.block_size
        if num_blocks is None:
            num_blocks = self.max_batch * self._nb_per_seq + 1
        self.allocator = BlockAllocator(num_blocks, self.block_size)
        self.cache = PagedKVCache(module, num_blocks, self.block_size)

        # -- the serving front door (ISSUE 17) ---------------------------
        if admission not in ("fifo", "slo"):
            raise ValueError(
                f"admission must be 'fifo' or 'slo', got {admission!r}"
            )
        if prefill_chunk is not None and int(prefill_chunk) < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}"
            )
        self.prefix_cache = bool(prefix_cache)
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        self.admission = str(admission)
        # any front-door feature routes ALL prefill through the chunked
        # paged program (suffix prefill past a cached prefix and
        # preemption's prompt+generated recompute are the same mechanism)
        self._frontdoor = (self.prefix_cache
                           or self.prefill_chunk is not None
                           or self.admission == "slo")
        if self._frontdoor and draft is not None:
            raise ValueError(
                "prefix_cache/prefill_chunk/admission='slo' cannot be "
                "combined with a draft model: the draft's pools never "
                "hold a cached prefix's K/V, so speculative verify "
                "would read garbage"
            )
        self._prefix = (RadixPrefixCache(self.block_size)
                        if self.prefix_cache else None)
        self._tq = TenantQueues() if self.admission == "slo" else None
        self._chunk_fns: dict[tuple, object] = {}

        self._draft_module = None
        self._draft_params = draft_params
        self.spec_tokens = 0
        if draft is not None:
            dm = draft.module if isinstance(draft, ModelSpec) else draft
            if not isinstance(dm, TransformerLM):
                raise TypeError(
                    f"draft must be a TransformerLM (or its ModelSpec), "
                    f"got {type(dm)}"
                )
            if dm.vocab != module.vocab:
                raise ValueError(
                    f"draft vocab {dm.vocab} != target vocab {module.vocab}"
                )
            if int(spec_tokens) < 1:
                raise ValueError(
                    f"spec_tokens must be >= 1, got {spec_tokens}"
                )
            if module.attn_window is not None or dm.attn_window is not None:
                raise ValueError(
                    "speculative serving does not support sliding-window "
                    "models (the verify span crosses the window band)"
                )
            self._draft_module = dm
            self.spec_tokens = int(spec_tokens)
            self.draft_cache = PagedKVCache(dm, num_blocks, self.block_size)

        self._tables = np.zeros((self.max_batch, self._nb_per_seq),
                                np.int32)
        self._slots: list[_Slot | None] = [None] * self.max_batch
        # per-step hot-loop caches, refreshed only when the batch
        # composition changes (admission/retire), not every token: the
        # flattened slot map and the per-row sampling-param arrays
        self._batch_dirty = True
        self._np_slots: np.ndarray | None = None
        self._dev_tables_by_width: dict[int, object] = {}
        self._dev_sampling = None
        self._all_greedy = True
        self._queue: deque[Request] = deque()
        self._lock = threading.RLock()
        self._wake = threading.Condition(self._lock)
        self._closed = False
        self._stop = False
        self._thread: threading.Thread | None = None
        self.stats_ = {
            "submitted": 0, "admitted": 0, "completed": 0,
            "cancelled": 0, "rejected": 0, "failed": 0,
            "steps": 0, "prefills": 0, "tokens_generated": 0,
            "occupancy_sum": 0,
            "spec_rounds": 0, "spec_proposed": 0, "spec_accepted": 0,
            "swaps": 0, "refilled": 0,
            "chunk_rows": 0, "chunk_rows_padded": 0,
        }
        if self.admission == "slo":
            self.stats_["preemptions"] = 0
        if self.prefix_cache:
            self.stats_.update(prefix_hit_tokens=0,
                               prefix_prompt_tokens=0, cow_copies=0)
        # retired-request latency ring (ISSUE 13): one bounded record
        # per finalized request — the per-SLO-class p50/p99 +
        # queue/prefill/decode breakdown the watchtower samples and the
        # serving SLO rule judges. Appended under the engine lock.
        self._retired: deque = deque(maxlen=2048)

        self._decode_fn, self._decode_fn_greedy = self._make_decode()
        self._decode_widths: set[int] = set()
        self._step_num = 0
        self._prefill_fns: dict[int, object] = {}
        self._spec_fn = self._make_spec() if self._draft_module else None

    # -- device programs -----------------------------------------------------

    def _make_decode(self):
        from distkeras_tpu.models.lm import TransformerLM

        module, bs = self._module, self.block_size

        # the functions' names are the programs' in a profiler trace
        # (``jit_serve_decode`` on its ``XLA Modules`` line) and in the
        # run log's ``jax.compile`` entries
        def serve_decode(params, k_pools, v_pools, tok, tables, write_slot,
                         positions, temp, top_k, top_p, greedy, seeds):
            logits, k_pools, v_pools = module.apply(
                {"params": params}, tok, k_pools, v_pools, tables,
                write_slot, positions, bs,
                method=TransformerLM.paged_decode_step,
            )
            # deterministic per (request seed, absolute position): a
            # resubmitted request replays the same stream
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
            )(seeds, positions + 1)
            nxt = sample_rows(logits, keys, temp, top_k, top_p, greedy)
            return nxt, k_pools, v_pools

        # all-greedy fast path: serving batches are frequently pure-greedy
        # and the per-row warp costs two [B, vocab] sorts per token
        def serve_decode_greedy(params, k_pools, v_pools, tok, tables,
                                write_slot, positions):
            logits, k_pools, v_pools = module.apply(
                {"params": params}, tok, k_pools, v_pools, tables,
                write_slot, positions, bs,
                method=TransformerLM.paged_decode_step,
            )
            nxt = jnp.argmax(logits.astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
            return nxt, k_pools, v_pools

        return (jax.jit(serve_decode, donate_argnums=(1, 2)),
                jax.jit(serve_decode_greedy, donate_argnums=(1, 2)))

    def _make_prefill(self):
        from distkeras_tpu.models.lm import TransformerLM

        module, dm = self._module, self._draft_module

        def serve_prefill(params, d_params, k_pools, v_pools, dk_pools,
                          dv_pools, prompts, row_slots, lp, temp, top_k,
                          top_p, greedy, seeds):
            logits, kvs = module.apply(
                {"params": params}, prompts,
                method=TransformerLM.prefill_raw,
            )
            k_pools = tuple(p.at[row_slots].set(k)
                            for p, (k, _) in zip(k_pools, kvs))
            v_pools = tuple(p.at[row_slots].set(v)
                            for p, (_, v) in zip(v_pools, kvs))
            if dm is not None:
                _, dkvs = dm.apply(
                    {"params": d_params}, prompts,
                    method=TransformerLM.prefill_raw,
                )
                dk_pools = tuple(p.at[row_slots].set(k)
                                 for p, (k, _) in zip(dk_pools, dkvs))
                dv_pools = tuple(p.at[row_slots].set(v)
                                 for p, (_, v) in zip(dv_pools, dkvs))
            last = jnp.take_along_axis(
                logits, (lp - 1)[:, None, None], axis=1
            )[:, 0]                                          # [n, V]
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
            )(seeds, lp)
            tok = sample_rows(last, keys, temp, top_k, top_p, greedy)
            return tok, k_pools, v_pools, dk_pools, dv_pools

        return jax.jit(serve_prefill, donate_argnums=(2, 3, 4, 5))

    def _make_chunk(self):
        """The front-door prefill program: one ``paged_extend_rows`` pass
        feeding each row's next chunk of uncached tokens at its own
        position — suffix prefill past a cached prefix, Sarathi-style
        chunked prefill of a long prompt, and preemption's
        prompt+generated recompute are all this one program. The sampled
        token is only meaningful on a row's FINAL chunk (``last_idx``
        points at the last prompt token's logits; ``sample_pos`` is the
        prompt length so the key matches ``_make_prefill`` exactly);
        intermediate chunks discard it."""
        from distkeras_tpu.models.lm import TransformerLM

        module, bs = self._module, self.block_size

        def serve_chunk(params, k_pools, v_pools, tokens, tables,
                        write_slots, positions, last_idx, temp, top_k,
                        top_p, greedy, seeds, sample_pos):
            logits, k_pools, v_pools = module.apply(
                {"params": params}, tokens, k_pools, v_pools, tables,
                write_slots, positions, bs,
                method=TransformerLM.paged_extend_rows,
            )
            last = jnp.take_along_axis(
                logits, last_idx[:, None, None], axis=1
            )[:, 0]                                          # [n, V]
            keys = jax.vmap(
                lambda s, p: jax.random.fold_in(jax.random.PRNGKey(s), p)
            )(seeds, sample_pos)
            tok = sample_rows(last, keys, temp, top_k, top_p, greedy)
            return tok, k_pools, v_pools

        return jax.jit(serve_chunk, donate_argnums=(1, 2))

    def _make_spec(self):
        from distkeras_tpu.models.lm import TransformerLM

        module, dm, K = self._module, self._draft_module, self.spec_tokens
        bs = self.block_size

        def serve_spec(params, d_params, k, v, dk, dv, tok, tables,
                       positions, write_slots):
            def draft_step(carry, xs):
                t, dkp, dvp = carry
                i, ws = xs
                lg, dkp, dvp = dm.apply(
                    {"params": d_params}, t, dkp, dvp, tables, ws,
                    positions + i, bs,
                    method=TransformerLM.paged_decode_step,
                )
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                return (nxt, dkp, dvp), nxt

            # K+1 draft steps for K proposals: the extra step writes the
            # LAST proposal's K/V (its logits are discarded). Without it a
            # fully-accepted round leaves a permanent hole in the draft
            # cache at position p+K (the target's verify writes p..p+K,
            # the draft scan only p..p+K-1) — a zero K/V that rescales
            # the draft's softmax forever after and quietly erodes
            # acceptance. Exactness never depends on the draft, but
            # acceptance is the throughput, so the hole is worth one
            # draft step per round.
            xs = (jnp.arange(K + 1), jnp.swapaxes(write_slots, 0, 1))
            (_, dk, dv), outs = jax.lax.scan(draft_step, (tok, dk, dv), xs)
            props = outs.T[:, :K]                            # [B, K]
            block = jnp.concatenate([tok[:, None], props], axis=1)
            t_logits, k, v = module.apply(
                {"params": params}, block, k, v, tables, write_slots,
                positions, bs, method=TransformerLM.paged_extend_rows,
            )
            g = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # [B, K+1]
            match = (props == g[:, :K]).astype(jnp.int32)
            a_row = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            return props, g, a_row, k, v, dk, dv

        return jax.jit(serve_spec, donate_argnums=(2, 3, 4, 5))

    # -- client surface ------------------------------------------------------

    def _blocks_needed(self, lp: int, max_new: int) -> int:
        return math.ceil((lp + max_new + self.spec_tokens)
                         / self.block_size)

    def submit(self, prompt, *, max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int | None = None,
               top_p: float | None = None, seed: int = 0,
               eos_id: int | None = None,
               request_id: str | None = None,
               slo_class: str = "default",
               tenant: str = "default") -> Request:
        """Queue one generation; returns the :class:`Request` handle
        immediately. Raises :class:`ServerBusyError` when the bounded
        admission queue is full (backpressure) and ``ValueError`` on
        malformed requests — both BEFORE the queue, so a rejected request
        costs the engine nothing. ``slo_class`` labels the request's
        latency telemetry (per-class p50/p99 vs SLO in the watchdog);
        under ``admission="slo"`` it is ALSO the admission priority, and
        ``tenant`` buckets the per-tenant fairness rotation."""
        module = self._module
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1:
            raise ValueError(f"prompt must be 1-D [length], got "
                             f"{prompt.shape}")
        lp = prompt.shape[0]
        if lp < 1:
            raise ValueError("prompt must have at least one token")
        if prompt.min() < 0 or prompt.max() >= module.vocab:
            raise ValueError(
                f"prompt tokens outside [0, vocab={module.vocab})"
            )
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if lp + max_new + self.spec_tokens > module.maxlen:
            raise ValueError(
                f"prompt length {lp} + max_new_tokens {max_new}"
                + (f" + spec_tokens {self.spec_tokens}"
                   if self.spec_tokens else "")
                + f" exceeds the model's maxlen {module.maxlen}"
            )
        if self._blocks_needed(lp, max_new) > self.allocator.capacity:
            raise ValueError(
                f"request needs {self._blocks_needed(lp, max_new)} blocks "
                f"but the pool only has {self.allocator.capacity}"
            )
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k is not None and not 1 <= int(top_k) <= module.vocab:
            raise ValueError(
                f"top_k must be in [1, vocab={module.vocab}], got {top_k}"
            )
        if top_p is not None and not 0.0 < float(top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if eos_id is not None and not 0 <= int(eos_id) < module.vocab:
            raise ValueError(
                f"eos_id {eos_id} outside vocab {module.vocab}"
            )
        if self.spec_tokens and (temperature != 0.0 or top_k is not None
                                 or top_p is not None):
            raise ValueError(
                "speculative serving is greedy-only: temperature/top_k/"
                "top_p cannot be combined with a draft model"
            )
        req = Request(
            prompt, max_new_tokens=max_new, temperature=float(temperature),
            top_k=top_k, top_p=top_p, seed=int(seed),
            eos_id=None if eos_id is None else int(eos_id),
            request_id=request_id, slo_class=slo_class, tenant=tenant,
        )
        with self._wake:
            if self._closed:
                raise ServerBusyError("engine is draining: not accepting "
                                      "new requests")
            if self._queued_count() >= self.max_queue:
                self.stats_["rejected"] += 1
                req.state = "rejected"
                raise ServerBusyError(
                    f"admission queue full ({self.max_queue} waiting)"
                )
            self.stats_["submitted"] += 1
            self._q_push(req)
            self._wake.notify_all()
        # flight recorder: the request id is the serving tier's
        # correlation id (carried in the wire frame), so this enqueue
        # mark, the queued/prefill spans, and the final serve.request
        # span stitch one request across threads
        return req

    def cancel(self, request: Request) -> None:
        """Mark a request for cancellation; the engine frees its slot and
        blocks at the next iteration (queued requests never start)."""
        with self._wake:
            request._cancelled = True
            self._wake.notify_all()

    # -- queue plumbing: one strict-FIFO deque, or the tenant queues ---------

    def _queued_count(self) -> int:
        return len(self._tq) if self._tq is not None else len(self._queue)

    def _q_push(self, req: Request) -> None:
        if self._tq is not None:
            self._tq.push(req)
        else:
            self._queue.append(req)

    def _q_push_front(self, req: Request) -> None:
        if self._tq is not None:
            self._tq.push_front(req)
        else:
            self._queue.appendleft(req)

    def _q_drain(self) -> list[Request]:
        if self._tq is not None:
            return self._tq.drain()
        out = list(self._queue)
        self._queue.clear()
        return out

    # -- the hot-swap version gate (distkeras_tpu/deploy) --------------------

    def swap_params(self, params, version: int, policy: str = "drain",
                    draft_params=None) -> None:
        """Stage a model swap; the scheduler applies it BETWEEN decode
        steps (never inside one — old and new weights in a single
        ``decode_step`` would be a correctness bug, so ``_params`` is
        only replaced at the top of ``step()`` on the scheduler thread).

        ``policy`` decides what happens to in-flight requests:

        - ``"drain"`` — admission pauses, in-flight rows finish on the
          OLD weights, the swap lands once the batch is empty. No work
          is discarded; the swap waits for the longest active request.
        - ``"refill"`` — in-flight rows are watermarked and re-prefilled
          under the NEW weights: their blocks are freed, their emitted
          tokens reset, and they re-enter the queue head in admission
          order. Re-admission stamps the new ``model_version``; sampling
          is deterministic per (seed, position), so the re-served stream
          is bit-identical to an oracle run at the NEW version.

        ``version`` is not required to increase — a rollback re-stages
        the baseline. Staging twice replaces the earlier staged swap.
        """
        if policy not in ("drain", "refill"):
            raise ValueError(
                f"policy must be 'drain' or 'refill', got {policy!r}"
            )
        with self._wake:
            self._staged_swap = (params, int(version), policy, draft_params)
            self._wake.notify_all()

    def _apply_swap_locked(self) -> None:
        """Apply a staged swap if its policy allows (call under the lock,
        from the scheduler thread only)."""
        staged = self._staged_swap
        if staged is None:
            return
        params, version, policy, draft_params = staged
        active = [b for b, s in enumerate(self._slots) if s is not None]
        if policy == "drain" and active:
            return  # admission is paused; the batch drains first
        if policy == "refill" and active:
            # watermark: requeue at the FRONT, preserving admission
            # order, with blocks freed and emitted tokens reset — the
            # re-prefill under the new weights replays the stream
            rows = sorted(active,
                          key=lambda b: self._slots[b].request.t_admit,
                          reverse=True)
            for b in rows:
                self._evacuate_row(b, reset_tokens=True)
                self.stats_["refilled"] += 1
        if self._prefix is not None:
            # version gate for the radix tree: every cached block holds
            # K/V computed under the OLD weights — flush it all (no node
            # is pinned here: drain waited for an empty batch, refill
            # just evacuated every row)
            freed = self._prefix.flush()
            if freed:
                self.allocator.free(freed)
        self._params = params
        if draft_params is not None:
            self._draft_params = draft_params
        self.model_version = version
        self._staged_swap = None
        self.stats_["swaps"] += 1

    # -- the scheduler loop --------------------------------------------------

    def _finalize(self, req: Request, state: str,
                  error: str | None = None) -> None:
        req.state = state
        req.error = error
        req.t_done = time.perf_counter()
        key = {"done": "completed", "cancelled": "cancelled",
               "failed": "failed"}[state]
        self.stats_[key] += 1
        if state == "done":
            self.stats_["tokens_generated"] += len(req.new_tokens)
        # latency telemetry (ISSUE 13): queue wait + prefill + decode
        # decompose the end-to-end latency from timestamps the request
        # already carries — no tracing required
        queue_s = (req.t_admit - req.t_submit
                   if req.t_admit is not None else None)
        total_s = req.t_done - req.t_submit
        decode_s = None
        if queue_s is not None:
            decode_s = total_s - queue_s - (req.prefill_s or 0.0)
        self._retired.append({
            "t": req.t_done, "slo_class": req.slo_class, "state": state,
            "total_s": total_s, "queue_s": queue_s,
            "prefill_s": req.prefill_s, "decode_s": decode_s,
            "new_tokens": len(req.new_tokens),
            "model_version": req.model_version,
        })
        if _trace.enabled():
            # whole-lifetime span (submit → retire), on the tracer's clock
            _trace.record(
                "serve.request", int(req.t_submit * 1e9),
                int(req.t_done * 1e9), corr=req.id,
                args={"state": state,
                      "new_tokens": len(req.new_tokens)},
            )
        req._event.set()

    def _retire(self, b: int, state: str, error: str | None = None) -> None:
        with self._wake:  # RLock: safe from inside step()'s locked region
            slot = self._slots[b]
            self._slots[b] = None
            self._tables[b, :] = 0
            self._batch_dirty = True
            self._release_pins(slot)
            self.allocator.free(slot.blocks)
            self._finalize(slot.request, state, error)

    def _release_pins(self, slot: _Slot) -> None:
        """Drop the row's references on shared radix-tree nodes: its
        matched chain and, if the copy-on-write landed nobody yet, the
        pending COW source (pinned at admission so eviction could not
        free it between match and copy)."""
        if self._prefix is None:
            return
        if slot.pinned:
            self._prefix.release(slot.pinned)
            slot.pinned = []
        if slot.cow is not None:
            self._prefix.release([slot.cow[0]])
            slot.cow = None

    def _evacuate_row(self, b: int, *, reset_tokens: bool) -> None:
        """Tear one RUNNING row down and re-queue its request at the
        head: private blocks freed, tree pins released, request back to
        ``queued``. Hot-swap ``refill`` and preemption-by-recompute share
        this — refill also resets the emitted stream (it replays under
        the new weights); preemption keeps ``new_tokens`` and the
        re-admission re-prefills prompt+generated-so-far, so sampling
        (deterministic per seed and absolute position) resumes
        bit-identically."""
        slot = self._slots[b]
        self._slots[b] = None
        self._tables[b, :] = 0
        self._batch_dirty = True
        self._release_pins(slot)
        self.allocator.free(slot.blocks)
        req = slot.request
        if reset_tokens:
            req.new_tokens = []
        req.state = "queued"
        req.t_admit = None
        req.prefill_s = None
        req.model_version = None
        self._q_push_front(req)

    def _admit(self) -> list[tuple[int, Request]]:
        """FIFO admission under the lock; returns newly filled (row, req)
        pairs whose prefill still has to run (device work happens outside
        the lock — ``submit`` must never block behind a forward pass)."""
        admitted = []
        if (self._staged_swap is not None
                and self._staged_swap[2] == "drain"):
            return admitted  # draining toward a staged swap: hold the door
        free_rows = [b for b, s in enumerate(self._slots) if s is None]
        while self._queue and free_rows:
            head = self._queue[0]
            if head._cancelled:
                self._queue.popleft()
                self._finalize(head, "cancelled", "cancelled while queued")
                continue
            need = self._blocks_needed(head.prompt.shape[0],
                                       head.max_new_tokens)
            if not self.allocator.can_alloc(need):
                break       # strict FIFO: never skip the head (starvation)
            self._queue.popleft()
            b = free_rows.pop(0)
            blocks = self.allocator.alloc(need)
            slot = _Slot(head, blocks)
            self._slots[b] = slot
            self._tables[b, :] = 0
            self._tables[b, :need] = blocks
            self._batch_dirty = True
            head.state = "running"
            head.t_admit = time.perf_counter()
            head.model_version = self.model_version
            self.stats_["admitted"] += 1
            if _trace.enabled():
                # the admission-wait span: submit → admit, per request
                _trace.record("serve.queued", int(head.t_submit * 1e9),
                              int(head.t_admit * 1e9), corr=head.id)
            admitted.append((b, head))
        return admitted

    # -- front-door admission (ISSUE 17) --------------------------------------

    def _q_pop_head(self, req: Request) -> None:
        if self._tq is not None:
            self._tq.pop(req)
        else:
            self._queue.popleft()

    def _admit_frontdoor(self) -> int:
        """Admission with the front door on: the head candidate (highest
        SLO class, tenant round-robin within it) is matched against the
        prefix cache, reserved only its UNCACHED blocks, and installed in
        ``"prefill"`` phase for the chunk loop. The head is never skipped
        — when it cannot fit even after tree eviction and (under SLO
        admission) preemption of strictly-lower-priority rows, admission
        stops: the same no-starvation rule as strict FIFO."""
        admitted = 0
        if (self._staged_swap is not None
                and self._staged_swap[2] == "drain"):
            return admitted  # draining toward a staged swap
        while True:
            if not any(s is None for s in self._slots):
                break
            if self._tq is not None:
                head = self._tq.candidate()
            else:
                head = self._queue[0] if self._queue else None
            if head is None:
                break
            if head._cancelled:
                self._q_pop_head(head)
                self._finalize(head, "cancelled", "cancelled while queued")
                continue
            res = self._reserve_for(head)
            if res is None:
                break
            self._q_pop_head(head)
            b = next(i for i, s in enumerate(self._slots) if s is None)
            self._install_row(b, head, res)
            admitted += 1
        return admitted

    def _reserve_for(self, req: Request):
        """Reserve blocks (and a pinned prefix-cache match) for ``req``
        under the lock, or return None when the pool cannot fit it. The
        shortfall ladder: evict refcount-0 cached chains first, then
        (SLO admission only) preempt strictly-lower-priority running
        rows, latest-admitted first. A valid request always fits an
        empty pool (submit() rejects anything over capacity), so the
        ladder terminates."""
        bs = self.block_size
        lp = req.prompt.shape[0]
        g = len(req.new_tokens)
        if g:
            # resume after preemption/requeue: re-prefill the prompt plus
            # everything emitted EXCEPT the pending last token — its K/V
            # is written when decode feeds it, exactly as if the request
            # had never left the batch
            feed = np.concatenate(
                [req.prompt, np.asarray(req.new_tokens[:-1], np.int32)])
        else:
            feed = req.prompt
        feed_len = int(feed.shape[0])
        total = self._blocks_needed(lp, req.max_new_tokens)
        match, cached_len = None, 0
        if self._prefix is not None:
            # fresh requests keep at least the LAST prompt token uncached
            # (its logits seed the first sample); a resumed request's
            # pending token is already known, so it may match all of feed
            cap = feed_len if g else lp - 1
            match = self._prefix.match(feed, cap)
            if match.cow_node is not None:
                match.cow_node.refs += 1  # pin until the slots are copied
            cached_len = match.tokens(bs)
        cb = len(match.nodes) if match else 0
        need = total - cb
        while not self.allocator.can_alloc(need):
            if self._prefix is not None:
                freed = self._prefix.evict(
                    need - self.allocator.free_blocks)
                if freed:
                    self.allocator.free(freed)
                    continue
            if not self._preempt_lower(req):
                if match is not None:
                    if match.cow_node is not None:
                        self._prefix.release([match.cow_node])
                    self._prefix.release(match.nodes)
                return None
        blocks = self.allocator.alloc(need)
        return (match, blocks, feed, feed_len, cached_len, g)

    def _preempt_lower(self, req: Request) -> bool:
        """Preempt ONE running row whose request has a strictly lower
        SLO priority (latest admitted first — the least sunk prefill
        cost), freeing its private blocks. The victim re-queues at its
        tenant's head and recomputes prompt+generated on re-admission."""
        if self._tq is None:
            return False
        prio = slo_priority(req.slo_class)
        victims = [b for b, s in enumerate(self._slots)
                   if s is not None
                   and slo_priority(s.request.slo_class) > prio]
        if not victims:
            return False
        b = max(victims, key=lambda x: self._slots[x].request.t_admit)
        victim_id = self._slots[b].request.id
        self._evacuate_row(b, reset_tokens=False)
        self.stats_["preemptions"] += 1
        return True

    def _install_row(self, b: int, req: Request, res) -> None:
        match, blocks, feed, feed_len, cached_len, g = res
        slot = _Slot(req, blocks)
        self._tables[b, :] = 0
        cb = len(match.nodes) if match else 0
        if cb:
            self._tables[b, :cb] = match.blocks
            slot.pinned = list(match.nodes)
        self._tables[b, cb:cb + len(blocks)] = blocks
        slot.feed = np.asarray(feed, np.int32)
        slot.feed_len = feed_len
        slot.next_pos = int(cached_len)
        if match is not None and match.cow_node is not None:
            # the divergent block's first cow_len slots are copied from
            # the COW source into the row's FIRST private block before
            # any forward touches them (_apply_cows, same step)
            slot.cow = (match.cow_node, match.cow_len, blocks[0])
        if self.prefix_cache:
            self.stats_["prefix_hit_tokens"] += int(cached_len)
            self.stats_["prefix_prompt_tokens"] += feed_len
        if g:
            slot.sample_first = False
            slot.resume_tok = int(req.new_tokens[-1])
        if cached_len >= feed_len:
            # fully cached resume: nothing left to prefill
            slot.phase = "decode"
            slot.last_tok = slot.resume_tok
            req.prefill_s = 0.0
        else:
            slot.phase = "prefill"
        self._slots[b] = slot
        self._batch_dirty = True
        req.state = "running"
        req.t_admit = time.perf_counter()
        req.model_version = self.model_version
        self.stats_["admitted"] += 1
        if _trace.enabled():
            _trace.record("serve.queued", int(req.t_submit * 1e9),
                          int(req.t_admit * 1e9), corr=req.id)

    def _run_prefills(self, admitted) -> None:
        """Prefill an admission burst in as few forwards as possible: one
        BATCHED ``prefill_raw`` per padded-length group (row count bucketed
        to powers of two — dummy rows write the scratch block — so compile
        count stays ``O(len buckets · log max_batch)``, not one program per
        group size). A burst of admissions at saturation was serializing
        ``n`` batch-1 forwards, each streaming the full weights; grouping
        streams them once per length bucket."""
        groups: dict[int, list] = {}
        for b, req in admitted:
            lp = req.prompt.shape[0]
            lpad = math.ceil(lp / self.block_size) * self.block_size
            groups.setdefault(lpad, []).append((b, req))
        vocab = self._module.vocab
        for lpad, grp in groups.items():
            n = len(grp)
            npad = 1 << (n - 1).bit_length()
            prompts = np.zeros((npad, lpad), np.int32)
            # dummy rows scatter into the scratch block (block 0) only:
            # duplicate indices are fine, nobody reads those slots
            row_slots = np.tile(
                np.tile(np.arange(self.block_size, dtype=np.int32),
                        lpad // self.block_size), (npad, 1))
            lp_arr = np.ones((npad,), np.int32)
            temp = np.zeros((npad,), np.float32)
            top_k = np.full((npad,), vocab, np.int32)
            top_p = np.ones((npad,), np.float32)
            greedy = np.ones((npad,), bool)
            seeds = np.zeros((npad,), np.int32)
            for i, (b, req) in enumerate(grp):
                lp = req.prompt.shape[0]
                prompts[i, :lp] = req.prompt
                row_slots[i] = slot_map(self._tables[b:b + 1],
                                        self.block_size)[0, :lpad]
                lp_arr[i] = lp
                temp[i] = req.temperature
                if req.top_k is not None:
                    top_k[i] = req.top_k
                if req.top_p is not None:
                    top_p[i] = req.top_p
                greedy[i] = req.greedy
                seeds[i] = req.seed
            key = (lpad, npad)
            if key not in self._prefill_fns:
                self._prefill_fns[key] = self._make_prefill()
            c, dc = self.cache, getattr(self, "draft_cache", None)
            # always timed (one clock read per prefill FORWARD, not per
            # request): the duration feeds each request's latency
            # breakdown whether or not tracing is on
            t_pf = time.perf_counter_ns()
            tok, c.k_pools, c.v_pools, dk, dv = self._prefill_fns[key](
                self._params, self._draft_params, c.k_pools, c.v_pools,
                dc.k_pools if dc else (), dc.v_pools if dc else (),
                jnp.asarray(prompts), jnp.asarray(row_slots),
                jnp.asarray(lp_arr), jnp.asarray(temp),
                jnp.asarray(top_k), jnp.asarray(top_p),
                jnp.asarray(greedy), jnp.asarray(seeds),
            )
            if dc:
                dc.k_pools, dc.v_pools = dk, dv
            tok = np.asarray(jax.device_get(tok))
            t1_pf = time.perf_counter_ns()
            for _, req in grp:
                # the group forward, attributed to every request it
                # prefilled (same interval — the latency breakdown and,
                # when tracing, the span, each with its own corr)
                req.prefill_s = (t1_pf - t_pf) / 1e9
                if _trace.enabled():
                    _trace.record("serve.prefill", t_pf, t1_pf,
                                  corr=req.id,
                                  args={"rows": n, "lpad": lpad})
            self.stats_["prefills"] += n
            for i, (b, req) in enumerate(grp):
                slot = self._slots[b]
                slot.next_pos = req.prompt.shape[0]
                slot.last_tok = int(tok[i])
                self._emit(b, [slot.last_tok])

    def _apply_cows(self) -> None:
        """Land every pending copy-on-write: device-copy each COW source
        block's shared leading slots into the row's first private block,
        then unpin the source. Runs BEFORE any forward each step — the
        chunk (or the fully-cached resume's decode) attends over those
        positions."""
        rows = [b for b, s in enumerate(self._slots)
                if s is not None and s.cow is not None]
        if not rows:
            return
        bs = self.block_size
        src, dst, pending = [], [], []
        for b in rows:
            node, m, d = self._slots[b].cow
            src.append(node.block * bs + np.arange(m, dtype=np.int64))
            dst.append(d * bs + np.arange(m, dtype=np.int64))
            pending.append((b, node))
        src = np.concatenate(src)
        dst = np.concatenate(dst)
        # pad to a power of two with scratch self-copies (slot 0 → slot
        # 0) so the jitted gather-scatter compiles a handful of shapes
        npad = 1 << (len(src) - 1).bit_length()
        pad = npad - len(src)
        if pad:
            src = np.concatenate([src, np.zeros(pad, np.int64)])
            dst = np.concatenate([dst, np.zeros(pad, np.int64)])
        self.cache.copy_slots(src, dst)
        with self._wake:
            for b, node in pending:
                self._slots[b].cow = None
                self._prefix.release([node])
                self.stats_["cow_copies"] += 1

    def _run_chunks(self, rows) -> None:
        """One chunk of front-door prefill for every ``"prefill"``-phase
        row: each feeds up to ``prefill_chunk`` (or its whole remaining
        suffix) tokens at its own position through ONE batched
        ``paged_extend_rows`` — then the step's decode batch runs, so a
        long prompt interleaves with in-flight decode instead of
        head-of-line-blocking it. A row whose feed completes flips to
        decode; fresh rows sample their first token from the last prompt
        position's logits, resumed rows re-emit nothing (their pending
        token was sampled before preemption)."""
        bs = self.block_size
        vocab = self._module.vocab
        rem = max(self._slots[b].feed_len - self._slots[b].next_pos
                  for b in rows)
        Tpad = (self.prefill_chunk if self.prefill_chunk is not None
                else 1 << (rem - 1).bit_length())
        n = len(rows)
        npad = 1 << (n - 1).bit_length()
        need_pos = max(min(Tpad, self._slots[b].feed_len
                           - self._slots[b].next_pos)
                       + self._slots[b].next_pos for b in rows)
        nb = min(self._nb_per_seq,
                 2 * math.ceil(math.ceil(need_pos / bs) / 2))
        key = (Tpad, npad, nb)
        # from before the host builds the arrays to after the token fetch
        with _trace.span("serve.chunk", cat="serve", profile=True,
                         args={"rows": n, "padded_rows": npad,
                               "tpad": Tpad, "width": nb,
                               "key": str(key)}) as chunk:
            tokens = np.zeros((npad, Tpad), np.int32)
            tables = np.zeros((npad, nb), np.int32)
            # pad rows / pad positions write the scratch block's slots —
            # garbage nobody reads, same trick as the legacy prefill buckets
            write_slots = np.tile((np.arange(Tpad) % bs).astype(np.int32),
                                  (npad, 1))
            positions = np.zeros((npad,), np.int32)
            last_idx = np.zeros((npad,), np.int32)
            sample_pos = np.zeros((npad,), np.int32)
            temp = np.zeros((npad,), np.float32)
            top_k = np.full((npad,), vocab, np.int32)
            top_p = np.ones((npad,), np.float32)
            greedy = np.ones((npad,), bool)
            seeds = np.zeros((npad,), np.int32)
            t_real = []
            for i, b in enumerate(rows):
                s = self._slots[b]
                r = s.request
                t = min(Tpad, s.feed_len - s.next_pos)
                t_real.append(t)
                tokens[i, :t] = s.feed[s.next_pos: s.next_pos + t]
                tables[i] = self._tables[b, :nb]
                pos = s.next_pos + np.arange(t)
                write_slots[i, :t] = tables[i, pos // bs] * bs + pos % bs
                positions[i] = s.next_pos
                last_idx[i] = min(max(s.feed_len - 1 - s.next_pos, 0),
                                  Tpad - 1)
                sample_pos[i] = s.feed_len   # == lp for fresh requests: the
                temp[i] = r.temperature      # key matches _make_prefill
                if r.top_k is not None:
                    top_k[i] = r.top_k
                if r.top_p is not None:
                    top_p[i] = r.top_p
                greedy[i] = r.greedy
                seeds[i] = r.seed
            if key not in self._chunk_fns:
                self._chunk_fns[key] = self._make_chunk()
            c = self.cache
            tok, c.k_pools, c.v_pools = self._chunk_fns[key](
                self._params, c.k_pools, c.v_pools, jnp.asarray(tokens),
                jnp.asarray(tables), jnp.asarray(write_slots),
                jnp.asarray(positions), jnp.asarray(last_idx),
                jnp.asarray(temp), jnp.asarray(top_k), jnp.asarray(top_p),
                jnp.asarray(greedy), jnp.asarray(seeds),
                jnp.asarray(sample_pos),
            )
            tok = np.asarray(jax.device_get(tok))
        t_pf, t1_pf = chunk.t0, chunk.t1
        with self._wake:
            self.stats_["chunk_rows"] += n
            self.stats_["chunk_rows_padded"] += npad
            for i, b in enumerate(rows):
                s = self._slots[b]
                r = s.request
                r.prefill_s = (r.prefill_s or 0.0) + (t1_pf - t_pf) / 1e9
                if _trace.enabled():
                    # the step's one chunk interval, once a request
                    _trace.record("serve.prefill", t_pf, t1_pf, corr=r.id,
                                  args={"rows": n, "chunk": int(t_real[i]),
                                        "pos": int(s.next_pos)})
                s.next_pos += t_real[i]
                if s.next_pos < s.feed_len:
                    continue
                # feed complete: this row decodes from the next step
                s.phase = "decode"
                self.stats_["prefills"] += 1
                if self._prefix is not None:
                    # donate the prompt's full blocks to the radix tree;
                    # blocks already cached along the chain stay private
                    lp = r.prompt.shape[0]
                    nfull = lp // bs
                    if nfull:
                        new_nodes, adopted = self._prefix.insert(
                            r.prompt,
                            [int(self._tables[b, k]) for k in range(nfull)],
                        )
                        s.pinned.extend(new_nodes)
                        if adopted:
                            adset = set(adopted)
                            s.blocks = [x for x in s.blocks
                                        if x not in adset]
                if s.sample_first:
                    s.last_tok = int(tok[i])
                    self._emit(b, [s.last_tok])
                else:
                    s.last_tok = s.resume_tok

    def _emit(self, b: int, tokens: list[int]) -> None:
        """Append emitted tokens to row ``b``'s request, applying the
        retire rule (budget, then first EOS — the rule
        :func:`per_row_new_token_counts` mirrors batch-wide)."""
        slot = self._slots[b]
        req = slot.request
        done = False
        for t in tokens:
            req.new_tokens.append(int(t))
            if req.eos_id is not None and int(t) == req.eos_id:
                done = True
                break
            if len(req.new_tokens) >= req.max_new_tokens:
                done = True
                break
        if done:
            self._retire(b, "done")

    def step(self) -> bool:
        """One scheduler iteration: retire cancellations, admit + prefill,
        one batched decode (or speculative) step. Returns whether any work
        was done — the loop thread sleeps on False."""
        self._step_num += 1
        with _trace.span("serve.step", cat="serve", profile=True,
                         step=self._step_num):
            return self._step()

    def _step(self) -> bool:
        with self._wake:
            with _trace.span("serve.retire", cat="serve", profile=True):
                for b, slot in enumerate(self._slots):
                    if slot is not None and slot.request._cancelled:
                        self._retire(b, "cancelled", "cancelled by client")
                self._apply_swap_locked()
            with _trace.span("serve.admit", cat="serve", profile=True):
                admitted = (self._admit_frontdoor() if self._frontdoor
                            else self._admit())
        worked = bool(admitted)
        if self._frontdoor:
            self._apply_cows()
            prefill_rows = [b for b, s in enumerate(self._slots)
                            if s is not None and s.phase == "prefill"]
            if prefill_rows:
                self._run_chunks(prefill_rows)
                worked = True
            active = [b for b, s in enumerate(self._slots)
                      if s is not None and s.phase == "decode"]
        else:
            if admitted:
                self._run_prefills(admitted)
            active = [b for b, s in enumerate(self._slots)
                      if s is not None]
        if not active:
            return worked
        # rows-in-flight rides the span (ISSUE 14): the analyzer's
        # batch-occupancy input
        with _trace.span("serve.decode_step", cat="serve", profile=True,
                         args={"rows": len(active)}):
            if self._spec_fn is not None:
                self._spec_step(active)
            else:
                self._decode_step(active)
        with self._wake:
            self.stats_["steps"] += 1
            self.stats_["occupancy_sum"] += len(active)
        return True

    def _refresh_batch_cache(self):
        """Rebuild the per-batch device arrays — ONLY when the batch
        composition changed (admission/retire), never per token: the slot
        map and sampling params are constants of a batch lineup, and
        rebuilding + re-uploading them each step is host work a step does
        not need."""
        if not self._batch_dirty:
            return
        B = self.max_batch
        self._np_slots = slot_map(self._tables, self.block_size)
        self._dev_tables_by_width = {}
        temp = np.zeros((B,), np.float32)
        top_k = np.full((B,), self._module.vocab, np.int32)
        top_p = np.ones((B,), np.float32)
        greedy = np.ones((B,), bool)
        seeds = np.zeros((B,), np.int32)
        for b, s in enumerate(self._slots):
            if s is None:
                continue
            r = s.request
            temp[b] = r.temperature
            if r.top_k is not None:
                top_k[b] = r.top_k
            if r.top_p is not None:
                top_p[b] = r.top_p
            greedy[b] = r.greedy
            seeds[b] = r.seed
        self._all_greedy = bool(greedy.all())
        self._dev_sampling = tuple(
            jnp.asarray(a) for a in (temp, top_k, top_p, greedy, seeds)
        )
        self._batch_dirty = False

    def _tables_for(self, need_pos: int):
        """Device block tables truncated to the working width: the paged
        gather (and the attention scores behind it) only needs to cover
        positions ``< need_pos``, so the step attends over the longest
        ACTIVE sequence, not ``maxlen`` — a real advantage over the dense
        scan, whose ``[B, maxlen]`` cache pays full width every step.
        Width is bucketed to 2-block multiples so XLA compiles a handful
        of step shapes, not one per length."""
        nb = min(self._nb_per_seq,
                 2 * math.ceil(math.ceil(need_pos / self.block_size) / 2))
        self._decode_widths.add(nb)
        if nb not in self._dev_tables_by_width:
            self._dev_tables_by_width[nb] = jnp.asarray(
                self._tables[:, :nb]
            )
        return self._dev_tables_by_width[nb]

    def _tok_positions(self, active):
        B = self.max_batch
        tok = np.zeros((B,), np.int32)
        positions = np.zeros((B,), np.int32)
        for b in active:
            s = self._slots[b]
            tok[b] = s.last_tok
            positions[b] = s.next_pos
        return tok, positions

    def _decode_step(self, active) -> None:
        self._refresh_batch_cache()
        tok, positions = self._tok_positions(active)
        write_slot = self._np_slots[np.arange(self.max_batch), positions]
        if self._frontdoor:
            # rows mid-chunked-prefill sit in the batch with REAL blocks
            # in their tables but position 0 here — without masking, the
            # decode write would land in their (possibly SHARED, cached)
            # first block's slot 0. Park every non-decode row's write in
            # the scratch block instead.
            mask = np.zeros((self.max_batch,), bool)
            mask[active] = True
            write_slot = np.where(
                mask, write_slot,
                np.arange(self.max_batch) % self.block_size)
        dev_tables = self._tables_for(int(positions.max()) + 1)
        c = self.cache
        if self._all_greedy:
            nxt, c.k_pools, c.v_pools = self._decode_fn_greedy(
                self._params, c.k_pools, c.v_pools, jnp.asarray(tok),
                dev_tables, jnp.asarray(write_slot),
                jnp.asarray(positions),
            )
        else:
            nxt, c.k_pools, c.v_pools = self._decode_fn(
                self._params, c.k_pools, c.v_pools, jnp.asarray(tok),
                dev_tables, jnp.asarray(write_slot),
                jnp.asarray(positions), *self._dev_sampling,
            )
        nxt = np.asarray(jax.device_get(nxt))
        for b in active:
            slot = self._slots[b]
            slot.next_pos += 1
            slot.last_tok = int(nxt[b])
            self._emit(b, [slot.last_tok])

    def _spec_step(self, active) -> None:
        K = self.spec_tokens
        self._refresh_batch_cache()
        tok, positions = self._tok_positions(active)
        slots = self._np_slots
        idx = positions[:, None] + np.arange(K + 1)[None, :]
        write_slots = np.take_along_axis(slots, idx, axis=1)
        c, dc = self.cache, self.draft_cache
        dev_tables = self._tables_for(int(positions.max()) + K + 1)
        props, g, a_row, c.k_pools, c.v_pools, dc.k_pools, dc.v_pools = \
            self._spec_fn(
                self._params, self._draft_params, c.k_pools, c.v_pools,
                dc.k_pools, dc.v_pools, jnp.asarray(tok),
                dev_tables, jnp.asarray(positions),
                jnp.asarray(write_slots),
            )
        props, g, a_row = jax.device_get((props, g, a_row))
        with self._wake:
            self.stats_["spec_rounds"] += 1
            self.stats_["spec_proposed"] += K * len(active)
        for b in active:
            slot = self._slots[b]
            a = int(a_row[b])
            emitted = [int(x) for x in props[b, :a]] + [int(g[b, a])]
            with self._wake:
                self.stats_["spec_accepted"] += a
            # per-row advancement: this row moves a+1 positions no matter
            # what the rest of the batch accepted
            slot.next_pos += a + 1
            slot.last_tok = int(g[b, a])
            self._emit(b, emitted)

    # -- lifecycle -----------------------------------------------------------

    def _idle(self) -> bool:
        return (self._queued_count() == 0
                and all(s is None for s in self._slots))

    def run_until_idle(self, max_steps: int = 1_000_000) -> None:
        """Synchronous drive (tests, parity oracles): step until every
        queued and running request has retired."""
        for _ in range(max_steps):
            with self._lock:
                if self._idle():
                    return
            self.step()
        raise RuntimeError(f"no progress after {max_steps} steps")

    def run(self) -> None:
        while True:
            with self._wake:
                if self._stop:
                    return
                if self._idle() and self._staged_swap is None:
                    # a staged swap on an idle engine still needs one
                    # step() to land (an activated version must not wait
                    # for the next request to arrive)
                    self._wake.wait(0.05)
                    continue
            try:
                self.step()
            except Exception as e:  # a poisoned step must not hang clients
                with self._wake:
                    # stop admitting: with the loop thread dead, anything
                    # submitted later would queue forever — reject it as
                    # busy (retryable) instead of hanging the client
                    self._closed = True
                    for b, slot in enumerate(self._slots):
                        if slot is not None:
                            self._retire(b, "failed", repr(e))
                    for req in self._q_drain():
                        self._finalize(req, "failed", repr(e))
                raise

    def start(self) -> None:
        self._thread = threading.Thread(target=self.run, daemon=True)
        self._thread.start()

    def close(self) -> None:
        """Stop accepting new requests (drain begins); in-flight and queued
        requests keep running to completion."""
        with self._wake:
            self._closed = True
            self._wake.notify_all()

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait until every accepted request has retired."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            with self._lock:
                if self._idle():
                    return True
            time.sleep(0.005)
        return False

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        self.close()
        if drain and self._thread is not None:
            self.drain(timeout)
        with self._wake:
            self._stop = True
            self._wake.notify_all()
        if self._thread is not None:
            # join BEFORE retiring leftovers: a step in flight reads
            # _slots/_tables outside the lock, so yanking rows under it
            # races into use-after-retire; the loop re-checks _stop each
            # iteration, so the join is bounded by one step
            self._thread.join(timeout=10)
            self._thread = None
        with self._wake:
            # anything still queued/running dies visibly, not silently
            for b, slot in enumerate(self._slots):
                if slot is not None:
                    self._retire(b, "cancelled", "engine stopped")
            for req in self._q_drain():
                self._finalize(req, "cancelled", "engine stopped")

    def latency_stats(self, window_s: float | None = None) -> dict:
        """Per-SLO-class latency summary (see
        :func:`summarize_latencies`) from the retired-request ring."""
        with self._lock:
            recs = list(self._retired)
        return summarize_latencies(recs, window_s=window_s)

    def stats(self) -> dict:
        with self._lock:
            s = dict(self.stats_)
            # snapshot the ring under the lock, summarize AFTER: the
            # percentile math is O(ring) and the decode loop contends
            # for this lock — a scrape must not stall token generation
            retired = list(self._retired)
            s["queued"] = self._queued_count()
            s["active"] = sum(1 for x in self._slots if x is not None)
            s["model_version"] = self.model_version
            s["staged_version"] = (
                self._staged_swap[1] if self._staged_swap else None
            )
            s["blocks_in_use"] = self.allocator.used_blocks
            s["blocks_free"] = self.allocator.free_blocks
            s["blocks_high_water"] = self.allocator.high_water
            s["programs_built"] = (len(self._chunk_fns)
                                   + len(self._prefill_fns)
                                   + len(self._decode_widths))
            s["mean_batch_occupancy"] = (
                round(s["occupancy_sum"] / s["steps"], 3)
                if s["steps"] else 0.0
            )
            if self.spec_tokens:
                s["spec_acceptance"] = (
                    round(s["spec_accepted"] / s["spec_proposed"], 4)
                    if s["spec_proposed"] else 0.0
                )
            if self._prefix is not None:
                s["prefix_cached_blocks"] = len(self._prefix)
                s["prefix_evictions"] = self._prefix.evictions
                tot = s["prefix_prompt_tokens"]
                s["prefix_hit_rate"] = (
                    round(s["prefix_hit_tokens"] / tot, 4) if tot else 0.0
                )
        s["latency"] = summarize_latencies(retired)
        return s

    def prefix_hit_rate(self) -> float:
        """Lifetime token-level prefix-cache hit rate (0.0 when the cache
        is off or nothing admitted yet) — the number the server publishes
        into directory meta so the router can weight replica affinity by
        where prefixes are already warm."""
        with self._lock:
            if self._prefix is None:
                return 0.0
            tot = self.stats_["prefix_prompt_tokens"]
            if not tot:
                return 0.0
            return round(self.stats_["prefix_hit_tokens"] / tot, 4)

    def flush_prefix_cache(self) -> int:
        """Drop every unpinned cached chain, returning its blocks to the
        allocator; returns how many blocks were freed. Chains pinned by
        in-flight rows survive."""
        with self._lock:
            if self._prefix is None:
                return 0
            freed = self._prefix.flush()
            if freed:
                self.allocator.free(freed)
            return len(freed)
