"""Async workers — hogwild replicas driving devices from host threads.

Parity: reference ``distkeras/workers.py`` — per-algorithm workers whose
``train(index, iterator)`` ran inside Spark executors: deserialize model,
local ``train_on_batch`` loop, ``pull``/``commit`` against the PS every
``communication_window`` batches (SURVEY.md §3.1). Here each worker is a host
thread that owns a jitted local-window function executing on its assigned
device (``jax.devices()[i % n]``); the thread does pull → window-on-device →
commit, overlapping freely with other workers — genuinely asynchronous, like
the reference, unlike the lockstep collective backend.

The per-algorithm commit payloads match §2b.3:

- ADAG / DOWNPOUR / DynSGD: window weight delta vs the pulled center (equal to
  the accumulated optimizer update); worker re-bases onto the fresh center
  after each commit.
- AEASGD / EAMSGD: elastic difference ``alpha · (worker − center)``; the
  worker subtracts it locally and keeps its own variable across windows.

The center-side fold semantics live in ``MergeRule.fold`` (shared with the
sync backend's oracle tests).
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Any

import jax
import numpy as np

from distkeras_tpu import utils
from distkeras_tpu.observability import trace as _trace
from distkeras_tpu.parallel.merge_rules import ElasticAverageMerge
from distkeras_tpu.parameter_servers import (
    ParameterServer,
    ParameterServerClient,
    SocketParameterServer,
    StandbySocketParameterServer,
)

Pytree = Any

#: Exchange-phase histogram bucket edges (milliseconds, powers of two):
#: a sample lands in the first bucket whose edge is >= its value, with one
#: overflow bucket past the last edge. Cheap enough to run per window and
#: coarse enough to stay JSON-small in ``trainer.ps_stats_``.
_PHASE_BUCKETS = (0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0,
                  128.0, 256.0, 512.0, 1024.0)


def aggregate_exchange_phases(workers) -> dict:
    """Merge every worker's per-phase exchange timings (fetch / compress /
    commit / pull ms — see ``AsyncWorker._phase``) into one summary dict,
    attached to ``trainer.ps_stats_["exchange_phases"]`` so the overlap
    the pipelined exchange buys is observable, not asserted. JSON-clean."""
    out: dict = {}
    for w in workers:
        for name, rec in getattr(w, "_phases", {}).items():
            agg = out.setdefault(name, {
                "count": 0, "total_ms": 0.0, "max_ms": 0.0,
                "hist_ms_le": list(_PHASE_BUCKETS) + ["inf"],
                "hist": [0] * (len(_PHASE_BUCKETS) + 1),
            })
            agg["count"] += rec["count"]
            agg["total_ms"] += rec["total_ms"]
            agg["max_ms"] = max(agg["max_ms"], rec["max_ms"])
            agg["hist"] = [a + b for a, b in zip(agg["hist"], rec["hist"])]
    for rec in out.values():
        rec["mean_ms"] = (
            rec["total_ms"] / rec["count"] if rec["count"] else 0.0
        )
    return out


def _build_local_window(loss_step, optimizer):
    """One worker's jitted window: scan `window` local steps on its device."""
    import optax

    def window(params, nt, opt, batches):
        def one_step(carry, batch):
            params, nt, opt = carry
            (loss, new_nt), grads = jax.value_and_grad(loss_step, has_aux=True)(
                params, nt, batch
            )
            updates, opt = optimizer.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
            return (params, new_nt, opt), loss

        (params, nt, opt), losses = jax.lax.scan(
            one_step, (params, nt, opt), batches
        )
        return params, nt, opt, jax.numpy.mean(losses)

    return jax.jit(window)


class AsyncWorker:
    """One training replica on one device, exchanging with the PS."""

    def __init__(self, worker_id: int, device, window_fn, optimizer, ps,
                 rule, window: int, batch_size: int, nt, history, lock,
                 barrier: threading.Barrier | None = None,
                 ckpt_pred=None,
                 restore: dict | None = None, start_epoch: int = 0,
                 tolerant: bool = False, codec=None, fault_plan=None,
                 assigner=None, drain_event: threading.Event | None = None,
                 coordinator=None, joiner: bool = False,
                 pipeline_depth: int = 0, fused: bool = True):
        self.worker_id = worker_id
        self.device = device
        self.window_fn = window_fn
        self.optimizer = optimizer
        self.ps = ps
        self.rule = rule
        self.window = window
        self.batch_size = batch_size
        self.nt = nt
        self.history = history
        self.lock = lock
        # Epoch barrier, installed only when checkpointing is on: workers
        # rendezvous at epoch boundaries the cadence predicate selects, so one
        # of them can snapshot a consistent (center, per-worker state) tuple.
        # Without a checkpoint_dir epochs stay free-running (hogwild), as in
        # the reference. ckpt_pred is identical across workers, so they all
        # agree on which epochs rendezvous.
        self.barrier = barrier
        self.ckpt_pred = ckpt_pred
        self.restore = restore
        self.start_epoch = int(start_epoch)
        self.tolerant = bool(tolerant)
        # Lossy commit compression (parallel.compression) with error
        # feedback: the residual the codec dropped is added to the next
        # window's commit, so the transmitted stream telescopes to the true
        # one. Residual state is per-worker and intentionally NOT
        # checkpointed (restarting feedback at zero is harmless).
        self.codec = codec
        self._resid = None
        self.snapshot: dict | None = None
        self.error: BaseException | None = None
        # Resilience hooks (distkeras_tpu/resilience): the fault plan's
        # kill-at-window chaos hook, and piggyback heartbeats — the lease
        # renewal rides the window loop when the client supports it, so
        # liveness tracks actual training progress (no extra threads).
        self.fault_plan = fault_plan
        self._windows_done = 0
        # Elastic membership (resilience/elastic.py): with an `assigner`
        # the worker ignores its static shard and leases window-sized
        # blocks from the shared per-epoch pool instead — the loop that
        # lets workers join and drain mid-run without dropping or
        # double-training a single example. `drain_event` is the
        # preemption notice (checked at window boundaries: finish the
        # in-flight window, commit, hand blocks back, exit);
        # `coordinator.on_window` fires the fault plan's seeded
        # join/preempt events; `joiner=True` runs the live-join
        # handshake (the `join` wire action) before the first pull.
        self.assigner = assigner
        self.drain_event = drain_event
        self.coordinator = coordinator
        self.joiner = bool(joiner)
        # Pipelined exchange (ISSUE 10): depth 1 launches window N+1's
        # jitted compute on-device, then performs window N's exchange on
        # the host while the device runs — the committed delta is one
        # window stale (DynSGD prices it via the exchange's `lag` flag).
        # Depth 0 (default) is the serial loop, bit-identical to the
        # pre-pipeline behavior. `fused` routes the exchange through the
        # single-RTT EXCHANGE wire action when the client has one
        # (halving the wire cost); False keeps the commit();pull() pair.
        self.pipeline_depth = int(pipeline_depth)
        self.fused = bool(fused)
        # zero-copy host staging: per-leaf delta scratch (allocated once,
        # written with out=) + a double-buffered re-base target for the
        # pipelined loop — steady-state exchange does no per-window
        # O(model) allocation on the uncompressed path
        self._stage_delta: list | None = None
        self._stage_base: list[list] | None = None
        self._base_flip = 0
        # per-phase exchange timings (fetch/compress/commit/pull ms):
        # merged across workers into ps_stats_["exchange_phases"]
        self._phases: dict[str, dict] = {}
        # flight-recorder correlation (ISSUE 11): a per-worker window
        # ordinal sets this thread's corr id at each window's staging,
        # so the phase spans (and, via the wire frame / seqno, the PS's
        # fold+WAL spans) stitch into one timeline per exchange
        self._xid = 0
        # dispatch timestamp of the in-flight window's compute (ISSUE
        # 14): set at window_fn dispatch, closed into a worker.compute
        # span at fetch-return — the analyzer's overlap/compute
        # evidence. Only written while tracing is on (off path stays
        # allocation-free).
        self._t_launch: float | None = None

    def _record_compute(self, t_end: float) -> None:
        """Close the window's dispatch→fetch-return ``worker.compute``
        span (the interval the device had this window's work
        outstanding — in the pipelined loop the exchange hides inside
        it, which is exactly what the analyzer measures). Call only
        when tracing is enabled."""
        if self._t_launch is not None:
            _trace.record("worker.compute",
                          int(self._t_launch * 1e9), int(t_end * 1e9))
            self._t_launch = None

    def _compress(self, tree, owned: bool = False):
        """→ (wire payload, transmitted tree); updates the residual.

        Steady-state allocation-free (ISSUE 10 zero-copy staging): the
        residual UPDATE always writes in place into this worker's
        persistent residual buffers, and with ``owned=True`` (the delta
        paths, whose leaves are this worker's staging scratch) the
        residual ADD also writes into the input leaves — no model-sized
        temporaries per window. ``owned=False`` (default) never mutates
        the caller's tree, the historical contract."""
        if self.codec is None:
            return tree, tree
        if self._resid is not None:
            if owned:
                tree = jax.tree.map(
                    lambda t, r: np.add(t, r, out=t)
                    if getattr(t, "flags", None) is not None
                    and t.flags.writeable else t + r,
                    tree, self._resid,
                )
            else:
                tree = jax.tree.map(np.add, tree, self._resid)
        blob = self.codec.encode(tree)
        sent = self.codec.decode(blob)
        if self._resid is None:
            self._resid = jax.tree.map(np.subtract, tree, sent)
        else:
            jax.tree.map(
                lambda r, t, s: np.subtract(t, s, out=r),
                self._resid, tree, sent,
            )
        return blob, sent

    def _next_corr(self) -> None:
        """Stamp this thread's correlation id for the window being
        staged (``w<id>:x<n>``). The resilient client overrides it with
        the wire-carried ``w<id>:s<seq>`` when it assigns the commit
        seqno — either way the worker-side exchange span and the PS-side
        fold/WAL spans close under the same id. Call only when tracing
        is enabled (the off path must stay free)."""
        self._xid += 1
        _trace.set_corr(f"w{self.worker_id}:x{self._xid}")

    def _phase(self, name: str, t0: float) -> float:
        """Record one exchange-phase sample (ms since ``t0``); returns a
        fresh ``perf_counter`` for chaining the next phase. With tracing
        on, the same two timestamps become a real span (the ISSUE 11
        upgrade of the PR 10 phase histograms) — no extra clock reads."""
        t1 = time.perf_counter()
        if _trace.enabled():
            _trace.record("worker." + name, int(t0 * 1e9), int(t1 * 1e9))
        ms = (t1 - t0) * 1e3
        rec = self._phases.get(name)
        if rec is None:
            rec = self._phases[name] = {
                "count": 0, "total_ms": 0.0, "max_ms": 0.0,
                "hist": [0] * (len(_PHASE_BUCKETS) + 1),
            }
        rec["count"] += 1
        rec["total_ms"] += ms
        if ms > rec["max_ms"]:
            rec["max_ms"] = ms
        rec["hist"][bisect.bisect_left(_PHASE_BUCKETS, ms)] += 1
        return t1

    def _window_delta(self, params, base):
        """``params − base`` into the preallocated per-leaf delta staging
        buffers: ``np.asarray`` views the device buffer where the backend
        allows (the CPU path's zero-copy fetch; elsewhere it is the one
        unavoidable D2H copy) and the subtract writes into scratch
        allocated once per worker — no per-window O(model) allocation.
        Blocks until the window's compute is done (the `fetch` phase)."""
        cleaves, treedef = jax.tree.flatten(base)
        hleaves = jax.tree.leaves(params)
        if self._stage_delta is None:
            self._stage_delta = [
                np.empty(np.shape(h), np.asarray(h).dtype) for h in hleaves
            ]
        out = [
            np.subtract(np.asarray(h), np.asarray(c), out=s)
            for h, c, s in zip(hleaves, cleaves, self._stage_delta)
        ]
        return jax.tree.unflatten(treedef, out)

    def _rebase_host(self, center, sent):
        """The pipelined deferred re-base ``center + sent`` (the freshest
        center in hand plus this window's transmitted update) into one of
        TWO alternating staging buffer sets: the buffer fed to window N's
        ``device_put`` is only rewritten at window N+2, after window N's
        compute has provably finished — safe even when ``device_put``
        aliases the host buffer (CPU backends)."""
        cleaves, treedef = jax.tree.flatten(center)
        sleaves = jax.tree.leaves(sent)
        if self._stage_base is None:
            self._stage_base = [
                [np.empty(np.shape(c), np.asarray(c).dtype)
                 for c in cleaves]
                for _ in range(2)
            ]
        bufs = self._stage_base[self._base_flip]
        self._base_flip ^= 1
        out = [
            np.add(np.asarray(c), np.asarray(s), out=b)
            for c, s, b in zip(cleaves, sleaves, bufs)
        ]
        return jax.tree.unflatten(treedef, out)

    def _do_exchange(self, blob, lag: bool = False):
        """ONE wire exchange: the fused single-RTT EXCHANGE action when
        enabled and the client speaks it, else the classic commit();
        pull() pair — timed per phase either way (the fused RTT lands in
        `commit`; `pull` stays empty, which is itself the observable 2→1
        claim). NOTE: the unfused pair cannot carry ``lag`` (the wire
        has no slot for it), so trainers.py rejects pipelining without
        fusion — a direct caller combining them would silently
        under-price DynSGD τ by one window."""
        t0 = time.perf_counter()
        exchange = getattr(self.ps, "exchange", None) if self.fused \
            else None
        if exchange is not None:
            center = exchange(self.worker_id, blob, lag=lag)
            self._phase("commit", t0)
        else:
            self.ps.commit(self.worker_id, blob)
            t0 = self._phase("commit", t0)
            center = self.ps.pull(self.worker_id)
            self._phase("pull", t0)
        return center

    def train(self, index: int, shard_cols: tuple, num_epoch: int,
              shuffle: bool, seed: int) -> None:
        """Reference signature spirit: ``Worker.train(index, iterator)``."""
        try:
            # the pipelined (depth-1) loops apply to the delta-committing
            # rules only: an elastic-rule commit depends on a fresh pull,
            # so its exchange cannot be deferred behind the next window
            # (run_async_training validates this loudly; direct callers
            # fall back to the serial loop)
            pipelined = self.pipeline_depth >= 1 and not isinstance(
                self.rule, ElasticAverageMerge
            )
            if self.assigner is not None:
                # elastic membership: shard_cols is the FULL column set;
                # the shared assigner hands out window blocks instead of
                # a static per-worker shard (epochs/shuffle/seed live in
                # the assigner, built once by run_async_training)
                if pipelined:
                    self._train_elastic_pipelined(shard_cols)
                else:
                    self._train_elastic(shard_cols)
            elif pipelined:
                self._train_pipelined(index, shard_cols, num_epoch,
                                      shuffle, seed)
            else:
                self._train(index, shard_cols, num_epoch, shuffle, seed)
        except BaseException as e:  # surface thread failures to the driver
            self.error = e
            if self.barrier is not None:
                self.barrier.abort()  # don't deadlock peers at the barrier

    def _train(self, index, shard_cols, num_epoch, shuffle, seed):
        rows = len(shard_cols[0])
        win_rows = self.window * self.batch_size
        n_windows = rows // win_rows
        elastic = isinstance(self.rule, ElasticAverageMerge)
        # register the liveness lease up front (no-op on plain clients);
        # a restarted worker's first heartbeat re-admits it after eviction
        maybe_heartbeat = getattr(self.ps, "maybe_heartbeat", None)
        if maybe_heartbeat is not None:
            maybe_heartbeat()

        if self.restore is not None:
            # Optimizer state and non-trainables always come from the snapshot.
            # Elastic workers own their variables, so params are restored too;
            # delta workers re-base onto the restored center (matching the
            # post-commit pull they do mid-run).
            nt = jax.device_put(self.restore["nt"], self.device)
            opt = jax.device_put(self.restore["opt"], self.device)
            if elastic:
                params = jax.device_put(self.restore["params"], self.device)
            else:
                center = self.ps.pull(self.worker_id)
                params = jax.device_put(center, self.device)
        else:
            center = self.ps.pull(self.worker_id)
            params = jax.device_put(center, self.device)
            nt = jax.device_put(self.nt, self.device)
            opt = jax.jit(self.optimizer.init)(params)

        for epoch in range(self.start_epoch, num_epoch):
            order = (
                np.random.default_rng((seed, index, epoch)).permutation(rows)
                if shuffle
                else np.arange(rows)
            )
            for w in range(n_windows):
                if self.fault_plan is not None:
                    # chaos hook: kill-at-window faults fire here, keyed
                    # on the worker's GLOBAL window index (deterministic;
                    # a restarted worker replaying the index survives)
                    self.fault_plan.maybe_kill(
                        self.worker_id, self._windows_done
                    )
                    # deterministic persistent-straggler chaos (ISSUE
                    # 13): the configured worker sleeps here every
                    # window — the commit-skew alert's test subject
                    self.fault_plan.maybe_straggle(self.worker_id)
                sl = order[w * win_rows : (w + 1) * win_rows]
                batches = tuple(
                    c[sl].reshape((self.window, self.batch_size) + c.shape[1:])
                    for c in shard_cols
                )
                batches = jax.device_put(batches, self.device)
                if _trace.enabled():
                    self._t_launch = time.perf_counter()
                params, nt, opt, loss = self.window_fn(params, nt, opt, batches)
                params, center = self._exchange_window(
                    params, center, loss, epoch, elastic
                )
                self._windows_done += 1
                if maybe_heartbeat is not None:
                    maybe_heartbeat()  # rate-limited lease renewal
            if self.barrier is not None and self.ckpt_pred(epoch):
                self.snapshot = {
                    "opt": utils.tree_to_numpy(opt),
                    "nt": utils.tree_to_numpy(nt),
                }
                if elastic:
                    # only elastic workers own their variables; delta workers
                    # re-base onto the restored center, so saving their params
                    # would bloat every checkpoint by W unused model copies
                    self.snapshot["params"] = utils.tree_to_numpy(params)
                self._epoch_done = epoch
                try:
                    self.barrier.wait()  # one thread runs the ckpt action
                except threading.BrokenBarrierError:
                    if not self.tolerant:
                        raise  # fail fast: the driver will raise anyway
                    # a tolerated peer death aborted the rendezvous: keep
                    # training without further checkpoints rather than
                    # dying with it
                    self.barrier = None
        self.final_nt = utils.tree_to_numpy(nt)

    def _exchange_window(self, params, center, loss, epoch: int,
                         elastic: bool):
        """The per-window PS exchange, shared by the fixed-pool and
        elastic loops (one code path for the commit math). Returns the
        re-based ``(params, center)``."""
        if _trace.enabled():
            self._next_corr()
        if elastic:
            # pull a FRESH center at exchange time (reference EASGD
            # semantics), commit the elastic difference, keep own
            # variable moved toward the center — by the TRANSMITTED
            # difference, so worker and center stay symmetric under
            # lossy compression. The commit DEPENDS on the pull here, so
            # the elastic rules cannot ride the fused single-RTT action.
            t0 = time.perf_counter()
            center = self.ps.pull(self.worker_id)
            t0 = self._phase("pull", t0)
            host_params = utils.tree_to_numpy(params)
            t0 = self._phase("fetch", t0)
            if _trace.enabled():
                self._record_compute(t0)
            diff = self.rule.worker_commit(host_params, center)
            blob, sent = self._compress(diff)
            t0 = self._phase("compress", t0)
            self.ps.commit(self.worker_id, blob)
            self._phase("commit", t0)
            params = jax.device_put(
                jax.tree.map(lambda p, d: p - d, host_params, sent),
                self.device,
            )
        else:
            # commit window delta; re-base onto the fresh center — ONE
            # round trip through the fused EXCHANGE action (commit folded
            # and the post-fold center returned together)
            t0 = time.perf_counter()
            delta = self._window_delta(params, center)
            t0 = self._phase("fetch", t0)
            if _trace.enabled():
                self._record_compute(t0)
            blob, _ = self._compress(delta, owned=True)
            self._phase("compress", t0)
            center = self._do_exchange(blob)
            params = jax.device_put(center, self.device)

        with self.lock:
            self.history.append({
                "loss": float(loss),
                "epoch": epoch,
                "worker": self.worker_id,
            })
        return params, center

    def _train_pipelined(self, index, shard_cols, num_epoch, shuffle,
                         seed) -> None:
        """Depth-1 pipelined window loop (ISSUE 10): launch window N+1's
        jitted compute on-device immediately, then perform window N's
        exchange on the host WHILE the device runs — the device→host
        fetch is the only serial cost left; the encode/compress and the
        wire round trip hide behind compute.

        The data flow, per window N (u_N = window N's accumulated local
        update, sent_N its transmitted image under lossy compression):

        - window N+1 starts from ``C_{N-1} + sent_N`` — the freshest
          center in hand (exchange N completes one iteration later) plus
          this window's own update, so every update is committed exactly
          once and the worker's base trails the serial loop's by exactly
          one exchange. For a single DOWNPOUR worker the two coincide
          bit-for-bit (``C_N == C_{N-1} + sent_N`` with fold scale 1 —
          pinned by test).
        - exchange N carries ``lag=True``: the server prices DynSGD τ
          from the PREVIOUS pull version, because u_N was computed from
          the center recorded one exchange earlier — the pipeline's extra
          window of staleness is priced, never hidden.

        Epoch-barrier checkpointing is excluded up front (trainers.py):
        a barrier inside the loop would snapshot with one window still
        un-exchanged."""
        rows = len(shard_cols[0])
        win_rows = self.window * self.batch_size
        n_windows = rows // win_rows
        maybe_heartbeat = getattr(self.ps, "maybe_heartbeat", None)
        if maybe_heartbeat is not None:
            maybe_heartbeat()
        center = self.ps.pull(self.worker_id)
        params = jax.device_put(center, self.device)
        base = utils.tree_to_numpy(center)  # window 1's start, on host
        nt = jax.device_put(self.nt, self.device)
        opt = jax.jit(self.optimizer.init)(params)
        pending = None  # window N's (blob, loss, epoch), exchanged at N+1
        for epoch in range(self.start_epoch, num_epoch):
            order = (
                np.random.default_rng((seed, index, epoch)).permutation(rows)
                if shuffle
                else np.arange(rows)
            )
            for w in range(n_windows):
                if self.fault_plan is not None:
                    self.fault_plan.maybe_kill(
                        self.worker_id, self._windows_done
                    )
                    # deterministic persistent-straggler chaos (ISSUE
                    # 13): the configured worker sleeps here every
                    # window — the commit-skew alert's test subject
                    self.fault_plan.maybe_straggle(self.worker_id)
                sl = order[w * win_rows : (w + 1) * win_rows]
                batches = tuple(
                    c[sl].reshape(
                        (self.window, self.batch_size) + c.shape[1:]
                    )
                    for c in shard_cols
                )
                batches = jax.device_put(batches, self.device)
                # async dispatch: the device starts this window NOW...
                if _trace.enabled():
                    self._t_launch = time.perf_counter()
                params, nt, opt, loss = self.window_fn(
                    params, nt, opt, batches
                )
                if pending is not None:
                    # ...while the host exchanges the PREVIOUS window
                    center = self._flush_pipelined(pending)
                # sync on this window's output; stage the next one
                if _trace.enabled():
                    self._next_corr()
                t0 = time.perf_counter()
                delta = self._window_delta(params, base)
                t0 = self._phase("fetch", t0)
                if _trace.enabled():
                    self._record_compute(t0)
                blob, sent = self._compress(delta, owned=True)
                self._phase("compress", t0)
                base = self._rebase_host(center, sent)
                params = jax.device_put(base, self.device)
                pending = (blob, loss, epoch)
                self._windows_done += 1
                if maybe_heartbeat is not None:
                    maybe_heartbeat()
        if pending is not None:
            self._flush_pipelined(pending)  # drain the last window
        self.final_nt = utils.tree_to_numpy(nt)

    def _flush_pipelined(self, pending):
        """Exchange one deferred window (the pipelined loop's host leg):
        fused commit+pull with the honest-τ ``lag`` flag, then the
        history row — losses land when their window's exchange completes,
        exactly like the serial loop's ordering contract."""
        blob, loss, epoch = pending
        center = self._do_exchange(blob, lag=True)
        with self.lock:
            self.history.append({
                "loss": float(loss),
                "epoch": epoch,
                "worker": self.worker_id,
            })
        return center

    def _train_elastic(self, cols: tuple) -> None:
        """Elastic membership loop (resilience/elastic.py): lease window
        blocks from the shared assigner until the run is out of work, a
        preemption notice drains this worker, or a fault fires.

        The live-join handshake is this method's preamble: ``join`` (the
        wire action — lease admitted, pool/joined counters) followed by
        the first ``pull``, which initializes this worker's server-side
        pull-version so its first DynSGD commit carries the true small τ
        — never the maximal-staleness price a version-less worker would
        pay. The fresh seqno stream comes with the fresh client. Block
        completion is confirmed AFTER the window's commit ACK, so a
        clean drain hands back only genuinely untrained blocks."""
        elastic_rule = isinstance(self.rule, ElasticAverageMerge)
        maybe_heartbeat = getattr(self.ps, "maybe_heartbeat", None)
        if self.joiner:
            join = getattr(self.ps, "join", None)
            if join is not None:
                join()
        if maybe_heartbeat is not None:
            maybe_heartbeat()
        center = self.ps.pull(self.worker_id)
        params = jax.device_put(center, self.device)
        nt = jax.device_put(self.nt, self.device)
        opt = jax.jit(self.optimizer.init)(params)
        drain = self.drain_event
        stop = drain.is_set if drain is not None else None
        try:
            while True:
                if drain is not None and drain.is_set():
                    # preemption notice: in-flight window already
                    # committed and confirmed — exit at the boundary. An
                    # elastic-RULE worker owns its local variable, so a
                    # clean drain first commits the FINAL elastic
                    # difference (ISSUE 10 satellite, PR 9 follow-up):
                    # without it the drained worker's whole uncommitted
                    # progress — everything its variable holds beyond
                    # the center — is silently abandoned mid-epoch.
                    if elastic_rule and self._windows_done > 0:
                        self._commit_final_elastic(params)
                    break
                task = self.assigner.claim(self.worker_id, stop=stop)
                if task is None:
                    break
                epoch, block, idx = task
                if self.fault_plan is not None:
                    self.fault_plan.maybe_kill(
                        self.worker_id, self._windows_done
                    )
                    # deterministic persistent-straggler chaos (ISSUE
                    # 13): the configured worker sleeps here every
                    # window — the commit-skew alert's test subject
                    self.fault_plan.maybe_straggle(self.worker_id)
                batches = tuple(
                    c[idx].reshape(
                        (self.window, self.batch_size) + c.shape[1:]
                    )
                    for c in cols
                )
                batches = jax.device_put(batches, self.device)
                if _trace.enabled():
                    self._t_launch = time.perf_counter()
                params, nt, opt, loss = self.window_fn(
                    params, nt, opt, batches
                )
                params, center = self._exchange_window(
                    params, center, loss, epoch, elastic_rule
                )
                # the commit ACKed (durable when a WAL is on): the block
                # is trained — confirm it before anything can drain us
                self.assigner.complete(self.worker_id, epoch, block)
                self._windows_done += 1
                if maybe_heartbeat is not None:
                    maybe_heartbeat()
                if self.coordinator is not None:
                    # seeded join/preempt chaos rides the same
                    # (worker, completed-window-count) seam as kill_at
                    self.coordinator.on_window(
                        self.worker_id, self._windows_done
                    )
        finally:
            # hand any leased-but-unconfirmed block back — the drain
            # path for clean exits, the safety net for deaths
            self.assigner.release(self.worker_id)
        self.final_nt = utils.tree_to_numpy(nt)

    def _commit_final_elastic(self, params) -> None:
        """Clean-drain EASGD epilogue: pull a fresh center, commit the
        final elastic difference ``α·(worker − center)``, and move the
        local variable by the transmitted image — the same symmetric
        step every window takes, run once more at the exit boundary so
        the center keeps the drained worker's contribution. The
        post-step variable is stashed in ``final_params_`` (the center-
        equivalence test pins ``c + α(w − c)`` against it)."""
        center = self.ps.pull(self.worker_id)
        host_params = utils.tree_to_numpy(params)
        diff = self.rule.worker_commit(host_params, center)
        blob, sent = self._compress(diff)
        self.ps.commit(self.worker_id, blob)
        self.drained_center_ = center
        self.final_params_ = host_params

    def _train_elastic_pipelined(self, cols: tuple) -> None:
        """Depth-1 pipelined elastic loop: the ``_train_pipelined`` data
        flow over assigner-leased window blocks. The exactly-once ledger
        is untouched — a block is confirmed (``assigner.complete``) only
        after its window's exchange ACKs, which the pipeline merely
        DEFERS by one window; a drain or pool-exhaustion exit flushes the
        pending window first, so the clean-drain contract ("finish the
        in-flight window, commit, hand blocks back") holds verbatim."""
        from distkeras_tpu.resilience.elastic import WOULD_BLOCK

        maybe_heartbeat = getattr(self.ps, "maybe_heartbeat", None)
        if self.joiner:
            join = getattr(self.ps, "join", None)
            if join is not None:
                join()
        if maybe_heartbeat is not None:
            maybe_heartbeat()
        center = self.ps.pull(self.worker_id)
        params = jax.device_put(center, self.device)
        base = utils.tree_to_numpy(center)
        nt = jax.device_put(self.nt, self.device)
        opt = jax.jit(self.optimizer.init)(params)
        drain = self.drain_event
        stop = drain.is_set if drain is not None else None
        pending = None  # (blob, loss, epoch, block)
        try:
            while True:
                if drain is not None and drain.is_set():
                    break  # flush below finishes the in-flight window
                task = self.assigner.claim(self.worker_id, stop=stop,
                                           wait=False)
                if task is WOULD_BLOCK:
                    # the pool may be waiting on OUR deferred block:
                    # flush the pending exchange (confirming it), then
                    # claim blocking like the serial loop — the pipeline
                    # degrades to serial exactly at pool starvation
                    if pending is not None:
                        center = self._flush_elastic_pipelined(
                            pending, maybe_heartbeat
                        )
                        pending = None
                    task = self.assigner.claim(self.worker_id, stop=stop)
                if task is None:
                    break
                epoch, block, idx = task
                if self.fault_plan is not None:
                    self.fault_plan.maybe_kill(
                        self.worker_id, self._windows_done
                    )
                    # deterministic persistent-straggler chaos (ISSUE
                    # 13): the configured worker sleeps here every
                    # window — the commit-skew alert's test subject
                    self.fault_plan.maybe_straggle(self.worker_id)
                batches = tuple(
                    c[idx].reshape(
                        (self.window, self.batch_size) + c.shape[1:]
                    )
                    for c in cols
                )
                batches = jax.device_put(batches, self.device)
                if _trace.enabled():
                    self._t_launch = time.perf_counter()
                params, nt, opt, loss = self.window_fn(
                    params, nt, opt, batches
                )
                if pending is not None:
                    center = self._flush_elastic_pipelined(
                        pending, maybe_heartbeat
                    )
                if _trace.enabled():
                    self._next_corr()
                t0 = time.perf_counter()
                delta = self._window_delta(params, base)
                t0 = self._phase("fetch", t0)
                if _trace.enabled():
                    self._record_compute(t0)
                blob, sent = self._compress(delta, owned=True)
                self._phase("compress", t0)
                base = self._rebase_host(center, sent)
                params = jax.device_put(base, self.device)
                pending = (blob, loss, epoch, block)
            if pending is not None:
                self._flush_elastic_pipelined(pending, maybe_heartbeat)
                pending = None
        finally:
            # hand any leased-but-unconfirmed block back — with the
            # pending window flushed above, a clean exit holds none
            self.assigner.release(self.worker_id)
        self.final_nt = utils.tree_to_numpy(nt)

    def _flush_elastic_pipelined(self, pending, maybe_heartbeat):
        """Exchange one deferred elastic window: fused commit+pull with
        the honest-τ lag flag, THEN confirm the block (complete-after-ACK
        — the exactly-once ledger's invariant), then the window-boundary
        hooks (heartbeat, seeded join/preempt chaos) in the serial
        loop's order."""
        blob, loss, epoch, block = pending
        center = self._do_exchange(blob, lag=True)
        with self.lock:
            self.history.append({
                "loss": float(loss),
                "epoch": epoch,
                "worker": self.worker_id,
            })
        # the exchange ACKed (durable when a WAL is on): the block is
        # trained — confirm it before anything can drain us
        self.assigner.complete(self.worker_id, epoch, block)
        self._windows_done += 1
        if maybe_heartbeat is not None:
            maybe_heartbeat()
        if self.coordinator is not None:
            self.coordinator.on_window(self.worker_id, self._windows_done)
        return center


def run_async_training(trainer, ds, shuffle: bool):
    """Drive the PS backend for a DistributedTrainer (reference: the
    ``mapPartitionsWithIndex(worker.train).collect()`` job).

    Returns ``(center_params, nt, history_records)``.
    """
    spec = trainer.spec
    rule = trainer.allocate_merge_rule()
    optimizer = trainer.allocate_optimizer()
    params, nt = spec.init_np(trainer.seed)
    W = trainer.num_workers

    # Elastic membership (resilience/elastic.py): dynamic pool — blocks
    # leased from a shared assigner, live joins, preemption drains, the
    # autoscaler. The fixed-pool machinery (static shards, epoch
    # barriers, restart supervisor) is replaced by the coordinator.
    elastic_mode = bool(getattr(trainer, "elastic", False))

    # Checkpoint/resume (parity with the collective backend): restore the PS
    # center + per-worker (params, opt, nt) saved at an epoch barrier.
    ckpt_dir = getattr(trainer, "checkpoint_dir", None)
    start_epoch = 0
    restores: list[dict | None] = [None] * W
    restored_updates = 0
    if ckpt_dir and elastic_mode and not getattr(trainer, "resume", False):
        import warnings

        warnings.warn(
            "elastic runs do not write epoch-barrier checkpoints (the "
            "barrier assumes a fixed pool); checkpoint_dir is resume-only "
            "under elastic=True",
            stacklevel=2,
        )
    if ckpt_dir and getattr(trainer, "resume", False):
        from distkeras_tpu import checkpoint as ckpt

        if ckpt.latest_step(ckpt_dir) is not None:
            payload, step = ckpt.restore_checkpoint(ckpt_dir)
            saved_workers = payload["workers"]
            params = payload["center"]
            if elastic_mode:
                # elastic resume, always: the pool is dynamic, so the
                # checkpointed center is the model and EVERY worker
                # starts with fresh state from it — the same
                # warn_elastic_resume contract both backends share
                ckpt.warn_elastic_resume(len(saved_workers), W)
            elif len(saved_workers) == W:
                restores = list(saved_workers)
            else:
                # elastic resume (same semantics as the collective
                # backend's): the checkpointed center is the model; the new
                # worker count starts with fresh per-worker state from it
                ckpt.warn_elastic_resume(len(saved_workers), W)
            restored_updates = int(payload.get("num_updates", 0))
            start_epoch = int(payload["epoch"]) + 1

    from distkeras_tpu.parallel.compression import Int8Codec, resolve_codec
    from distkeras_tpu.resilience.retry import ResilientPSClient, RetryPolicy

    transport = getattr(trainer, "ps_transport", "inprocess")
    external_host = getattr(trainer, "ps_host", None)
    offset = int(getattr(trainer, "worker_id_offset", 0))
    # Flight recorder (ISSUE 11): trace=True / trace_dir= turn on the
    # span recorder for this run (idempotent when a caller
    # already enabled it; we only disable what we enabled). The timeline
    # lands in trace_dir as Chrome trace-event JSON, path stashed on
    # trainer.trace_path_.
    trace_dir = getattr(trainer, "trace_dir", None)
    trace_on = bool(getattr(trainer, "trace", False)) \
        or trace_dir is not None
    trace_owner = False
    trainer.trace_path_ = None
    if trace_on and not _trace.enabled():
        _trace.enable(sample=float(getattr(trainer, "trace_sample", 1.0)))
        trace_owner = True
    # the ONE ownership record: trainers._train_ps reads it to release
    # the recorder when this run dies mid-flight (no finally here — the
    # success path below disables and clears it)
    trainer._trace_owner_ = trace_owner
    codec = resolve_codec(getattr(trainer, "compression", None))
    # Resilience knobs (distkeras_tpu/resilience): a retry policy or a
    # heartbeat interval turns the plain transport clients into
    # reconnecting, seqno-deduplicated, lease-renewing wrappers.
    # Pipelined fused exchange (ISSUE 10): depth-1 overlaps each window's
    # exchange with the NEXT window's on-device compute; the fused flag
    # routes commit+pull through the single-RTT EXCHANGE wire action.
    # Both apply to the delta-committing rules only — an elastic-rule
    # commit depends on a fresh pull, so it can neither fuse nor defer.
    pipeline_depth = int(getattr(trainer, "ps_pipeline_depth", 0))
    fused_exchange = bool(getattr(trainer, "ps_fused_exchange", True))
    if pipeline_depth and isinstance(rule, ElasticAverageMerge):
        raise ValueError(
            "ps_pipeline_depth >= 1 applies to the delta-committing "
            "rules (ADAG/DOWNPOUR/DynSGD); the elastic rules pull a "
            "FRESH center before computing their commit, so their "
            "exchange cannot be deferred behind the next window"
        )
    retry_policy = getattr(trainer, "retry_policy", None)
    hb_interval = getattr(trainer, "heartbeat_interval", None)
    resilient = retry_policy is not None or hb_interval is not None
    lease_timeout = getattr(trainer, "lease_timeout", None)
    if lease_timeout is None and hb_interval is not None:
        # a missed-5-heartbeats default: prompt eviction without flapping
        lease_timeout = 5.0 * float(hb_interval)
    fault_plan = getattr(trainer, "fault_plan", None)
    if fault_plan is not None and not elastic_mode \
            and getattr(fault_plan, "has_elastic_events", False):
        raise ValueError(
            "fault_plan carries join/preempt membership events but the "
            "trainer is not elastic — set elastic=True (a fixed-pool run "
            "never consults them, so the chaos would silently test "
            "nothing)"
        )
    # PS durability + failover knobs (resilience/wal.py, DESIGN.md):
    # ps_wal_dir turns on the write-ahead commit log (crash-restart
    # recovery); ps_standby adds a warm replica streaming applied commits;
    # either one (or a kill-PS fault plan) activates the trainer-side
    # PSFailoverSupervisor, which pings the primary and promotes/restarts
    # on a lapsed lease, repointing the workers' endpoint resolver.
    ps_wal_dir = getattr(trainer, "ps_wal_dir", None)
    ps_snapshot_every = int(getattr(trainer, "ps_snapshot_every", 100))
    # group commit (ISSUE 7): >1 batches a window of commits onto one
    # fsync with the ACKs deferred until it lands (durable AND fast); 1 is
    # the PR 5 flush-per-record behavior; 0 is time-bounded async. The
    # interval bounds the durability window in seconds in every mode.
    ps_wal_group_window = int(getattr(trainer, "ps_wal_group_window", 8))
    ps_wal_group_interval = float(
        getattr(trainer, "ps_wal_group_interval", 0.25)
    )
    ps_standby = bool(getattr(trainer, "ps_standby", False))
    ps_failover_timeout = getattr(trainer, "ps_failover_timeout", None)
    if ps_failover_timeout is None:
        ps_failover_timeout = (
            lease_timeout if lease_timeout is not None else 2.0
        )
    kill_ps_chaos = (fault_plan is not None and getattr(
        fault_plan, "kill_ps_after_commits", None) is not None)
    # Membership directory (distkeras_tpu/directory, ISSUE 15): the
    # trainer either HOSTS the replicated coordination service next to
    # the fleet it describes (directory=True — primary + standby +
    # directory failover supervision, every PS endpoint registered with
    # a lease) or DISCOVERS an external fleet through one
    # (ps_directory=seeds). In both modes worker clients are minted
    # from directory lookups — zero endpoint constructor args — and a
    # FencedEpochError or connect failure re-resolves THROUGH the
    # directory, so failover repoints readers without per-worker
    # plumbing and elastic joiners on other hosts find the fleet.
    # (fault plans carrying directory events without directory=True are
    # rejected at trainer construction — see DistributedTrainer)
    directory_on = bool(getattr(trainer, "directory", False))
    dir_seeds = getattr(trainer, "ps_directory", None)
    hosted_directory = None
    external_directory = None
    # Sharded center (distkeras_tpu/sharding, ISSUE 8): partition the
    # param tree across ps_num_shards servers by consistent hashing over
    # leaf paths, with chain replication (ps_chain_length) per shard.
    # ps_chain_length > 1 with ONE shard is the PR 5 standby topology —
    # the sharded wiring subsumes it.
    ps_num_shards = int(getattr(trainer, "ps_num_shards", 1))
    ps_chain_length = int(getattr(trainer, "ps_chain_length", 1))
    sharded = (ps_num_shards > 1 or ps_chain_length > 1) \
        and external_host is None
    shard_supervised = sharded and transport == "socket" and (
        ps_chain_length > 1 or kill_ps_chaos or ps_wal_dir is not None)
    if transport == "socket" \
            and (ps_standby or kill_ps_chaos or shard_supervised
                 or directory_on or dir_seeds is not None) \
            and retry_policy is None:
        # failover is only survivable through reconnecting clients: a
        # plain client dies with the primary's TCP connection. The
        # default policy's 6 attempts span ~1.5 s — tighter than the
        # detect-and-promote window — so the auto policy budgets for
        # (failover_timeout + promotion) with room to spare. Installed
        # whenever no caller-supplied policy exists (a heartbeat-only
        # resilient client would otherwise ride the 6-attempt default
        # into a failover window and die); an explicit retry_policy is
        # trusted to budget for the failover itself.
        resilient = True
        retry_policy = RetryPolicy(
            max_attempts=100, base_delay=0.05, max_delay=0.5,
            deadline=max(60.0, 20.0 * float(ps_failover_timeout)),
        )
    if directory_on:
        import os as _os

        from distkeras_tpu.directory import HostedDirectory

        hosted_directory = HostedDirectory(
            wal_dir=(None if ps_wal_dir is None
                     else _os.path.join(ps_wal_dir, "directory")),
            standby=bool(getattr(trainer, "directory_standby", True)),
            default_ttl=max(2.0 * float(ps_failover_timeout), 1.0),
            failover_timeout=float(ps_failover_timeout),
            fault_plan=fault_plan,
        )
        hosted_directory.start()
    ps_resolver = None
    if resilient and transport == "native" and codec is not None:
        raise ValueError(
            "ps_transport='native' carries commit seqnos on the raw f32 "
            "wire only — drop compression or use ps_transport='socket' "
            "when retry_policy/heartbeat_interval are set"
        )
    # clients validate the value; direct-runner callers without the
    # trainer-constructor check still fail fast in each constructor
    pull_comp = getattr(trainer, "pull_compression", None)
    if codec is not None and transport == "native":
        # exact type, not isinstance: the C++ fold implements the STOCK
        # Int8Codec semantics — silently swapping a subclass's custom
        # encode/decode for them would train with the wrong quantizer
        if type(codec) is not Int8Codec:
            raise ValueError(
                f"ps_transport='native' supports the stock compression="
                f"'int8' only (its C++ fold IS that codec); "
                f"{type(codec).__name__} needs ps_transport='socket'"
            )
        # every float leaf must ride the segmented wire: the flat frame has
        # no raw-passthrough representation for tiny leaves
        codec = Int8Codec(min_size=1)
    if getattr(trainer, "ema_decay", None) is not None \
            and external_host is not None:
        # mirrors the trainer-constructor validation for direct callers
        raise ValueError(
            "ema_decay with an external ps_host must be configured on the "
            "PS owner's server (the center lives there)"
        )
    sharded_group = None
    if sharded:
        # N-shard center: one group object owns the shard servers, their
        # chains, per-shard WAL dirs under ps_wal_dir, and (socket) the
        # per-shard failover supervisors; it quacks like a single PS for
        # everything below (get_model/get_ema/num_updates/stats/stop).
        from distkeras_tpu.sharding import ShardedPSGroup

        sharded_group = ShardedPSGroup(
            params, rule, W, num_shards=ps_num_shards,
            transport=transport,
            ema_decay=getattr(trainer, "ema_decay", None),
            lease_timeout=lease_timeout, wal_root=ps_wal_dir,
            snapshot_every=ps_snapshot_every,
            wal_group_window=ps_wal_group_window,
            wal_group_interval=ps_wal_group_interval,
            chain_length=ps_chain_length,
        )
        sharded_group.initialize()
        sharded_group.start()
        if shard_supervised:
            sharded_group.start_supervision(
                fault_plan=fault_plan if kill_ps_chaos else None,
                failover_timeout=float(ps_failover_timeout),
                directory=hosted_directory,
            )
        elif hosted_directory is not None:
            # no supervisors to renew the leases: register non-expiring
            # entries (discovery still works; nothing ever ages out)
            for _sid, _srv in enumerate(sharded_group.servers):
                hosted_directory.register_shard(
                    _sid, _srv, sharded_group.plan, supervised=False,
                )
        ps = sharded_group

        def make_client(i):
            # a fan-out client per worker: per-shard transport clients
            # (resolver-aware under supervision), each with its OWN seqno
            # stream when resilient — exactly-once is a per-shard property
            return sharded_group.make_client(
                offset + i, pull_compression=pull_comp,
                retry_policy=retry_policy, heartbeat_interval=hb_interval,
                resilient=resilient,
            )
    elif dir_seeds is not None:
        # External fleet discovered through a membership directory
        # (ISSUE 15): no local server and NO endpoint constructor args —
        # the directory seeds are the only bootstrap, the fleet shape
        # (shard count, ring digest) comes from the registrations, and
        # build_client below mints each worker's fully-wired client
        # from a lookup.
        from distkeras_tpu.directory import DirectoryClient, parse_seeds

        ps = None
        external_directory = DirectoryClient(parse_seeds(dir_seeds))
    elif external_host is not None:
        # External PS (another process/host — the reference's driver-hosted
        # PS serving remote executors): this process contributes W workers;
        # the server owner holds the center and the global worker count.
        # checkpoint_dir here snapshots THIS process's worker states plus a
        # pulled center copy; on resume the live PS's center is the truth
        # (workers re-pull it), the saved copy is a disaster-recovery
        # artifact for the PS owner. num_updates stays server-side.
        ps = None
        if transport == "native":
            from distkeras_tpu.native_ps import FlatSpec, NativePSClient

            flat_spec = FlatSpec(params)

            def make_client(i):
                return NativePSClient(
                    external_host, int(getattr(trainer, "ps_port", 0)),
                    offset + i, flat_spec, pull_compression=pull_comp,
                )
        else:
            def make_client(i):
                return ParameterServerClient(
                    external_host, int(getattr(trainer, "ps_port", 0)),
                    offset + i, pull_compression=pull_comp,
                )
    elif transport == "native":
        from distkeras_tpu.native_ps import (
            NativePSClient,
            NativeSocketParameterServer,
        )

        ps = NativeSocketParameterServer(
            params, rule, W, port=getattr(trainer, "ps_port", 0),
            ema_decay=getattr(trainer, "ema_decay", None),
            lease_timeout=lease_timeout,
            # full durability on the native transport too (ISSUE 7): the
            # C++ group-commit WAL writes a log recover_ps_state replays
            # bit-identically — a crashed native PS restarts in place
            wal_dir=ps_wal_dir, snapshot_every=ps_snapshot_every,
            wal_group_window=ps_wal_group_window,
            wal_group_interval=ps_wal_group_interval,
        )
        ps.initialize()
        ps.start()

        def make_client(i):
            return NativePSClient("127.0.0.1", ps.port, i, ps.spec,
                                  pull_compression=pull_comp)
    elif transport == "socket":
        ps = SocketParameterServer(
            params, rule, W, port=getattr(trainer, "ps_port", 0),
            ema_decay=getattr(trainer, "ema_decay", None),
            lease_timeout=lease_timeout,
            wal_dir=ps_wal_dir, snapshot_every=ps_snapshot_every,
            wal_group_window=ps_wal_group_window,
            wal_group_interval=ps_wal_group_interval,
        )
        ps.initialize()
        ps.start()

        if ps_standby or kill_ps_chaos:
            # failover-capable wiring: clients resolve the CURRENT
            # primary (host, port, fencing epoch) per connect, so a
            # promotion repoints every reconnect with no per-worker
            # plumbing — resilience/retry.py PSEndpoint
            from distkeras_tpu.resilience.retry import PSEndpoint

            ps_resolver = PSEndpoint("127.0.0.1", ps.port,
                                     epoch=ps.fence_epoch)

            def make_client(i):
                host, port, epoch = ps_resolver.resolve()
                return ParameterServerClient(
                    host, port, i, pull_compression=pull_comp, epoch=epoch,
                )
        else:
            def make_client(i):
                return ParameterServerClient("127.0.0.1", ps.port, i,
                                             pull_compression=pull_comp)
    elif transport == "shm":
        # shared-memory ring transport (ISSUE 12): zero-syscall,
        # zero-copy exchange for the colocated regime — same protocol,
        # resilience tokens, WAL, and chaos seams as the socket wire,
        # framed over per-worker mmap ring pairs. Colocated-only by
        # construction (trainers.py rejects ps_host with it).
        from distkeras_tpu.shm import ShmParameterServer, ShmPSClient

        ps = ShmParameterServer(
            params, rule, W, ema_decay=getattr(trainer, "ema_decay", None),
            lease_timeout=lease_timeout,
            wal_dir=ps_wal_dir, snapshot_every=ps_snapshot_every,
            wal_group_window=ps_wal_group_window,
            wal_group_interval=ps_wal_group_interval,
        )
        ps.initialize()
        ps.start()

        def make_client(i):
            # any id mints a fresh ring pair — the elastic coordinator
            # builds joiner clients through this factory too
            return ShmPSClient(ps, i, pull_compression=pull_comp)
    elif transport == "inprocess":
        ps = ParameterServer(
            params, rule, W, ema_decay=getattr(trainer, "ema_decay", None),
            lease_timeout=lease_timeout,
            wal_dir=ps_wal_dir, snapshot_every=ps_snapshot_every,
            wal_group_window=ps_wal_group_window,
            wal_group_interval=ps_wal_group_interval,
        )

        def make_client(i):
            return _BoundPS(ps, i, pull_compression=pull_comp)
    else:
        raise ValueError(f"unknown ps_transport {transport!r}")

    # hot standby + trainer-side PS failover supervision (socket only:
    # the in-process PS shares this process's fate, and the native PS
    # degrades to no-WAL — see NativeSocketParameterServer)
    ps_standby_server = None
    ps_supervisor = None
    ps_publish = None
    if hosted_directory is not None and ps is not None \
            and sharded_group is None:
        # single-PS registration: shard 0 of 1. Supervised entries lease
        # out and are renewed by the supervisor's pings; without one the
        # entry is non-expiring (nobody would renew it).
        ps_publish = hosted_directory.register_shard(
            0, ps, None, supervised=(ps_standby or kill_ps_chaos),
        )
    if transport == "socket" and ps is not None and sharded_group is None \
            and (ps_standby or kill_ps_chaos):
        from distkeras_tpu.resilience.recovery import PSFailoverSupervisor

        if ps_standby:
            ps_standby_server = StandbySocketParameterServer(
                params, rule, W,
                ema_decay=getattr(trainer, "ema_decay", None),
                lease_timeout=lease_timeout,
                wal_dir=(None if ps_wal_dir is None
                         else f"{ps_wal_dir}/standby"),
                snapshot_every=ps_snapshot_every,
                wal_group_window=ps_wal_group_window,
                wal_group_interval=ps_wal_group_interval,
            )
            ps_standby_server.initialize()
            ps_standby_server.start()
            for attempt in range(3):
                # a FaultPlan active during setup can drop the attach
                # handshake — the stream is worth a couple of retries
                try:
                    ps.attach_standby("127.0.0.1", ps_standby_server.port)
                    break
                except (ConnectionError, OSError):
                    if attempt == 2:
                        raise

        restart_factory = None
        if ps_wal_dir is not None:
            def restart_factory():
                new = SocketParameterServer(
                    params, rule, W, port=0,
                    ema_decay=getattr(trainer, "ema_decay", None),
                    lease_timeout=lease_timeout,
                    wal_dir=ps_wal_dir, snapshot_every=ps_snapshot_every,
                    wal_group_window=ps_wal_group_window,
                    wal_group_interval=ps_wal_group_interval,
                )
                new.initialize()
                new.start()
                return new

        if kill_ps_chaos:
            # the kill fires IN the commit path (deterministic in commit
            # count — a fast run cannot slip between supervisor polls),
            # tearing in-flight ACKs exactly like a real kill; the
            # supervisor's ping loop then discovers the corpse
            def _kill_hook(version, _ps=ps, _plan=fault_plan):
                if _plan.should_kill_ps(version):
                    _plan.note_ps_kill()
                    _ps._crash()

            ps.post_commit_hook = _kill_hook

        ps_supervisor = PSFailoverSupervisor(
            ps_resolver, ps, standby=ps_standby_server,
            restart_factory=restart_factory,
            failover_timeout=float(ps_failover_timeout),
            publish=ps_publish,
        )
        ps_supervisor.start()

    deploy_streamer = getattr(trainer, "deploy_streamer", None)
    if deploy_streamer is not None:
        # deploy/ (ISSUE 16): hook the serving tier's read replicas onto
        # the live center(s) before any worker folds, so snapshots
        # stream from fold 1. With a hot standby the chain slot is
        # taken — the streamer rides the chain TAIL (standby forwards),
        # keeping failover and serving on one record stream.
        target = sharded_group if sharded_group is not None else (
            ps_standby_server if ps_standby_server is not None else ps)
        if target is None:
            raise ValueError(
                "deploy_streamer= needs a trainer-hosted PS to stream "
                "from (external ps_host / directory-only runs attach "
                "the streamer on the PS owner's side)"
            )
        deploy_streamer.attach_to(target)

    if trace_on:
        # native servers keep their span ring in C++ — arm it (no-op on
        # the Python servers, whose spans record directly)
        _servers = (list(sharded_group.servers)
                    if sharded_group is not None
                    else [ps] if ps is not None else [])
        for _srv in _servers:
            _set = getattr(_srv, "set_trace", None)
            if _set is not None:
                _set(True)

    def build_client(i):
        """One worker's FULLY-WIRED client (any id — the elastic
        coordinator mints clients for live joiners too): the sharded
        fan-out arrives wrapped from the group; otherwise the resilient
        wrapper (reconnect + seqno dedup + heartbeats) goes on here.
        With a directory (hosted or external) EVERY client — initial
        workers and live joiners alike — is minted from a directory
        lookup, zero endpoint constructor args: the PR 9 follow-up
        (joiners on other hosts discover the fleet) by construction."""
        if hosted_directory is not None:
            return hosted_directory.build_worker_client(
                params, offset + i, retry_policy=retry_policy,
                heartbeat_interval=hb_interval,
                pull_compression=pull_comp,
            )
        if external_directory is not None:
            from distkeras_tpu.directory import build_ps_client

            return build_ps_client(
                external_directory, params, offset + i,
                retry_policy=retry_policy,
                heartbeat_interval=hb_interval,
                pull_compression=pull_comp,
            )
        if sharded_group is not None:
            # resilience lives per shard INSIDE the fan-out — see
            # ShardedPSGroup.make_client
            return make_client(i)
        if resilient:
            # reconnect-and-retry with per-worker commit seqnos (dedup'd
            # server-side) + piggyback lease heartbeats — retry.py
            return ResilientPSClient(
                lambda: make_client(i), offset + i,
                policy=retry_policy, heartbeat_interval=hb_interval,
                resolver=ps_resolver,
            )
        return make_client(i)

    clients = [] if elastic_mode else [build_client(i) for i in range(W)]

    cols = trainer.features_col + [trainer.label_col]
    shards = None
    if not elastic_mode:
        shards = ds.worker_shards(
            W, trainer.batch_size, trainer.communication_window, cols,
            seed=trainer.seed if shuffle else None, cover_all=shuffle,
        )  # tuple of [W, rows_pw, …]

    if restored_updates and ps is not None \
            and not getattr(ps, "recovered_", False):
        # WAL recovery is the finer-grained truth; only a checkpoint-
        # resume WITHOUT a recovered WAL seeds the update count
        ps.num_updates = restored_updates

    window_fn = _build_local_window(trainer._loss_step(), optimizer)
    # hogwild threads drive this PROCESS's chips; under jax.distributed the
    # global device list includes devices other controllers own
    devices = jax.local_devices()
    history: list[dict] = []
    hlock = threading.Lock()

    # The watchtower (ISSUE 13): watch=True / watch_dir= / watch_rules=
    # run a background scraper sampling the PS stats surface, per-worker
    # progress, and the training loss into ring-buffered time series,
    # with the declarative watchdog evaluating its alert rules after
    # every scrape. Alerts land in trainer.watch_alerts_ (and the
    # `metrics` wire action, via the server's watchtower attribute);
    # watch_dir= dumps the series + alert ledger as one JSON artifact
    # (path in trainer.watch_path_); watch_hook= fires per transition.
    watch_dir = getattr(trainer, "watch_dir", None)
    watch_rules = getattr(trainer, "watch_rules", None)
    watch_on = (bool(getattr(trainer, "watch", False))
                or watch_dir is not None or watch_rules is not None
                or getattr(trainer, "watch_hook", None) is not None)
    watchtower = None
    trainer.watch_alerts_ = None
    trainer.watch_path_ = None
    trainer.watchtower_ = None
    trainer._watchtower_active_ = None
    if watch_on:
        from distkeras_tpu.observability.timeseries import ps_source
        from distkeras_tpu.observability.watch import Watchtower

        watchtower = Watchtower(
            rules=watch_rules,
            interval=float(getattr(trainer, "scrape_interval", 0.5)),
            hook=getattr(trainer, "watch_hook", None),
        )
        if ps is not None:
            # scrape the ACTIVE server across a failover (the crashed
            # primary's counters freeze; the promoted one's move)
            def _watch_ps(_ps=ps):
                if ps_supervisor is not None:
                    active = getattr(ps_supervisor, "active", None)
                    if active is not None:
                        return active
                return _ps

            watchtower.add_source("ps", ps_source(_watch_ps))
            # the wire-visible alert ledger: every Python-served shard/
            # server carries the one watchtower (the native C++ server
            # has no Python handler loop — its scrape stays CLI-side)
            servers = (list(sharded_group.servers)
                       if sharded_group is not None else [ps])
            for srv in servers:
                if hasattr(srv, "watchtower"):
                    srv.watchtower = watchtower
        watchtower.add_history(history, hlock)
        if trace_on:
            # the analyst's online shadow (ISSUE 14): classify the
            # recorder's recent spans each scrape tick into the
            # analyze.regime_code series — BottleneckShiftRule's input
            from distkeras_tpu.observability.analyze import regime_source

            watchtower.add_source("regime", regime_source())
        # ownership for crash paths (same contract as _trace_owner_):
        # trainers._train_ps stops a scraper the failed run left behind
        trainer._watchtower_active_ = watchtower

    workers: list[AsyncWorker] = []
    barrier = None
    snap_client = None
    ckpt_pred = None
    if ckpt_dir and not elastic_mode:
        from distkeras_tpu import checkpoint as ckpt

        every = int(getattr(trainer, "checkpoint_every", 1))

        def ckpt_pred(epoch, _every=every, _n=trainer.num_epoch):
            return ckpt.should_checkpoint(epoch, _every, _n)

        if ps is None:
            # External PS: the center snapshot must NOT ride a training
            # worker's connection — pull() records that worker's center
            # version server-side, which would understate its DynSGD
            # staleness after every checkpoint. A dedicated client with a
            # sentinel worker id (no commits ever use it) keeps the
            # snapshot read version-neutral for the real workers.
            SNAP_WID = 2**32 - 1
            if external_directory is not None:
                from distkeras_tpu.directory import build_ps_client

                snap_client = build_ps_client(
                    external_directory, params, SNAP_WID,
                    retry_policy=retry_policy,
                )
            elif transport == "native":
                from distkeras_tpu.native_ps import NativePSClient

                snap_client = NativePSClient(
                    external_host, int(getattr(trainer, "ps_port", 0)),
                    SNAP_WID, flat_spec,
                )
            else:
                snap_client = ParameterServerClient(
                    external_host, int(getattr(trainer, "ps_port", 0)),
                    SNAP_WID,
                )

        def _checkpoint_action():
            # runs in one worker thread while all others wait at the barrier;
            # only cadence-selected epochs reach the barrier at all. The
            # update count stays with the server when it is external.
            # Under PS failover the CURRENT primary (supervisor.active)
            # owns the center — the crashed one would serve a stale copy.
            live = (ps_supervisor.active
                    if ps_supervisor is not None else ps)
            epoch = workers[0]._epoch_done
            payload = {
                "center": (live.get_model() if live is not None
                           else snap_client.pull()),
                "workers": [w.snapshot for w in workers],
                "epoch": epoch,
            }
            if live is not None:
                payload["num_updates"] = live.num_updates
            ckpt.save_checkpoint(ckpt_dir, payload, step=epoch)
            # the rendezvous is the run's one coherent epoch boundary:
            # log the REC_EPOCH mark so chained read replicas (deploy/)
            # cut their epoch snapshot at exactly this fold count
            mk = getattr(live if live is not None else snap_client,
                         "mark_epoch", None)
            if mk is not None:
                try:
                    mk(int(epoch))
                except Exception:  # noqa: BLE001
                    pass  # advisory: never fail the checkpoint barrier

        barrier = threading.Barrier(W, action=_checkpoint_action)

    supervisor = None
    coordinator = None
    restart_budget = int(getattr(trainer, "worker_restart_budget", 0))
    if elastic_mode:
        # Elastic pool (resilience/elastic.py): the coordinator owns the
        # worker set — initial workers, live joiners (fault-plan events
        # or the autoscaler), preemption drains against a deadline — and
        # the shared ShardAssigner owns the data: window blocks leased
        # per epoch, confirmed after the window's commit, handed back on
        # drain. Every example trains exactly once per epoch across any
        # clean membership schedule (the oracle in tests/test_elastic).
        from distkeras_tpu.resilience.elastic import (
            ElasticCoordinator,
            ElasticPolicy,
            ShardAssigner,
        )

        cols_full = tuple(np.asarray(ds[c]) for c in cols)

        def _mark_epoch(epoch: int) -> None:
            # elastic epoch boundary (every block of the epoch confirmed):
            # the membership-independent moment the deployer's read
            # replicas cut epoch snapshots at — and, via the snapshot
            # store's checkpoint_dir, the resumable elastic epoch-barrier
            # checkpoint elastic runs never had (ROADMAP item 2 satellite)
            live = (ps_supervisor.active
                    if ps_supervisor is not None else ps)
            mk = getattr(live, "mark_epoch", None)
            if mk is not None:
                try:
                    mk(int(epoch))
                except Exception:  # noqa: BLE001
                    pass  # advisory: a mark must never stall training

        assigner = ShardAssigner(
            len(ds), trainer.communication_window, trainer.batch_size,
            trainer.num_epoch, seed=trainer.seed, shuffle=shuffle,
            start_epoch=start_epoch, on_epoch_complete=_mark_epoch,
        )
        max_pool = getattr(trainer, "max_pool_size", None)
        if max_pool is None:
            max_pool = 2 * W  # joins need headroom; unbounded is a footgun
        target = getattr(trainer, "autoscale_target", None)
        if isinstance(target, ElasticPolicy):
            policy = target
        elif target is not None:
            policy = ElasticPolicy(
                target_rounds_per_sec=float(target),
                max_workers=int(max_pool),
            )
        else:
            policy = None

        def _spawn(worker_id, is_joiner):
            client = build_client(worker_id)
            w = AsyncWorker(
                worker_id, devices[worker_id % len(devices)], window_fn,
                optimizer, client, rule, trainer.communication_window,
                trainer.batch_size, nt, history, hlock,
                tolerant=getattr(trainer, "tolerate_worker_failures",
                                 False),
                codec=codec, fault_plan=fault_plan,
                assigner=assigner, drain_event=threading.Event(),
                coordinator=coordinator, joiner=is_joiner,
                pipeline_depth=pipeline_depth, fused=fused_exchange,
            )
            t = threading.Thread(
                target=w.train,
                args=(worker_id, cols_full, trainer.num_epoch, shuffle,
                      trainer.seed),
                daemon=True, name=f"distkeras-elastic-{worker_id}",
            )
            t.start()
            return w, client, t

        coordinator = ElasticCoordinator(
            assigner, _spawn, make_drain_client=build_client,
            fault_plan=fault_plan, policy=policy,
            drain_timeout=float(
                getattr(trainer, "preempt_drain_timeout", 5.0)
            ),
            max_pool_size=int(max_pool),
            # ONE progress record: the coordinator samples per-worker
            # windows into the watchtower's store (when watching), and
            # the policy observes rates off those series — the same
            # series the commit-skew alert evaluates
            store=watchtower.store if watchtower is not None else None,
        )
        if watchtower is not None:
            # the coordinator's poll loop feeds worker.* at its own
            # cadence; the scraper covers the PS/history/τ series
            watchtower.start()
        coordinator.start(list(range(W)))
        coordinator.run()
        workers = coordinator.all_workers()
        clients = coordinator.all_clients()
    else:
        workers = [
            AsyncWorker(
                i, devices[i % len(devices)], window_fn, optimizer,
                clients[i], rule, trainer.communication_window,
                trainer.batch_size, nt, history, hlock,
                barrier=barrier, ckpt_pred=ckpt_pred,
                restore=restores[i], start_epoch=start_epoch,
                tolerant=getattr(trainer, "tolerate_worker_failures",
                                 False),
                codec=codec, fault_plan=fault_plan,
                pipeline_depth=pipeline_depth, fused=fused_exchange,
            )
            for i in range(W)
        ]

    if watchtower is not None and not elastic_mode:
        # fixed pool: the scraper samples per-worker progress itself
        # (the elastic coordinator's poll loop does it over there)
        from distkeras_tpu.observability.timeseries import progress_source

        # only workers still TRAINING are sampled: a finished worker's
        # flat counter would read as a rate-0 "straggler" to the skew
        # rule, when it is just done (its series ages out of the rate
        # window instead); dead workers likewise stop being progress
        watchtower.add_source("progress", progress_source(
            lambda: {w.worker_id: int(getattr(w, "_windows_done", 0))
                     for w in workers
                     if w.error is None and not hasattr(w, "final_nt")}
        ))
        watchtower.start()

    def _args_of(i):
        return (i, tuple(col[i] for col in shards), trainer.num_epoch,
                shuffle, trainer.seed)

    if elastic_mode:
        pass  # the coordinator already drove the run to completion
    elif restart_budget > 0:
        # restart-with-budget recovery (resilience/recovery.py): a dead
        # worker relaunches from its latest snapshot (or the on-disk
        # checkpoint's entry, or a fresh center pull) up to K times
        from distkeras_tpu.resilience.recovery import WorkerSupervisor

        def _fallback_restore(i):
            if not ckpt_dir:
                return None
            from distkeras_tpu import checkpoint as ckpt

            if ckpt.latest_step(ckpt_dir) is None:
                return None
            payload, _ = ckpt.restore_checkpoint(ckpt_dir)
            saved = payload.get("workers") or []
            return saved[i] if i < len(saved) else None

        supervisor = WorkerSupervisor(
            workers, _args_of, max_restarts=restart_budget,
            restart_delay=float(getattr(trainer, "worker_restart_delay",
                                        0.0)),
            fallback_restore=_fallback_restore,
        )
        supervisor.run()
    else:
        threads = [
            threading.Thread(target=w.train, args=_args_of(i), daemon=True)
            for i, w in enumerate(workers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    # Training is over: retire the PS failover supervisor FIRST (it must
    # not declare the primary dead because we stopped it), then resolve
    # which server actually holds the final center — the original
    # primary, the promoted standby, or the restarted-in-place server.
    active_ps = ps
    if ps_supervisor is not None:
        ps_supervisor.stop()
        active_ps = ps_supervisor.active
        if ps_supervisor.error is not None and not any(
                w.error is not None for w in workers):
            raise RuntimeError(
                "the PS failover supervisor died while the workers "
                "survived"
            ) from ps_supervisor.error
    elif sharded_group is not None and shard_supervised:
        # the group reads per-shard ACTIVE servers itself; only the
        # supervision threads need retiring before the final reads
        sharded_group.stop_supervision()
        sup_err = sharded_group.supervisor_error
        if sup_err is not None and not any(
                w.error is not None for w in workers):
            raise RuntimeError(
                "a shard failover supervisor died while the workers "
                "survived"
            ) from sup_err

    if watchtower is not None:
        # one final synchronous tick (end-of-run counters always land in
        # the series), then publish the ledger — and the one-file
        # timeseries dump when watch_dir= asked for it
        watchtower.stop()
        trainer.watchtower_ = watchtower
        trainer.watch_alerts_ = watchtower.alerts_json()
        if watch_dir is not None:
            import os as _os

            trainer.watch_path_ = watchtower.dump(_os.path.join(
                watch_dir,
                f"ps-watch-{_os.getpid()}-{time.time_ns()}.json",
            ))
        trainer._watchtower_active_ = None

    # Resilience observability, stashed next to ps_stats_: the commit-
    # seqno oracle (logical commits issued vs folds applied — see the
    # chaos tests), client retry/reconnect totals, supervisor restarts,
    # and what the fault plan actually injected.
    trainer.resilience_stats_ = None
    trainer.directory_stats_ = (
        hosted_directory.stats() if hosted_directory is not None else None
    )
    if resilient or supervisor is not None or fault_plan is not None \
            or coordinator is not None:
        trainer.resilience_stats_ = {
            "logical_commits": sum(
                int(getattr(c, "seq", 0)) for c in clients
            ),
            "retries": sum(
                int(getattr(c, "retries", 0)) for c in clients
            ),
            "reconnects": sum(
                int(getattr(c, "reconnects", 0)) for c in clients
            ),
            "restarts": supervisor.stats()["restarts"] if supervisor else 0,
            "faults": fault_plan.stats() if fault_plan is not None else None,
            "ps_failover": (
                ps_supervisor.stats() if ps_supervisor is not None
                else sharded_group.failover_stats()
                if sharded_group is not None and shard_supervised
                else None
            ),
            # elastic membership: joins/drains/timeouts + the assigner's
            # exactly-once ledger (resilience/elastic.py)
            "elastic": (coordinator.stats() if coordinator is not None
                        else None),
            # membership directory (ISSUE 15): registrations, lookups,
            # the directory's OWN failover log, and the final view
            "directory": trainer.directory_stats_,
        }

    def _surfaced_error(w):
        # a timeout-drained worker was given up on — whatever its
        # abandoned thread raised afterward is expected fallout
        # (recorded in the elastic stats), not a run failure
        if coordinator is not None:
            return coordinator.worker_error(w)
        return w.error

    errors = [e for w in workers
              if (e := _surfaced_error(w)) is not None]
    if errors:
        # a BrokenBarrierError is a symptom of a peer's failure — surface the
        # root cause first (and BEFORE any final PS round-trip: a dead
        # external PS must not mask the workers' own errors)
        errors.sort(key=lambda e: isinstance(e, threading.BrokenBarrierError))
        survivors = sum(1 for w in workers if _surfaced_error(w) is None)
        fatal = (not getattr(trainer, "tolerate_worker_failures", False)
                 or survivors == 0)  # tolerated, but nobody survived
        if fatal:
            first = errors[0]
            if supervisor is not None and not isinstance(
                    first, (KeyboardInterrupt, threading.BrokenBarrierError)):
                # the supervisor only leaves a worker dead once its budget
                # is spent — name that, with the last death as the cause
                from distkeras_tpu.resilience.recovery import (
                    RestartBudgetExceeded,
                )

                raise RestartBudgetExceeded(
                    f"worker died past its restart budget "
                    f"({restart_budget} restarts): "
                    f"{type(first).__name__}: {first}"
                ) from first
            raise first
        import warnings

        warnings.warn(
            f"{len(errors)} of {len(workers)} PS workers failed "
            f"({type(errors[0]).__name__}: {errors[0]}); center trained by "
            f"the {survivors} survivors",
            stacklevel=2,
        )

    final_center = None
    if ps is None:
        # external PS: the final center belongs to its owner — take a last
        # snapshot over the wire (bounded: training is done, a stuck server
        # must not hang the driver), leave the server running
        if hasattr(clients[0], "_sock"):
            clients[0]._sock.settimeout(60)
        else:
            clients[0].set_timeout(60.0)  # native client: same bound
        try:
            final_center = clients[0].pull()
        except OSError as e:
            raise RuntimeError(
                f"training finished but the external PS at {external_host} "
                f"stopped answering the final pull: {e}"
            ) from e
    for c in clients:
        c.close()  # in-process close is a no-op; resilient close deregisters
    if snap_client is not None:
        snap_client.close()
    if active_ps is not None:
        # PS hot-path observability: stash the contention/throughput
        # counters (see ParameterServer.stats) on the trainer and stream
        # one JSON line alongside the other metrics when logging is on.
        # Kept OUT of the history: history records are per-worker loss rows
        # and downstream consumers key on their schema. After a failover
        # these are the ACTIVE server's counters (its num_updates spans
        # the whole run — the cross-failover exactly-once oracle; its op
        # counters start at the takeover).
        trainer.ps_stats_ = (
            active_ps.stats() if hasattr(active_ps, "stats") else None
        )
        if trainer.ps_stats_ is not None:
            # per-phase exchange timings (fetch/compress/commit/pull ms
            # histograms, merged across workers): the transport-agnostic
            # proof that the pipelined exchange actually overlapped —
            # with fusion on, `pull` has ZERO samples (2→1 RTTs) and the
            # commit RTT hides behind the next window's compute
            trainer.ps_stats_["exchange_phases"] = \
                aggregate_exchange_phases(workers)
        if trainer.ps_stats_ is not None \
                and getattr(trainer, "log_metrics", False):
            import json
            import sys

            print(json.dumps({"ps_stats": trainer.ps_stats_}),
                  file=sys.stderr, flush=True)
        if trace_on:
            # pull the native C++ span rings into the recorder while the
            # servers are still up (the scrape rides the wire)
            _servers = (list(sharded_group.active_servers)
                        if sharded_group is not None else [active_ps])
            for _srv in _servers:
                _scrape = getattr(_srv, "scrape_trace_events", None)
                if _scrape is not None:
                    try:
                        _trace.add_events(_scrape())
                    except (OSError, ConnectionError):
                        pass  # a crashed native server keeps no ring
        if ps is not None and ps is not active_ps:
            ps.stop()  # the crashed primary: releases any leftovers
        if ps_standby_server is not None \
                and ps_standby_server is not active_ps:
            ps_standby_server.stop()  # warm replica that never took over
        active_ps.stop()
        if getattr(trainer, "ema_decay", None) is not None:
            trainer.ema_params_ = active_ps.get_ema()
    if hosted_directory is not None:
        hosted_directory.stop()
    if external_directory is not None:
        external_directory.close()

    if trace_on and trace_dir is not None:
        import os as _os

        trainer.trace_path_ = _trace.save(_os.path.join(
            trace_dir, f"ps-trace-{_os.getpid()}-{time.time_ns()}.json"
        ))
    trainer.analysis_ = None
    if trace_on and bool(getattr(trainer, "analyze", False)):
        # the analyst (ISSUE 14): strictly post-hoc — the run is over,
        # the recorder still holds every span (native rings already
        # scraped above), the watchtower store contributes its counter
        # series. A diagnosis failure must never fail the run it
        # describes.
        from distkeras_tpu.observability import analyze as _analyze

        try:
            trainer.analysis_ = _analyze.analyze_events(
                _trace.events(), dropped=_trace.live_dropped(),
                store=watchtower.store if watchtower is not None
                else None,
            )
        except Exception as e:  # noqa: BLE001 — diagnosis is best-effort
            import warnings

            warnings.warn(
                f"post-run trace analysis failed "
                f"({type(e).__name__}: {e})", stacklevel=2,
            )
    if trace_owner:
        _trace.disable()
        trainer._trace_owner_ = False

    final_nt = next(
        (w.final_nt for w in workers if hasattr(w, "final_nt")), nt
    )
    return (active_ps.get_model() if active_ps is not None
            else final_center, final_nt, history)


class _BoundPS:
    """In-process client proxy: binds a worker_id to the shared PS object.

    ``pull_compression="int8"`` round-trips the compressed-pull encode/
    decode even though no wire is crossed — it keeps the in-process
    transport a faithful oracle for the socket/native ones (same
    quantization, same server-side error feedback)."""

    def __init__(self, ps: ParameterServer, worker_id: int,
                 pull_compression: str | None = None,
                 epoch: int | None = None):
        from distkeras_tpu.parallel.compression import (
            validate_pull_compression,
        )

        self._ps = ps
        self.worker_id = worker_id
        self.pull_compression = validate_pull_compression(pull_compression)
        # fencing token (parity with ParameterServerClient): None = legacy
        self.epoch = None if epoch is None else int(epoch)

    def pull(self, worker_id: int | None = None):
        from distkeras_tpu.parallel.compression import maybe_decode

        if self.pull_compression == "int8":
            return maybe_decode(self._ps.pull(self.worker_id,
                                              compressed=True))
        return self._ps.pull(self.worker_id)

    def commit(self, worker_id: int | None, payload, seq: int | None = None,
               epoch: int | None = None):
        self._ps.commit(self.worker_id, payload, seq=seq,
                        epoch=self.epoch if epoch is None else epoch)

    def exchange(self, worker_id: int | None, payload,
                 seq: int | None = None, lag: bool = False):
        """Fused commit + pull (ISSUE 10). No wire is crossed, but the
        in-process transport runs the same fused server path (one
        center-lock section, same counters, same int8 round-trip when
        pull_compression is on) so it stays a faithful oracle for the
        socket/native wires."""
        from distkeras_tpu.parallel.compression import maybe_decode

        blob, _applied = self._ps.exchange(
            self.worker_id, payload, seq=seq, epoch=self.epoch, lag=lag,
            compressed=self.pull_compression == "int8",
        )
        return maybe_decode(blob)

    def heartbeat(self, retries: int = 0) -> bool:
        return self._ps.heartbeat(self.worker_id, retries=retries)

    def deregister(self) -> None:
        self._ps.deregister_worker(self.worker_id)

    def join(self) -> dict:
        rec = self._ps.join_worker(self.worker_id)
        rec["ok"] = True
        return rec

    def drain(self, timeout: bool = False) -> None:
        self._ps.drain_worker(self.worker_id, timeout=timeout)

    def close(self):
        pass
