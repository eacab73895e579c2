"""Asynchronous parameter servers — the reference's center-variable semantics.

Parity: reference ``distkeras/parameter_servers.py`` — ``ParameterServer``
base with ``initialize / run / stop / get_model / num_updates``, a socket
service loop (one handler thread per connection, a lock around the center
weights, the self-connect ``cancel_accept`` shutdown trick), and per-algorithm
commit folds (SURVEY.md §2b #11-12, §3.3).

Role in the rebuild: the default path never runs a server — parameter exchange
is a collective. This module exists for the *true-async* mode
(``backend="ps"``): hogwild-style workers (host threads driving their own
chip) pull/commit against a center that folds commits one at a time, exactly
like the reference. The fold math is the SAME ``MergeRule.fold`` used by the
sync lowering, so the unit tests pin both backends to one oracle. The socket
variant is the DCN story: a PS reachable across pod slices.

Staleness is tracked for real here: ``pull`` records the center version a
worker saw; ``commit`` computes τ = center updates since that pull and hands
it to the rule (DynSGD scales by 1/(τ+1); other rules ignore it).

Locking discipline (mirrors ``native/dkps.cpp``; see DESIGN.md):

- ``_lock`` (center lock) protects ``center``/``num_updates``/
  ``_pull_versions`` and the ``_pull_errors`` map itself. Its critical
  sections are O(fold): commit's fold runs under it (each fold REBINDS
  ``center`` to a fresh tree, so the published tree is immutable and acts
  as a copy-on-write snapshot), while pulls only record the version and
  grab the snapshot reference — never an O(model) encode or copy.
- each ``_PullState.lock`` (per-worker residual lock) protects that
  worker's compressed-pull error-feedback residual and scratch; int8
  quantization runs under it, so different workers' compressed pulls
  overlap instead of serializing behind the center.
- ``_ema_lock`` protects the EMA tree; the per-commit EMA fold runs under
  it, fed by the post-fold center snapshot, ordered by center version
  (a fold racing behind a newer one is dropped, not applied stale).
- lock ordering: the center lock is never held while taking a worker or
  EMA lock and vice versa — each section takes exactly one lock, so no
  ordering cycle exists.

``stats()`` exposes contention counters (pulls/commits, bytes moved, center
lock wait/hold ns) — the same counter set ``native/dkps.cpp`` tracks.
"""

from __future__ import annotations

import collections
import pickle
import threading
import time
from typing import Any

import numpy as np

from distkeras_tpu import networking, utils
from distkeras_tpu.observability import trace as _trace
from distkeras_tpu.parallel.compression import is_encoded, maybe_decode
from distkeras_tpu.parallel.merge_rules import MergeRule

Pytree = Any


class _TimedLock:
    """``threading.Lock`` with wait/hold accounting (monotonic ns).

    The counters feed ``ParameterServer.stats()``: mean hold time is the
    review-time proof that the center lock's critical sections stayed
    O(fold). Counter updates happen while the lock is held, so they need no
    extra synchronization; reads from ``stats()`` are approximate (a torn
    read can lag by one in-flight acquire, which is fine for telemetry).
    """

    __slots__ = ("_lock", "acquires", "wait_ns", "hold_ns", "_t_acq")

    def __init__(self):
        self._lock = threading.Lock()
        self.acquires = 0
        self.wait_ns = 0
        self.hold_ns = 0
        self._t_acq = 0

    def acquire(self, blocking: bool = True,
                timeout: float | None = None) -> bool:
        """Timed/non-blocking acquire for the batched fold drain
        (ISSUE 12): only a SUCCESSFUL acquire counts — the whole point
        of batching is that a follower whose fold rode the leader's
        acquisition never touches the lock, and the ``acquires`` counter
        is the observable proof."""
        t0 = time.perf_counter_ns()
        if timeout is None:
            got = self._lock.acquire(blocking)
        else:
            got = self._lock.acquire(blocking, timeout)
        if not got:
            return False
        t1 = time.perf_counter_ns()
        self.wait_ns += t1 - t0
        self.acquires += 1
        self._t_acq = t1
        return True

    def release(self) -> None:
        self.hold_ns += time.perf_counter_ns() - self._t_acq
        self._lock.release()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()


#: follower wake/retry slice for the batched fold drain: a follower whose
#: work is being folded by the current leader wakes the instant its item's
#: event is set; the timeout only bounds the retry cadence when the lock
#: is held by a NON-fold section (a pull snapshot, a fence)
_FOLD_WAIT_SLICE = 0.0005


class _FoldWork:
    """One queued commit/exchange awaiting the batched fold drain
    (ISSUE 12 — see ``ParameterServer._enqueue_and_fold``). Carries the
    pre-lock-encoded inputs in and the locked section's outputs back to
    the submitting thread, which runs every post-lock step (durability
    wait, EMA fold, chaos hook, counters) itself — only the center-lock
    section is combined."""

    __slots__ = (
        "worker_id", "payload", "seq", "epoch", "lag", "fused",
        "compressed", "wire_frame", "rec_payload", "rec_sum", "rec_type",
        "corr", "done", "exc", "fenced", "server_epoch", "dup", "applied",
        "version", "center_snap", "snap_out", "st", "wait_token",
        "snap_state", "batched",
    )

    def __init__(self, worker_id, payload, seq, epoch, lag, fused,
                 compressed, wire_frame, rec_payload, rec_sum, rec_type,
                 corr):
        self.worker_id = worker_id
        self.payload = payload
        self.seq = seq
        self.epoch = epoch
        self.lag = lag
        self.fused = fused
        self.compressed = compressed
        self.wire_frame = wire_frame
        self.rec_payload = rec_payload
        self.rec_sum = rec_sum
        self.rec_type = rec_type
        self.corr = corr
        self.done = threading.Event()
        self.exc: BaseException | None = None
        self.fenced = False
        self.server_epoch = 0
        self.dup = False
        self.applied = False
        self.version = 0
        self.center_snap = None
        self.snap_out = None
        self.st = None
        self.wait_token = None
        self.snap_state = None
        self.batched = False


class _PullState:
    """One worker's compressed-pull state: error-feedback residual plus
    encode scratch, guarded by its OWN lock (mirrors dkps.cpp's per-worker
    ``PullErr`` mutex). Quantization holds this lock — not the center lock —
    so different workers' compressed pulls overlap, while a reconnecting
    client reusing a worker id serializes against the old handler instead
    of racing on the residual. Residual/scratch lists are allocated lazily
    under this lock on the first compressed pull (never under the center
    lock: allocation is O(model))."""

    __slots__ = ("lock", "err", "qf", "epoch")

    def __init__(self):
        self.lock = threading.Lock()
        self.err: list | None = None   # per-leaf f32 residuals (None = exact)
        self.qf: list | None = None    # per-leaf f32 scratch: quantized vals
        self.epoch = 0                 # encode counter: guards late rollbacks


class ParameterServer:
    """In-process center variable with per-algorithm fold semantics.

    Base class of the hierarchy (reference ``ParameterServer``); also directly
    usable as the shared-memory PS for same-process worker threads
    (``ps_transport="inprocess"``).
    """

    def __init__(self, center: Pytree, rule: MergeRule, num_workers: int,
                 ema_decay: float | None = None,
                 lease_timeout: float | None = None,
                 wal_dir: str | None = None, snapshot_every: int = 100,
                 fence_epoch: int = 0, wal_group_window: int = 8,
                 wal_group_interval: float = 0.25):
        from distkeras_tpu.resilience.heartbeat import WorkerRegistry

        self.center = utils.tree_to_numpy(center)
        self.rule = rule
        self.num_workers = int(num_workers)
        self.num_updates = 0
        # Fencing epoch (resilience/wal.py, DESIGN.md "PS durability"):
        # commits carrying an epoch token are folded only when it matches;
        # a mismatch raises FencedEpochError — the mechanism that rejects
        # a superseded history's late folds after a failover promoted a
        # new primary. Epoch-less commits (legacy clients) are never
        # fenced. Guarded by the center lock.
        self.fence_epoch = int(fence_epoch)
        self._n_fenced_commits = 0
        # center lock (timed: stats() reports its wait/hold) — see the
        # module docstring for the full locking discipline
        self._lock = _TimedLock()
        self._pull_versions: dict[int, int] = {}
        # Batched local EXCHANGE (ISSUE 12): commits queue here and are
        # drained in ONE center-lock acquisition by whichever thread
        # holds the lock (flat combining) — K colocated workers' windows
        # fold back-to-back in arrival order inside one lock section.
        # The queue lock is leaf-level: held only for O(1) list ops,
        # never while folding or while any other lock is held.
        self._fold_mu = threading.Lock()
        self._fold_pending: list[_FoldWork] = []
        # The PREVIOUS recorded pull version per worker (ISSUE 10): every
        # pull-version record shifts cur → prev, so prev always holds the
        # version recorded one exchange/pull earlier. A pipelined worker's
        # fused exchange prices DynSGD τ from prev (``lag=True``) because
        # the delta it commits was computed from the center returned one
        # exchange ago — the deliberate one-window staleness the pipeline
        # introduces must be PRICED, not hidden. Guarded by the center
        # lock; reconstructed on replay by the same shift rule.
        self._prev_pull_versions: dict[int, int] = {}
        # Liveness: worker leases renewed by heartbeats (resilience/
        # heartbeat.py). Workers that never heartbeat are never leased, so
        # nothing ever expires — legacy runs see zero overhead/behavior
        # change. Eviction clears the worker's pull version (under the
        # center lock — the registry holds no lock while calling back), so
        # a zombie's post-eviction commit shows DynSGD the FULL center
        # history as its staleness and gets down-weighted to ~nothing.
        self.lease_timeout = (
            30.0 if lease_timeout is None else float(lease_timeout)
        )
        self._registry = WorkerRegistry(
            self.lease_timeout, on_evict=self._on_evict
        )
        # Commit dedup (resilience/retry.py): per-worker last APPLIED
        # seqno; a replayed commit (same worker, seq <= last) is counted,
        # not folded — the lost-ACK retry can never double-fold. Guarded
        # by the center lock (the check is one dict probe, O(1)).
        self._last_seq: dict[int, int] = {}
        self._n_dup_commits = 0
        # Polyak/EMA averaging of the center, updated per commit (the
        # classic async-SGD companion — the EASGD paper evaluates the
        # averaged center). None = off; read with get_ema().
        if ema_decay is not None:
            ema_decay = float(ema_decay)
            if not 0.0 <= ema_decay < 1.0:
                raise ValueError(
                    f"ema_decay must be in [0, 1), got {ema_decay}"
                )
        self.ema_decay = ema_decay
        self._ema = (
            jax_tree_copy(self.center) if ema_decay is not None else None
        )
        # EMA state lives under its OWN lock, fed by the post-fold center
        # snapshot: the O(model) fma never runs under the center lock.
        # _ema_version orders racing folds — a fold that lost the race to a
        # newer center is dropped (its update is subsumed, not applied
        # stale); sequential commits always fold exactly once, in order.
        self._ema_lock = threading.Lock()
        self._ema_version = 0
        # per-leaf scratch reused across EMA folds (no model-sized
        # temporaries per commit); guarded by _ema_lock
        self._ema_scratch = (
            None if self._ema is None
            else _tree_map(np.empty_like, self._ema)
        )
        # per-worker compressed-pull state (error-feedback residual + its
        # lock + encode scratch), created on a worker's first compressed
        # pull — see pull()
        self._pull_errors: dict[int, _PullState] = {}
        # contention/throughput counters behind stats(); the center lock
        # carries its own timing, these cover op counts and bytes. bytes
        # are array payload bytes AS MOVED (encoded size for codec blobs;
        # framing/pickle overhead excluded); raw pulls/commits are costed
        # at the center's size, computed once here (structure is fixed
        # for the server's lifetime).
        self._stats_lock = threading.Lock()
        # Delivered-traffic settling (ISSUE 11): the socket/native wire
        # paths count pull-side traffic only AFTER the reply is fully
        # sent, so a stats read racing the last in-flight reply could
        # lag it. Handlers bracket the send→count window with this
        # gauge; stats() waits for it to reach zero (bounded) before
        # reading — end-of-run counter reads are exact, no ≤1-per-worker
        # tolerance needed. Guarded by _stats_lock.
        self._n_pending_replies = 0
        self._n_pulls = 0
        self._n_compressed_pulls = 0
        self._n_commits = 0
        self._n_fused = 0
        self._n_batched_folds = 0
        self._bytes_in = 0
        self._bytes_out = 0
        # elastic-membership accounting (resilience/elastic.py): the pool
        # gauge starts at the configured worker count; live joins grow
        # it, preemption drains shrink it (clean or deadline-lapsed —
        # the latter also counted in drain_timeouts). Telemetry, not
        # durable state: like the op counters, a recovered server's
        # counts restart while the dedup/lease state replays exactly.
        self._pool_size = int(num_workers)
        self._n_joined = 0
        self._n_preempted = 0
        self._n_drain_timeouts = 0
        # join/drain idempotence (all under _stats_lock): the wire
        # actions ride lossy links, so a lost-ACK replay must not
        # double-count a membership event — same hazard the commit path
        # dedups with seqnos. A wid's join counts once until it drains;
        # its drain counts once until it re-joins; eviction clears both
        # (the sets stay bounded across worker generations).
        self._joined_wids: set[int] = set()
        self._drained_wids: set[int] = set()
        self._t_start = time.monotonic()
        self._center_nbytes = sum(
            np.asarray(l).nbytes for l in _tree_leaves(self.center)
        )
        # -- durability (resilience/wal.py): write-ahead commit log + the
        # hot-standby replication stream. Both sinks receive the SAME
        # framed records, appended/sent inside the center lock so the
        # durable order IS the fold order, and always BEFORE the caller
        # gets its ACK (append-before-ACK is what makes a torn-log commit
        # safely replayable: no ACK went out, the client retries, the
        # recovered dedup table folds it once). The O(model) payload
        # pickle AND its CRC run BEFORE the lock (REC_COMMIT2's split-CRC
        # framing exists exactly so they can); only a buffered append of
        # pre-encoded chunks rides the critical section. With group
        # commit (wal_group_window > 1, the default) the ACK is deferred
        # until the flusher thread lands a whole window of commits on ONE
        # fsync — the replica stream keeps its pre-ACK ordering either
        # way (records are sent under the lock, the ACK only moves
        # later). A standby send failure degrades: the replica is dropped
        # (counted), never wedging the fold path for good.
        self._wal = None
        self.recovered_ = False
        self.wal_replay_s = 0.0
        if wal_dir is not None:
            from distkeras_tpu.resilience.wal import (
                CommitLog,
                recover_ps_state,
            )

            t0 = time.monotonic()
            state = recover_ps_state(
                wal_dir, rule, self.num_workers, self.ema_decay,
                template=self.center,
            )
            if state is not None:
                self._adopt_state(state)
                self.recovered_ = True
                self.wal_replay_s = time.monotonic() - t0
            self._wal = CommitLog(wal_dir, snapshot_every=snapshot_every,
                                  group_window=wal_group_window,
                                  group_interval=wal_group_interval)
            self._wal.open_segment(self.num_updates)
        self._replica_sock = None   # hot-standby stream (attach_standby)
        self._n_standby_drops = 0
        self._snap_pending: dict | None = None
        # chaos seam: called with the post-fold version after every
        # applied commit, OUTSIDE the center lock. The kill-PS fault
        # wiring crashes the server from here — deterministic in commit
        # count (a poll-based kill can miss a fast run entirely), and
        # mid-service, so in-flight ACKs tear exactly like a real kill.
        self.post_commit_hook = None
        # Continuous observability (ISSUE 13): a bounded ring of recent
        # per-commit DynSGD τ samples (appended under the center lock —
        # one O(1) deque append per fold), read by the watchtower's
        # scraper into the ps.tau_p95 series; and the watchtower itself
        # when a trainer/operator attaches one — the `metrics` wire
        # action then carries the alert ledger to remote scrapers.
        self._tau_recent: collections.deque = collections.deque(maxlen=512)
        self.watchtower = None
        # Live-deployment accounting (distkeras_tpu/deploy): the newest
        # center version a read replica MATERIALIZED as a serving
        # snapshot, reported back via report_deploy_version (in-process)
        # or the deploy_report wire action. 0 = nothing deployed yet —
        # stats() then reports deploy_lag_folds as 0, not num_updates,
        # so training-only runs never look behind. Guarded by
        # _stats_lock (monotone max, telemetry not durable state).
        self._deploy_version = 0
        # shard-map handshake record (distkeras_tpu/sharding): when this
        # server holds ONE SHARD of a partitioned center, the group sets
        # {"shard_id", "num_shards", "ring"} here; ping and the
        # "shard_map" action advertise it so a mis-wired client fails
        # fast (ShardMapMismatchError) instead of folding leaves into
        # the wrong shard. None = unsharded (the default).
        self.shard_info: dict | None = None

    def _adopt_state(self, state: dict) -> None:
        """Install a recovered/streamed full state (wal.ps_state_dict
        shape). Callers hold no locks yet (construction / standby apply
        loop)."""
        self.center = state["center"]
        self.num_updates = int(state["num_updates"])
        self._pull_versions = dict(state["pull_versions"])
        self._prev_pull_versions = dict(
            state.get("prev_pull_versions", {})
        )
        self._last_seq = dict(state["last_seq"])
        self.fence_epoch = max(self.fence_epoch, int(state["fence_epoch"]))
        if self.ema_decay is not None and state.get("ema") is not None:
            self._ema = state["ema"]
            self._ema_version = int(state["ema_version"])
            self._ema_scratch = _tree_map(np.empty_like, self._ema)
        self._center_nbytes = sum(
            np.asarray(l).nbytes for l in _tree_leaves(self.center)
        )

    def _capture_state_locked(self) -> dict:
        """Capture the center-side recoverable state — call under the
        center lock. O(workers) dict copies + O(1) refs (the published
        center is an immutable copy-on-write snapshot). The EMA is added
        AFTERWARD by ``_attach_ema_state`` under its own lock (one lock
        at a time — the discipline holds); its version may run ahead of
        the captured center version, which replay handles by skipping
        EMA folds at or below the stored ``ema_version``."""
        from distkeras_tpu.resilience.wal import ps_state_dict

        return ps_state_dict(
            self.center, self.num_updates, self._pull_versions,
            self._last_seq, None, 0, self.fence_epoch,
            prev_pull_versions=self._prev_pull_versions,
        )

    def _attach_ema_state(self, state: dict) -> dict:
        if self._ema is not None:
            with self._ema_lock:
                state["ema"] = jax_tree_copy(self._ema)
                state["ema_version"] = self._ema_version
        return state

    # -- service lifecycle (no-ops for the in-process PS) --------------------

    def initialize(self) -> None:
        pass

    def run(self) -> None:
        pass

    def stop(self) -> None:
        self._close_durability()

    def _close_durability(self) -> None:
        """Flush + close the WAL and the replication stream (clean stop —
        a CRASH, by definition, skips this and leans on the per-record
        flushes)."""
        if self._wal is not None:
            self._wal.close()
        sock = self._replica_sock
        self._replica_sock = None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    # -- the wire actions ----------------------------------------------------

    def pull(self, worker_id: int, compressed: bool = False) -> Pytree:
        """Return current center weights, recording the version seen.

        ``compressed=True`` returns a wire-safe int8 blob instead of the
        raw tree (decode with ``parallel.compression.maybe_decode``): every
        float leaf is absmax-quantized to int8 AFTER adding this worker's
        accumulated quantization residual, and the new residual is kept
        server-side — bidirectional error feedback (DoubleSqueeze, Tang et
        al. 2019), so the stream of decoded pulls telescopes to the true
        center stream even though each individual pull is lossy. Combined
        with int8 commits the PS round-trip moves ~2/8 of the uncompressed
        bytes. Staleness bookkeeping is identical to an exact pull.

        Hot-path structure (the DOWNPOUR lesson — the center lock covers
        only the fold, never O(model) encode/copy work): the center lock
        section is O(1) — record the version and grab the published center
        snapshot (immutable: every commit rebinds ``center`` to a fresh
        tree). The O(model) work — the exact-pull copy, or int8
        quantization against this worker's residual — happens OUTSIDE it,
        quantization under the per-worker residual lock, mirroring the C++
        PULL_INT8 structure in ``native/dkps.cpp``.
        """
        snap, st = self._begin_pull(worker_id, compressed)
        if not compressed:
            out = jax_tree_copy(snap)  # O(model), off the center lock
            self._count(pulls=1, bytes_out=self._center_nbytes)
            return out
        with st.lock:
            blob, nbytes = self._encode_pull(st, snap)
        self._count(compressed_pulls=1, bytes_out=nbytes)
        return blob

    def _begin_pull(self, worker_id: int, compressed: bool) -> tuple:
        """The ONE center-lock pull preamble (shared by ``pull`` and the
        socket wire path, so the staleness/snapshot bookkeeping cannot
        diverge between transports): O(1) — record the version this
        worker saw, grab the immutable center snapshot, and resolve this
        worker's residual state when compressing."""
        with self._lock:
            prev = self._pull_versions.get(worker_id)
            if prev is not None:
                self._prev_pull_versions[worker_id] = prev
            self._pull_versions[worker_id] = self.num_updates
            if self._wal is not None or self._replica_sock is not None:
                # pull versions are recoverable state (DynSGD prices the
                # NEXT commit off them) — a tiny framed record per pull
                from distkeras_tpu.resilience import wal as _wal

                self._log_locked(_wal.encode_record(
                    _wal.REC_PULL, (int(worker_id), int(self.num_updates))
                ))
            snap = self.center
            st = None
            if compressed:
                st = self._pull_errors.get(worker_id)
                if st is None:
                    st = self._pull_errors[worker_id] = _PullState()
        return snap, st

    def _encode_pull(self, st: _PullState, snapshot: Pytree) -> tuple:
        """Quantize ``snapshot + residual`` to int8, updating the residual.

        Runs under the worker's residual lock. The arithmetic is
        bit-identical to the historical under-center-lock encode (same
        add → absmax → divide → rint → dequant-subtract sequence in f32;
        the old clip pass was a provable no-op, see below), but runs in
        preallocated per-worker scratch: one int8 output allocation per
        float leaf instead of ~10 model-sized temporaries, and pulls no
        longer serialize behind the center lock.
        """
        import jax

        from distkeras_tpu.parallel.compression import _LEAF, _MARK

        leaves, treedef = jax.tree.flatten(snapshot)
        if st.err is None:
            st.err = [
                np.zeros(np.shape(l), np.float32)
                if _is_floatish(np.asarray(l)) else None
                for l in leaves
            ]
            st.qf = [None if e is None else np.empty_like(e) for e in st.err]
        enc = []
        nbytes = 0
        for i, leaf in enumerate(leaves):
            arr = np.asarray(leaf)
            err = st.err[i]
            if err is None:
                out = np.copy(arr)  # integer/bool leaves: exact
                enc.append(out)
                nbytes += out.nbytes
                continue
            dt = arr.dtype.name
            if arr.dtype != np.float32:
                arr = arr.astype(np.float32)
            qf = st.qf[i]
            # err doubles as the v = center + residual accumulator: after
            # the add it holds v, and the final subtract turns it back
            # into the new residual — two persistent buffers per worker
            # instead of three keeps the 4-worker working set cache-honest
            np.add(arr, err, out=err)
            amax = (max(float(err.max()), -float(err.min()))
                    if err.size else 0.0)
            scale = amax / 127.0 if amax > 0 else 1.0
            if np.float32(scale) >= np.finfo(np.float32).tiny:
                # fast path (every non-degenerate leaf): no clip pass —
                # with a NORMAL f32 scale ≥ amax/127 up to one rounding,
                # |v/scale| ≤ 127·(1 + ~2⁻²²) < 127.5 for every element,
                # so rint already lands in [-127, 127] and the historical
                # clip is a provable no-op (bit-identical removal)
                np.divide(err, np.float32(scale), out=qf)
                np.rint(qf, out=qf)
                q = qf.astype(np.int8)
                # residual: v − q·scale; qf holds exactly q's values
                np.multiply(qf, np.float32(scale), out=qf)
                np.subtract(err, qf, out=err)
            else:
                # degenerate leaf: amax is so small that f32(scale)
                # underflows to zero or subnormal, where the divide can
                # produce inf (residual-poisoning NaNs downstream) or
                # round past 127.5 (int8 wrap). Keep the historical
                # clipped encode for exactly this case — same observable
                # behavior as the old code (decoded values ≈ 0, the
                # whole magnitude stays in the residual), cost irrelevant
                # at these magnitudes.
                with np.errstate(divide="ignore", invalid="ignore",
                                 over="ignore"):
                    qi = np.clip(np.rint(err / np.float32(scale)),
                                 -127, 127)
                    np.nan_to_num(qi, copy=False, nan=0.0,
                                  posinf=127.0, neginf=-127.0)
                    q = qi.astype(np.int8)
                    np.subtract(
                        err,
                        q.astype(np.float32) * np.float32(scale),
                        out=err,
                    )
            enc.append({_LEAF: "int8", "dt": dt, "q": q, "s": scale})
            nbytes += q.nbytes + 8  # payload + per-leaf scale
        st.epoch += 1  # this encode supersedes any pending late rollback
        return ({_MARK: "int8", "tree": jax.tree.unflatten(treedef, enc)},
                nbytes)

    def commit(self, worker_id: int, payload: Pytree,
               seq: int | None = None, epoch: int | None = None,
               wire_frame: bytes | None = None) -> bool:
        """Fold one worker's commit into the center under the center lock.

        Commits may arrive codec-compressed (``parallel.compression`` —
        int8 / top-k wire blobs); the fold always sees the decoded dense
        tree, so merge-rule semantics are codec-independent. Decode runs
        before the lock and the per-commit EMA fold after it (under the
        EMA lock, against the just-published snapshot) — the center lock's
        critical section is exactly the fold (plus, when durability is on,
        one buffered WAL/replica write of the PRE-pickled record: the
        O(model) pickle runs before the lock).

        ``seq`` (per-worker, monotone, assigned by the resilient client)
        makes the fold exactly-once under retries: a (worker, seq) pair
        already applied is counted as a duplicate and skipped — the
        retried-after-lost-ACK commit never double-folds. ``seq=None``
        (legacy callers) keeps at-most-once-per-call semantics.

        ``epoch`` is the client's fencing token: a mismatch against
        ``fence_epoch`` raises :class:`~distkeras_tpu.networking.
        FencedEpochError` WITHOUT folding — the late commit of a zombie
        primary's worker (or a fenced server's client) is rejected, never
        silently absorbed into a superseded history. ``epoch=None``
        (legacy clients) is never fenced.

        Returns True when the commit folded, False when it was a
        duplicate.
        """
        applied, _snap, _st = self._commit_impl(
            worker_id, payload, seq=seq, epoch=epoch,
            wire_frame=wire_frame,
        )
        return applied

    def exchange(self, worker_id: int, payload: Pytree,
                 seq: int | None = None, epoch: int | None = None,
                 lag: bool = False, compressed: bool = False,
                 wire_frame: bytes | None = None) -> tuple:
        """Fused commit + pull — ONE call (one wire round trip on the
        socket/native transports) that folds this worker's commit and
        returns the fresh post-fold center, halving the per-window
        exchange cost of the classic ``commit(); pull()`` pair.

        Semantics are exactly the pair's, executed atomically under one
        center-lock section: the fold is priced with the same τ a
        standalone commit would see, then the pull version is recorded at
        the post-fold ``num_updates`` and the published snapshot grabbed.
        A duplicate (replayed ``seq``) skips the fold but still performs
        the pull half — a lost-ACK replay gets a fresh center and records
        its version exactly as a retried ``pull`` would, and can never
        double-fold or advance ``num_updates`` twice. A fenced exchange
        raises without folding or pulling.

        ``lag=True`` (the pipelined worker) prices τ from the PREVIOUS
        recorded pull version: the committed delta was computed from the
        center returned one exchange ago, and DynSGD must see that extra
        window of staleness (see ``_prev_pull_versions``).

        Returns ``(weights_or_blob, applied)`` — the raw center copy, or
        the int8 error-feedback blob when ``compressed=True``.
        """
        applied, snap, st = self._commit_impl(
            worker_id, payload, seq=seq, epoch=epoch, lag=lag,
            fused=True, compressed=compressed, wire_frame=wire_frame,
        )
        if not compressed:
            out = jax_tree_copy(snap)  # O(model), off the center lock
            self._count(pulls=1, bytes_out=self._center_nbytes, fused=1)
            return out, applied
        with st.lock:
            blob, nbytes = self._encode_pull(st, snap)
        self._count(compressed_pulls=1, bytes_out=nbytes, fused=1)
        return blob, applied

    def _commit_impl(self, worker_id: int, payload: Pytree,
                     seq: int | None = None, epoch: int | None = None,
                     wire_frame: bytes | None = None, fused: bool = False,
                     lag: bool = False, compressed: bool = False) -> tuple:
        """The shared commit pipeline behind ``commit`` and ``exchange``:
        decode → off-lock durable encode → fold (+ fused pull
        bookkeeping) under the center lock **via the batched drain** →
        deferred-ACK durability wait → EMA fold. Returns ``(applied,
        snap, st)``; ``snap``/``st`` are the fused pull's center snapshot
        and per-worker residual state (None unless ``fused``). Counts the
        COMMIT-side stats only — the caller counts the pull side once the
        reply is actually delivered (socket/shm) or materialized
        (in-process).

        Batched local exchange (ISSUE 12): the locked section is no
        longer entered per commit. Each commit enqueues a
        :class:`_FoldWork` and the drain in ``_enqueue_and_fold`` folds
        every queued window in ONE center-lock acquisition, in arrival
        order — bit-identity is preserved because folds are
        order-dependent but the drain applies the SAME serialized
        arrival order the per-commit lock would have imposed, and each
        worker still gets its own post-fold snapshot, DynSGD τ, seqno
        dedup verdict, and WAL record. Everything after the lock (chaos
        hook, group-commit durability wait, EMA fold, snapshot publish)
        runs in the submitting thread, exactly as before."""
        import zlib as _zlib

        from distkeras_tpu.resilience import wal as _wal

        nbytes = self._payload_nbytes(payload)  # wire size: BEFORE decode
        with _trace.span("ps.decode"):
            payload = maybe_decode(payload)
        rec_payload = None
        rec_sum = 0
        rec_type = _wal.REC_COMMIT2
        if self._wal is not None or self._replica_sock is not None:
            # durable sinks replay the EXACT fold input: coerce to numpy
            # once (workers already send numpy trees; this is a no-op
            # pass), then encode AND checksum OUTSIDE the lock — the
            # whole O(model) work happens here, in this worker's handler
            # thread (the PR 3 per-worker discipline), so different
            # workers' encodes overlap instead of serializing behind the
            # center. The fold below uses the same coerced tree (and the
            # wire-frame replay re-runs this same decode pipeline), so
            # replay is bit-identical either way.
            payload = utils.tree_to_numpy(payload)
            if wire_frame is not None:
                # socket/shm pickle lane: the request frame's bytes are
                # already in hand — log them verbatim, no re-pickle pass
                rec_payload = wire_frame
                rec_type = _wal.REC_COMMIT_WIRE
            else:
                rec_payload = pickle.dumps(
                    payload, protocol=pickle.HIGHEST_PROTOCOL
                )
            rec_sum = _zlib.adler32(rec_payload)
        work = _FoldWork(
            worker_id, payload, seq, epoch, lag, fused, compressed,
            wire_frame, rec_payload, rec_sum, rec_type,
            _trace.current_corr() if _trace.enabled() else None,
        )
        self._enqueue_and_fold(work)
        if work.exc is not None:
            raise work.exc
        if work.fenced:
            # the payload still crossed the wire: count its bytes (the
            # native server does — stats parity), just not a commit
            self._count(bytes_in=nbytes)
            raise networking.FencedEpochError(
                "commit fenced: a newer primary holds this history",
                client_epoch=epoch, server_epoch=work.server_epoch,
            )
        if work.dup:
            self._count(dup_commits=1, bytes_in=nbytes)
            return False, work.snap_out, work.st
        self._count(commits=1, bytes_in=nbytes,
                    batched_folds=1 if work.batched else 0)
        hook = self.post_commit_hook
        if hook is not None:
            # chaos seam, deliberately BEFORE the durability wait: a
            # kill-PS fault here crashes the server with this commit
            # appended but its group not yet flushed — the torn-GROUP
            # case the recovery tests pin (every unACKed commit in the
            # lost window replays and folds exactly once)
            hook(work.version)
        if self._wal is not None:
            if work.wait_token is not None and self._wal.group_mode:
                # group commit: the ACK this return releases must imply
                # fsync'd — block until the flusher lands our window. A
                # failed wait (the log was abandoned by a crash/IO error,
                # or timed out) means this commit is NOT durable: refuse
                # to ACK it — the retryable error tears the caller's
                # connection (the C++ handler breaks the same way), the
                # client replays, and the dedup table on whatever server
                # answers next folds it at most once.
                with _trace.span("ps.wal_wait"):
                    durable = self._wal.wait_durable(work.wait_token)
                if not durable:
                    raise networking.ProtocolError(
                        "commit folded but its WAL group never became "
                        "durable (log abandoned or fsync stalled) — "
                        "no ACK; replay it", retryable=True,
                    )
            else:
                self._wal.maybe_fsync()  # periodic, off the critical path
        if self._ema is not None:
            d = self.ema_decay
            version = work.version
            snap = work.center_snap

            def fma(e, c, s):
                np.multiply(np.asarray(c, dtype=e.dtype), 1.0 - d, out=s)
                e *= d
                e += s

            with self._ema_lock:
                # version-ordered: if a concurrent commit already folded a
                # NEWER center, this fold is subsumed — dropping it keeps
                # the EMA a well-formed average of center snapshots instead
                # of applying an older center after a newer one.
                if version > self._ema_version:
                    self._ema_version = version
                    _tree_map(fma, self._ema, snap, self._ema_scratch)
        if work.snap_state is not None and self._wal._fh is not None:
            self._attach_ema_state(work.snap_state)
            self._wal.publish_snapshot(work.snap_state)
        return True, work.snap_out, work.st

    def _enqueue_and_fold(self, work: _FoldWork) -> None:
        """The batched fold drain (ISSUE 12, flat combining): enqueue,
        then either become the leader — acquire the center lock ONCE and
        fold EVERY queued commit in arrival order — or wait for the
        current leader to fold ours. A follower whose window rode the
        leader's drain never acquires the center lock at all: at K
        colocated workers the lock is acquired < once per fold
        (``batched_folds`` / ``center_lock_acquires`` in stats are the
        observable claim). Arrival order is the queue's append order —
        the same serialized order the per-commit lock would have
        imposed, so batched and serial folds are bit-identical (pinned
        by test)."""
        t0 = time.perf_counter_ns()
        with self._fold_mu:
            self._fold_pending.append(work)
        while True:
            # fast path / leader election: non-blocking, so an
            # uncontended commit pays nothing over the old direct lock
            if self._lock.acquire(blocking=False):
                try:
                    with self._fold_mu:
                        batch = self._fold_pending
                        self._fold_pending = []
                    if batch:
                        self._drain_folds_locked(batch)
                finally:
                    self._lock.release()
                # any drain that ran since our enqueue — ours or an
                # earlier leader's — necessarily included our work
                return
            # a leader (or a pull) holds the lock: wake the instant our
            # item completes, re-contend on the slice timeout otherwise
            if work.done.wait(timeout=_FOLD_WAIT_SLICE):
                # keep the contention signal honest: pre-batching,
                # commit queueing showed up as center-lock wait; a
                # follower never acquires, so its time-to-fold is
                # credited to wait_ns here (unsynchronized add — the
                # telemetry counters are documented approximate)
                self._lock.wait_ns += time.perf_counter_ns() - t0
                return

    def _drain_folds_locked(self, batch: list[_FoldWork]) -> None:
        """Fold one drained batch — call holding the center lock. Every
        item is processed (its ``done`` event always set) and exceptions
        are carried per item to the submitting thread; K folds under one
        acquisition show as ``batched_folds`` in stats."""
        batched = len(batch) >= 2
        for work in batch:
            work.batched = batched
            self._fold_one_locked(work)

    def _fold_one_locked(self, work: _FoldWork) -> None:
        """One commit's center-lock section (the body the per-commit
        lock used to run), operating on a :class:`_FoldWork` — call
        holding the center lock. Always sets ``work.done``."""
        import zlib as _zlib

        from distkeras_tpu.resilience import wal as _wal

        t0 = time.perf_counter_ns()
        worker_id = work.worker_id
        try:
            fenced = (work.epoch is not None
                      and work.epoch != self.fence_epoch)
            work.server_epoch = self.fence_epoch
            dup = False
            if not fenced and work.seq is not None:
                if work.seq <= self._last_seq.get(worker_id, 0):
                    dup = True
                else:
                    self._last_seq[worker_id] = work.seq
            if not fenced and not dup:
                if work.lag and worker_id in self._prev_pull_versions:
                    # pipelined exchange: the delta was computed from the
                    # center returned one exchange AGO — price τ from the
                    # previous recorded pull version, not the current one
                    pull_version = self._prev_pull_versions[worker_id]
                else:
                    pull_version = self._pull_versions.get(worker_id, 0)
                staleness = self.num_updates - pull_version
                self._tau_recent.append(int(staleness))
                self.center = utils.tree_to_numpy(
                    self.rule.fold(
                        self.center, work.payload, self.num_workers,
                        staleness,
                    )
                )
                self.num_updates += 1
                work.version = self.num_updates
                work.center_snap = self.center
                if work.rec_payload is None and (
                        self._wal is not None
                        or self._replica_sock is not None):
                    # an attach_standby raced in between the pre-lock
                    # sink check and this fold: encode here (O(model)
                    # under the lock, but only for the one commit that
                    # straddles the attach) so the stream never misses a
                    # fold the attach-time base state didn't include
                    if work.wire_frame is not None:
                        work.rec_payload = work.wire_frame
                        work.rec_type = _wal.REC_COMMIT_WIRE
                    else:
                        work.payload = utils.tree_to_numpy(work.payload)
                        work.rec_payload = pickle.dumps(
                            work.payload,
                            protocol=pickle.HIGHEST_PROTOCOL,
                        )
                    work.rec_sum = _zlib.adler32(work.rec_payload)
                if work.rec_payload is not None:
                    # O(1) under the lock: frame the pre-encoded payload
                    # (split-checksum commit — the header hashes only the
                    # 32-byte prefix) and queue the chunk REFS (bytes are
                    # immutable: no copy, no I/O, inside the lock)
                    work.wait_token = self._log_commit_locked(
                        worker_id, work.seq, pull_version, work.version,
                        work.rec_payload, work.rec_sum, work.rec_type,
                        corr=work.corr,
                    )
                if self._wal is not None and self._wal.should_snapshot():
                    # phase 1 under the lock: rotate the segment at this
                    # exact version and capture the center-side state;
                    # the O(model) serialize+fsync publish runs after the
                    # lock in the submitting thread (and after its EMA
                    # fold, so the snapshot's EMA never trails its center)
                    self._wal.rotate(self.num_updates)
                    work.snap_state = self._capture_state_locked()
            if work.fused and not fenced:
                # the fused pull half — applied AND duplicate commits get
                # it (a lost-ACK replay still needs the fresh center, and
                # recording its version is exactly what a retried pull
                # would do): shift cur → prev, record the post-fold
                # version, grab the immutable snapshot — O(1), the same
                # bookkeeping as _begin_pull
                prev = self._pull_versions.get(worker_id)
                if prev is not None:
                    self._prev_pull_versions[worker_id] = prev
                self._pull_versions[worker_id] = self.num_updates
                if self._wal is not None or self._replica_sock is not None:
                    self._log_locked(_wal.encode_record(
                        _wal.REC_PULL,
                        (int(worker_id), int(self.num_updates)),
                    ))
                work.snap_out = self.center
                if work.compressed:
                    st = self._pull_errors.get(worker_id)
                    if st is None:
                        st = self._pull_errors[worker_id] = _PullState()
                    work.st = st
            if fenced:
                self._n_fenced_commits += 1
            work.fenced = fenced
            work.dup = dup
            work.applied = not fenced and not dup
        except BaseException as e:  # carried to the submitting thread
            work.exc = e
        finally:
            if _trace.enabled():
                # per-fold span with the COMMIT'S correlation id (the
                # leader's thread corr would mislabel followers' folds)
                _trace.record("ps.fold", t0, time.perf_counter_ns(),
                              corr=work.corr)
            work.done.set()

    def _log_commit_locked(self, worker_id: int, seq: int | None,
                           pull_version: int, version: int,
                           rec_payload: bytes, rec_sum: int,
                           rec_type: int,
                           corr: str | None = None) -> int | None:
        """Hand one commit record to every durable sink — call under the
        center lock (durable order == fold order; record-before-ACK).
        The payload bytes and their checksum were computed OFF the lock;
        this frames and queues pre-encoded chunks without ever copying or
        hashing the O(model) payload. Returns the WAL durability token
        (None without a WAL). ``corr`` is the commit's correlation id —
        under the batched fold drain the executing thread may be another
        commit's leader, so the span must carry the item's id, not the
        thread's."""
        from distkeras_tpu.resilience import wal as _wal

        with _trace.span("ps.wal_append", corr=corr):
            chunks = _wal.encode_commit_chunks(
                worker_id, seq, pull_version, version, rec_payload,
                rec_sum, rec_type=rec_type,
            )
            token = None
            if self._wal is not None:
                token = self._wal.append_chunks(chunks)
                self._wal.commits_since_snapshot += 1
            sock = self._replica_sock
            if sock is not None:
                try:
                    for chunk in chunks:
                        sock.sendall(chunk)
                except OSError:
                    self._replica_sock = None
                    self._n_standby_drops += 1
                    try:
                        sock.close()
                    except OSError:
                        pass
        return token

    def _log_locked(self, rec: bytes) -> None:
        """Hand one framed NON-commit record to every durable sink — call
        under the center lock (durable order == fold order). The WAL
        write is buffered; the replica send lands in the kernel socket
        buffer (a primary crash still flushes it — semi-sync
        replication). A replica send failure degrades to running without
        the standby instead of wedging the fold path."""
        if self._wal is not None:
            self._wal.append(rec)
        sock = self._replica_sock
        if sock is not None:
            try:
                sock.sendall(rec)
            except OSError:
                self._replica_sock = None
                self._n_standby_drops += 1
                try:
                    sock.close()
                except OSError:
                    pass

    def get_model(self) -> Pytree:
        with self._lock:
            snap = self.center
        return jax_tree_copy(snap)  # snapshot is immutable; copy off-lock

    # -- liveness (leases + heartbeats; resilience/heartbeat.py) -------------

    def heartbeat(self, worker_id: int, retries: int = 0) -> bool:
        """Renew (auto-registering) ``worker_id``'s lease; ``retries`` is
        the client's cumulative retry count, surfaced in ``stats()``.
        Returns False when this heartbeat (re-)registered the worker —
        i.e. it was unknown or had been evicted."""
        return self._registry.renew(worker_id, retries=retries)

    def deregister_worker(self, worker_id: int) -> None:
        """Clean worker exit: drop the lease without counting an eviction,
        and retire the commit-seqno fence (a future client for this worker
        id starts a fresh epoch; keeping the fence would only grow the
        map). The pull-version slots (cur AND prev) retire too: every
        worker loop pulls before committing, so a same-id successor never
        reads the dead generation's cur — but the successor's first pull
        would SHIFT a surviving cur into prev, and its first pipelined
        (lag-priced) exchange would then be priced from the dead
        generation's version instead of its own fresh pull."""
        self._registry.deregister(worker_id)
        with self._lock:
            self._last_seq.pop(worker_id, None)
            self._pull_versions.pop(worker_id, None)
            self._prev_pull_versions.pop(worker_id, None)
            if self._wal is not None or self._replica_sock is not None:
                from distkeras_tpu.resilience import wal as _wal

                self._log_locked(
                    _wal.encode_record(_wal.REC_DEREG, (int(worker_id),))
                )

    # -- elastic membership (resilience/elastic.py) --------------------------

    def join_worker(self, worker_id: int) -> dict:
        """Live-join admission: lease the worker (quietly — ``heartbeats``
        stays a pure heartbeat count) and grow the pool gauge. The
        joiner's very next ``pull`` records its pull-version, so its
        first DynSGD commit is priced at the true small τ. Returns the
        admission record the wire action answers with."""
        self._registry.register(worker_id)
        with self._stats_lock:
            self._drained_wids.discard(worker_id)
            if worker_id not in self._joined_wids:
                # a lost-ACK replay of the join must not double-count
                self._joined_wids.add(worker_id)
                self._n_joined += 1
                self._pool_size += 1
            pool = self._pool_size
        with self._lock:
            updates = self.num_updates
        return {"pool_size": pool, "num_updates": updates}

    def drain_worker(self, worker_id: int, timeout: bool = False) -> None:
        """Preemption drain: a clean deregister (lease dropped without an
        eviction, dedup seqno retired through the PR 5 bounded-table
        path) plus the elastic counters — ``timeout=True`` records a
        drain whose deadline lapsed (the force-drain path; eviction
        remains the backstop for the abandoned worker)."""
        self.deregister_worker(worker_id)
        with self._stats_lock:
            if worker_id in self._drained_wids:
                return  # lost-ACK replay: this drain already counted
            self._drained_wids.add(worker_id)
            self._joined_wids.discard(worker_id)
            self._n_preempted += 1
            if timeout:
                self._n_drain_timeouts += 1
            self._pool_size = max(0, self._pool_size - 1)

    def _on_evict(self, worker_ids: list[int]) -> None:
        """Lease expiry → forget the workers' pull versions, so DynSGD
        treats any zombie commit as maximally stale (τ = num_updates) —
        and retire their commit-dedup entries too, so elastic runs with
        many worker generations never grow ``_last_seq`` without bound.
        (The dedup loss is safe in practice: a replayed commit surviving
        past a whole lease timeout re-folds priced at maximal τ; the
        eviction/commit-race test pins that pricing.)"""
        with self._lock:
            for wid in worker_ids:
                self._pull_versions.pop(wid, None)
                self._prev_pull_versions.pop(wid, None)
                self._last_seq.pop(wid, None)
            if self._wal is not None or self._replica_sock is not None:
                from distkeras_tpu.resilience import wal as _wal

                self._log_locked(_wal.encode_record(
                    _wal.REC_EVICT, ([int(w) for w in worker_ids],)
                ))
        with self._stats_lock:
            # membership hygiene: an evicted wid's join/drain idempotence
            # records retire with it (a returning worker re-registers),
            # keeping the sets bounded under long elastic churn
            for wid in worker_ids:
                self._joined_wids.discard(wid)
                self._drained_wids.discard(wid)

    def fence(self, epoch: int) -> int:
        """Raise the fencing epoch (monotone): commits carrying an older
        token are rejected from here on. Called on a superseded primary by
        the promoting supervisor (best effort — a dead primary needs no
        fencing) and on a recovered/promoted server to stamp its new
        history. Durable before returning when a WAL is attached."""
        with self._lock:
            self.fence_epoch = max(self.fence_epoch, int(epoch))
            out = self.fence_epoch
            if self._wal is not None or self._replica_sock is not None:
                from distkeras_tpu.resilience import wal as _wal

                self._log_locked(
                    _wal.encode_record(_wal.REC_FENCE, (out,))
                )
        if self._wal is not None:
            self._wal.sync()  # the fence ack implies durability
        return out

    def mark_epoch(self, epoch: int) -> None:
        """Log a training-epoch boundary into the WAL/replication stream
        (REC_EPOCH). Ordered against the folds by the center lock, so a
        read replica sees the mark at EXACTLY the fold count the barrier
        observed — the deployer's epoch-boundary snapshot cut. Cheap
        no-op when neither a WAL nor a replica stream is attached."""
        with self._lock:
            if self._wal is not None or self._replica_sock is not None:
                from distkeras_tpu.resilience import wal as _wal

                self._log_locked(
                    _wal.encode_record(_wal.REC_EPOCH, (int(epoch),))
                )

    def report_deploy_version(self, version: int) -> None:
        """A read replica reports the newest center version it published
        as a serving snapshot (monotone max; see deploy/stream.py)."""
        with self._stats_lock:
            self._deploy_version = max(self._deploy_version, int(version))

    def attach_standby(self, host: str, port: int,
                       timeout: float = 10.0) -> None:
        """Connect the hot-standby replication stream: send the replica a
        full state snapshot, then stream every subsequent record (commit /
        pull / dereg / evict / fence) before the corresponding ACK goes
        out. Call BEFORE serving traffic — attaching mid-stream can leave
        the replica's EMA behind by in-flight post-lock EMA folds (the
        center itself is always exact)."""
        state = self._attach_ema_state({})  # EMA first: see docstring
        sock = networking.connect(host, int(port), timeout=timeout)
        sock.settimeout(timeout)
        with self._lock:
            base = self._capture_state_locked()
            base["ema"] = state.get("ema")
            base["ema_version"] = state.get("ema_version", 0)
            networking.send_data(
                sock, {"action": "replicate_stream", "state": base}
            )
            reply = networking.recv_data(sock)
            if not reply.get("ok"):
                sock.close()
                raise ConnectionError(
                    f"standby at {host}:{port} refused the replication "
                    f"stream: {reply}"
                )
            self._replica_sock = sock
        sock.settimeout(5.0)  # per-record send bound: a wedged standby
        # must cost at most one bounded stall before being dropped

    @property
    def has_standby(self) -> bool:
        return self._replica_sock is not None

    def get_ema(self) -> Pytree:
        """The Polyak-averaged center (None unless ``ema_decay`` was set)."""
        if self._ema is None:
            return None
        with self._ema_lock:
            # the EMA tree is folded in place, so the copy must stay under
            # its lock (unlike the copy-on-write center)
            return jax_tree_copy(self._ema)

    def _rollback_encode_locked(self, st: _PullState, snapshot: Pytree,
                                blob: dict) -> None:
        """Undo one ``_encode_pull``'s residual advance (call under
        ``st.lock``, with the SAME snapshot the encode saw): the blob was
        never delivered, so the EF stream must not account for it.
        Restores ``err_old = v − c`` from ``err = v − s·q`` (mirrors the
        dkps.cpp PULL_INT8 send-failure rollback). Error path only — the
        per-element temporaries here don't matter."""
        import jax

        from distkeras_tpu.parallel.compression import _LEAF

        enc_leaves = jax.tree.flatten(
            blob["tree"],
            is_leaf=lambda x: isinstance(x, dict) and _LEAF in x,
        )[0]
        snap_leaves = jax.tree.flatten(snapshot)[0]
        for i, (enc, c) in enumerate(zip(enc_leaves, snap_leaves)):
            err = st.err[i]
            if err is None:
                continue
            dq = np.multiply(enc["q"], np.float32(enc["s"]),
                             dtype=np.float32)
            np.add(err, dq, out=err)                       # back to v
            np.subtract(err, np.asarray(c, np.float32), out=err)  # v − c

    # -- observability -------------------------------------------------------

    def _payload_nbytes(self, payload: Pytree) -> int:
        """Wire size of one commit payload: array bytes of the tree as it
        ARRIVED (codec blobs count their encoded arrays plus ~8 bytes per
        scalar field, so int8 commits report ~1/4 of dense — matching the
        native server's wire accounting); raw trees cost the center's
        size, computed once at construction."""
        from distkeras_tpu.parallel.compression import is_encoded

        if not is_encoded(payload):
            return self._center_nbytes
        total = 0
        for leaf in _tree_leaves(payload):
            if isinstance(leaf, np.ndarray):
                total += leaf.nbytes
            else:
                total += 8  # scale floats / dtype tags / codec marks
        return total

    def _begin_reply(self) -> None:
        """Open a delivered-traffic window: this handler is between
        sending a reply and landing its counters — a concurrent stats
        read must settle on it (see ``_settle_stats``)."""
        with self._stats_lock:
            self._n_pending_replies += 1

    def _end_reply(self) -> None:
        with self._stats_lock:
            self._n_pending_replies -= 1

    def _settle_stats(self, timeout: float = 1.0) -> bool:
        """The stats settling barrier (ISSUE 11 satellite): wait until no
        handler sits between reply-send and counter-land, so a stats
        read taken after the last reply was *received* also sees it
        *counted*. Bounded: under continuous traffic the gauge passes
        through zero between ops; a wedged sender (dead client holding a
        send) times out rather than hanging telemetry — the read then
        degrades to the historical may-lag-by-in-flight semantics."""
        if self._n_pending_replies == 0:  # racy fast path: exact enough
            return True
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._stats_lock:
                if self._n_pending_replies == 0:
                    return True
            time.sleep(0.001)
        return False

    def _count(self, pulls=0, compressed_pulls=0, commits=0,
               bytes_in=0, bytes_out=0, dup_commits=0, fused=0,
               batched_folds=0):
        with self._stats_lock:
            self._n_pulls += pulls
            self._n_compressed_pulls += compressed_pulls
            self._n_commits += commits
            self._bytes_in += bytes_in
            self._bytes_out += bytes_out
            self._n_dup_commits += dup_commits
            self._n_fused += fused
            self._n_batched_folds += batched_folds

    def recent_staleness(self) -> list[int]:
        """Snapshot of the recent per-commit DynSGD τ ring (newest last)
        — the watchtower samples its p95 into ``ps.tau_p95``. Lock-free
        read racing the fold path's appends (the shared retry-on-mutate
        snapshot helper: a telemetry read must never fail the scrape)."""
        from distkeras_tpu.observability.timeseries import snapshot_deque

        return snapshot_deque(self._tau_recent)

    def stats(self, settle: bool = True) -> dict:
        """Contention + throughput counters (cheap, approximate under load).

        Keys (the native PS exposes the identical set — parity pinned by
        tests/test_native_ps.py):

        - ``pulls`` / ``compressed_pulls`` / ``commits``: op counts.
        - ``bytes_in`` / ``bytes_out``: array payload bytes moved (commit /
          pull directions) at their WIRE size — codec-compressed commits
          and int8 pulls count encoded bytes, so the compression win is
          visible here; framing overhead excluded.
        - ``center_lock_acquires`` / ``center_lock_wait_ns`` /
          ``center_lock_hold_ns``: hot-path center-lock contention totals;
          ``center_lock_mean_hold_ns`` is the per-acquire mean — the number
          that proves the critical sections stayed O(fold).
        - ``elapsed_s``, ``pulls_per_sec``, ``commits_per_sec``: since
          construction (compressed pulls count toward the pull rate).
        - resilience counters: ``dup_commits`` (replayed commits the seqno
          dedup refused to double-fold), ``active_workers`` /
          ``evicted_workers`` / ``heartbeats`` / ``worker_retries`` (the
          lease registry — see resilience/heartbeat.py).
        - elastic-membership counters (resilience/elastic.py):
          ``pool_size`` (gauge: configured workers + joins − drains),
          ``joined_workers`` / ``preempted_workers`` (lifetime join /
          drain totals), ``drain_timeouts`` (drains whose deadline
          lapsed into the force-drain path).

        ``settle=False`` skips the delivered-traffic settling barrier —
        the watchtower's periodic scrape must OBSERVE the run, not
        synchronize with its in-flight replies (end-of-run reads keep
        the default exactness).
        """
        if settle:
            self._settle_stats()
        elapsed = time.monotonic() - self._t_start
        with self._stats_lock:
            pulls = self._n_pulls
            cpulls = self._n_compressed_pulls
            commits = self._n_commits
            fusedx = self._n_fused
            batched = self._n_batched_folds
            bytes_in, bytes_out = self._bytes_in, self._bytes_out
            dups = self._n_dup_commits
            pool = self._pool_size
            joined = self._n_joined
            preempted = self._n_preempted
            drain_to = self._n_drain_timeouts
            deploy_v = self._deploy_version
        hb = self._registry.stats()
        wal = self._wal
        return build_ps_stats(
            pulls, cpulls, commits, bytes_in, bytes_out,
            self._lock.acquires, self._lock.wait_ns, self._lock.hold_ns,
            elapsed, dup_commits=dups,
            active_workers=hb["active_workers"],
            evicted_workers=hb["evicted_workers"],
            heartbeats=hb["heartbeats"],
            worker_retries=hb["worker_retries"],
            fenced_commits=self._n_fenced_commits,
            num_updates=self.num_updates,
            wal_records=0 if wal is None else wal.wal_records,
            wal_fsyncs=0 if wal is None else wal.wal_fsyncs,
            wal_group_max=0 if wal is None else wal.wal_group_max,
            pool_size=pool, joined_workers=joined,
            preempted_workers=preempted, drain_timeouts=drain_to,
            fused_exchanges=fusedx, batched_folds=batched,
            deploy_version=deploy_v,
        )


def build_ps_stats(pulls: int, compressed_pulls: int, commits: int,
                   bytes_in: int, bytes_out: int, lock_acquires: int,
                   lock_wait_ns: int, lock_hold_ns: int,
                   elapsed_s: float, dup_commits: int = 0,
                   active_workers: int = 0, evicted_workers: int = 0,
                   heartbeats: int = 0, worker_retries: int = 0,
                   fenced_commits: int = 0, num_updates: int = 0,
                   wal_records: int = 0, wal_fsyncs: int = 0,
                   wal_group_max: int = 0, pool_size: int = 0,
                   joined_workers: int = 0, preempted_workers: int = 0,
                   drain_timeouts: int = 0,
                   fused_exchanges: int = 0,
                   batched_folds: int = 0,
                   deploy_version: int = 0) -> dict:
    """The ONE stats-dict builder both PS transports share (Python counters
    here, C++ atomics via ``native_ps.NativeSocketParameterServer.stats``):
    key set and derived-value math are pinned by construction, so the
    transports cannot drift. The resilience counters (dup commits, lease
    registry) default to zero for transports/tools that predate them."""
    elapsed_s = max(elapsed_s, 1e-9)
    return {
        "pulls": pulls,
        "compressed_pulls": compressed_pulls,
        "commits": commits,
        "bytes_in": bytes_in,
        "bytes_out": bytes_out,
        "center_lock_acquires": lock_acquires,
        "center_lock_wait_ns": lock_wait_ns,
        "center_lock_hold_ns": lock_hold_ns,
        "center_lock_mean_hold_ns": (
            lock_hold_ns // lock_acquires if lock_acquires else 0
        ),
        "elapsed_s": elapsed_s,
        "pulls_per_sec": (pulls + compressed_pulls) / elapsed_s,
        "commits_per_sec": commits / elapsed_s,
        "dup_commits": dup_commits,
        "active_workers": active_workers,
        "evicted_workers": evicted_workers,
        "heartbeats": heartbeats,
        "worker_retries": worker_retries,
        "fenced_commits": fenced_commits,
        # lifetime fold count: unlike the op counters (which restart at
        # zero on a recovered/promoted server), num_updates is part of
        # the durable state — THE counter for the cross-failover
        # exactly-once oracle (num_updates == logical commits issued)
        "num_updates": num_updates,
        # WAL observability (0 without a WAL): records appended, real
        # fsync syscalls, and the largest commit window one fsync ever
        # released — wal_records/wal_fsyncs is the amortization proof
        # (group commit's whole point), wal_group_max the batching one
        "wal_records": wal_records,
        "wal_fsyncs": wal_fsyncs,
        "wal_group_max": wal_group_max,
        # elastic membership (resilience/elastic.py): the pool gauge
        # (configured workers + joins − drains) and the lifetime
        # join/drain totals; drain_timeouts counts deadline-lapsed
        # drains — the force-drain fallback path
        "pool_size": pool_size,
        "joined_workers": joined_workers,
        "preempted_workers": preempted_workers,
        "drain_timeouts": drain_timeouts,
        # fused-exchange observability (ISSUE 10): a fused EXCHANGE counts
        # one commit AND one pull in the op counters above (it is one of
        # each, semantically) but only ONE wire round trip — so the total
        # exchange-related RTTs are the op counts minus one per fusion.
        # The 2→1 RTT claim is checkable from any trainer's ps_stats_:
        # with fusion on, exchange_rtts == windows + initial pulls, not
        # 2×windows + initial pulls.
        "fused_exchanges": fused_exchanges,
        "exchange_rtts": (pulls + compressed_pulls + commits + dup_commits
                          - fused_exchanges),
        # batched local exchange (ISSUE 12): folds that landed inside a
        # multi-fold center-lock section (the flat-combining drain).
        # commits − batched_folds ≈ lock acquisitions spent on commits,
        # so batched_folds > 0 is the observable proof that K colocated
        # workers' windows folded under < K acquisitions. 0 on the
        # native transport (its C++ fold path is per-commit).
        "batched_folds": batched_folds,
        # live-deployment lag (distkeras_tpu/deploy): the newest center
        # version published to the serving tier, and how many folds the
        # training head is ahead of it. 0/0 until a deployer reports —
        # the gated DeployLagRule stays silent on training-only runs.
        "deploy_version": deploy_version,
        "deploy_lag_folds": (
            max(0, num_updates - deploy_version) if deploy_version else 0
        ),
    }


def _is_floatish(arr: np.ndarray) -> bool:
    """Float-family leaf (incl. the ml_dtypes extension floats)?"""
    return (np.issubdtype(arr.dtype, np.floating)
            or arr.dtype.name in ("bfloat16", "float8_e4m3fn",
                                  "float8_e5m2"))


def _tree_map(fn, *trees):
    import jax

    return jax.tree.map(fn, *trees)


def _tree_leaves(tree: Pytree) -> list:
    import jax

    return jax.tree.leaves(tree)


def jax_tree_copy(tree: Pytree) -> Pytree:
    return _tree_map(np.copy, tree)


class SocketParameterServer(ParameterServer):
    """TCP service wrapper: the reference's driver-hosted PS, DCN-ready.

    Wire protocol (length-prefixed restricted-pickle frames,
    ``networking.py``): client sends ``{"action": "pull"|"commit"|"stop",
    "worker_id": i, "payload": tree?}``; ``pull`` answers
    ``{"weights": tree}``. Trees are plain containers of numpy arrays.
    """

    def __init__(self, center: Pytree, rule: MergeRule, num_workers: int,
                 host: str = "127.0.0.1", port: int = 0,
                 ema_decay: float | None = None,
                 lease_timeout: float | None = None,
                 wal_dir: str | None = None, snapshot_every: int = 100,
                 fence_epoch: int = 0, wal_group_window: int = 8,
                 wal_group_interval: float = 0.25):
        super().__init__(center, rule, num_workers, ema_decay=ema_decay,
                         lease_timeout=lease_timeout, wal_dir=wal_dir,
                         snapshot_every=snapshot_every,
                         fence_epoch=fence_epoch,
                         wal_group_window=wal_group_window,
                         wal_group_interval=wal_group_interval)
        self.host = host
        self.port = int(port)
        self._server_sock: Any = None
        self._service_thread: threading.Thread | None = None
        self._handlers: list[threading.Thread] = []
        self._conns: list = []          # live handler sockets (crash seam)
        self._conns_lock = threading.Lock()
        self._running = False
        self.crashed_ = False

    def initialize(self) -> None:
        import socket as _socket

        self._server_sock = _socket.socket(_socket.AF_INET, _socket.SOCK_STREAM)
        self._server_sock.setsockopt(
            _socket.SOL_SOCKET, _socket.SO_REUSEADDR, 1
        )
        self._server_sock.bind((self.host, self.port))
        self.port = self._server_sock.getsockname()[1]  # ephemeral resolved
        self._server_sock.listen(64)
        self._running = True

    def start(self) -> None:
        """Run the accept loop in a daemon thread (reference ``service()``)."""
        self._service_thread = threading.Thread(target=self.run, daemon=True)
        self._service_thread.start()

    def run(self) -> None:
        while self._running:
            try:
                conn, _ = self._server_sock.accept()
            except OSError:
                break
            if not self._running:
                conn.close()
                break
            conn.setsockopt(
                __import__("socket").IPPROTO_TCP,
                __import__("socket").TCP_NODELAY, 1,
            )
            with self._conns_lock:
                self._conns.append(conn)
            t = threading.Thread(target=self._handle, args=(conn,), daemon=True)
            t.start()
            self._handlers.append(t)

    def _handle(self, conn) -> None:
        # Weight pytrees travel as plain containers + ndarrays INSIDE the
        # restricted-unpickled control frame — never as a nested pickle blob,
        # so no unrestricted pickle.loads ever touches wire bytes. (Wire trees
        # are model params: nested dict/list/tuple of arrays. Custom pytree
        # node types are rejected by the restricted unpickler by design.)
        try:
            while True:
                # raw frame kept alongside the decoded message: a durable
                # commit logs its wire bytes verbatim (REC_COMMIT_WIRE)
                # instead of re-pickling the tree
                msg, raw = networking.recv_data_raw(conn)
                action = msg.get("action")
                if _trace.enabled():
                    # adopt the frame's correlation id (stamped by the
                    # client when tracing is on): every span this handler
                    # records joins the worker-side exchange's timeline
                    _trace.set_corr(msg.get("corr"))
                if action == "pull":
                    self._serve_pull(conn, msg["worker_id"])
                elif action == "pull_int8":
                    # compressed pull: int8 blob + server-side error
                    # feedback (see ParameterServer.pull), with the send
                    # coupled to the residual advance (rollback on a
                    # dropped reply — parity with dkps.cpp PULL_INT8)
                    self._serve_compressed_pull(conn, msg["worker_id"])
                elif action == "commit":
                    try:
                        applied = self.commit(
                            msg["worker_id"], msg["payload"],
                            seq=msg.get("seq"), epoch=msg.get("epoch"),
                            wire_frame=raw,
                        )
                    except networking.FencedEpochError as fe:
                        # fencing is a protocol-level verdict, not a dead
                        # connection: answer with the server's epoch so
                        # the client can raise a typed, fatal error
                        networking.send_data(conn, {
                            "error": "fenced",
                            "epoch": fe.server_epoch,
                        })
                        continue
                    networking.send_data(conn, {"ok": True,
                                                "dup": not applied})
                elif action == "exchange":
                    # fused commit + pull (ISSUE 10): one round trip folds
                    # the delta and answers with the fresh post-fold
                    # center — see ParameterServer.exchange
                    self._serve_exchange(conn, msg, raw)
                elif action == "ping":
                    # liveness probe for the trainer-side failover
                    # supervisor (and the client's epoch discovery)
                    networking.send_data(conn, {
                        "ok": True, "epoch": self.fence_epoch,
                        "num_updates": self.num_updates,
                        "standby": bool(getattr(self, "is_standby", False)),
                        "shard": self.shard_info,
                    })
                elif action == "shard_map":
                    # shard-map handshake: which shard of which plan this
                    # server holds (None = unsharded), plus the fencing
                    # epoch the shard-map epoch is summed from
                    networking.send_data(conn, {
                        "ok": True, "shard": self.shard_info,
                        "epoch": self.fence_epoch,
                    })
                elif action == "fence":
                    # admin: raise the fencing epoch (the promoting
                    # supervisor fences a superseded primary with this)
                    networking.send_data(
                        conn, {"ok": True,
                               "epoch": self.fence(int(msg["epoch"]))}
                    )
                elif action == "mark_epoch":
                    # trainer epoch barrier: log the boundary into the
                    # WAL/replication stream (deploy/stream.py cuts its
                    # epoch snapshots from this mark)
                    self.mark_epoch(int(msg["epoch"]))
                    networking.send_data(conn, {"ok": True})
                elif action == "deploy_report":
                    # a read replica published a serving snapshot at this
                    # center version — feeds deploy_lag_folds in stats()
                    self.report_deploy_version(int(msg["version"]))
                    networking.send_data(conn, {"ok": True})
                elif action == "heartbeat":
                    # lease renewal (auto-registers); retries is the
                    # client's cumulative reconnect-and-retry count
                    known = self.heartbeat(
                        msg["worker_id"], retries=msg.get("retries", 0)
                    )
                    networking.send_data(conn, {"ok": True, "known": known})
                elif action == "deregister":
                    self.deregister_worker(msg["worker_id"])
                    networking.send_data(conn, {"ok": True})
                elif action == "join":
                    # elastic live-join admission (resilience/elastic.py):
                    # lease the joiner and answer with the pool gauge +
                    # current version (its next pull prices its DynSGD τ)
                    rec = self.join_worker(msg["worker_id"])
                    rec["ok"] = True
                    networking.send_data(conn, rec)
                elif action == "drain":
                    # preemption drain: clean deregister + elastic
                    # counters; timeout=True marks a lapsed deadline
                    self.drain_worker(msg["worker_id"],
                                      timeout=bool(msg.get("timeout")))
                    networking.send_data(conn, {"ok": True})
                elif action == "stats":
                    # live counters with the settling barrier applied
                    # (stats() flushes pending pull-side deliveries
                    # before reading) — the observability CLI's source
                    networking.send_data(
                        conn, {"ok": True, "stats": self.stats()}
                    )
                elif action == "metrics":
                    # the unified metrics surface (ISSUE 11/13): the
                    # settled counters normalized into typed metrics
                    # (plus the flight recorder's overflow counter), as
                    # a JSON snapshot + Prometheus text exposition —
                    # and, with a watchtower attached, the alert ledger
                    from distkeras_tpu.observability.metrics import (
                        metrics_reply,
                        ps_metrics,
                    )

                    networking.send_data(conn, metrics_reply(
                        ps_metrics(self.stats()), self.watchtower,
                    ))
                elif action == "replicate_stream":
                    # hot-standby replication (StandbySocketParameterServer
                    # overrides; a primary politely refuses)
                    if self._serve_replication(conn, msg):
                        break
                elif action in ("stop", "bye"):
                    break
                else:
                    networking.send_data(conn, {"error": f"bad action {action}"})
        except (ConnectionError, EOFError, OSError):
            pass
        except pickle.UnpicklingError:
            # hostile/garbled frame rejected by the restricted unpickler —
            # drop the connection quietly, don't kill the handler loudly
            pass
        finally:
            with self._conns_lock:
                if conn in self._conns:
                    self._conns.remove(conn)
            conn.close()

    def _serve_replication(self, conn, msg) -> bool:
        """Only a standby accepts a replication stream; True = the
        connection was consumed to completion (close it)."""
        networking.send_data(conn, {"ok": False, "error": "not a standby"})
        return False

    def _serve_pull(self, conn, worker_id: int) -> None:
        """Wire variant of the exact ``pull``: serializes the immutable
        center snapshot straight onto the wire (pickling already copies,
        so the in-process path's defensive tree copy would be a second,
        redundant O(model) pass here) and counts the pull only once the
        reply is fully sent — delivered-traffic semantics, matching the
        compressed path and the native server."""
        snap, _ = self._begin_pull(worker_id, compressed=False)
        self._begin_reply()
        try:
            networking.send_data(conn, {"weights": snap})
            self._count(pulls=1, bytes_out=self._center_nbytes)
        finally:
            self._end_reply()

    def _serve_exchange(self, conn, msg, raw: bytes) -> None:
        """Wire variant of the fused ``exchange``: fold + fused pull
        bookkeeping through ``_commit_impl`` (the request frame is logged
        verbatim — REC_COMMIT_WIRE replay extracts ``payload`` exactly as
        it does for a plain commit), then the reply serializes the
        immutable snapshot straight onto the wire. Compressed replies get
        the dropped-reply residual rollback of ``_serve_compressed_pull``;
        counters land only once the reply is fully sent (delivered-traffic
        semantics, both transports)."""
        compressed = bool(msg.get("compressed"))
        with _trace.span("ps.exchange"):
            try:
                applied, snap, st = self._commit_impl(
                    msg["worker_id"], msg["payload"], seq=msg.get("seq"),
                    epoch=msg.get("epoch"), wire_frame=raw, fused=True,
                    lag=bool(msg.get("lag")), compressed=compressed,
                )
            except networking.FencedEpochError as fe:
                networking.send_data(conn, {
                    "error": "fenced", "epoch": fe.server_epoch,
                })
                return
            if not compressed:
                self._begin_reply()
                try:
                    networking.send_data(
                        conn,
                        {"ok": True, "dup": not applied, "weights": snap},
                    )
                    self._count(pulls=1, bytes_out=self._center_nbytes,
                                fused=1)
                finally:
                    self._end_reply()
                return
            with st.lock:
                blob, nbytes = self._encode_pull(st, snap)
                epoch_ = st.epoch
            self._begin_reply()
            try:
                networking.send_data(
                    conn,
                    {"ok": True, "dup": not applied, "weights": blob},
                )
                self._count(compressed_pulls=1, bytes_out=nbytes, fused=1)
            except (ConnectionError, OSError):
                with st.lock:
                    if st.epoch == epoch_:
                        self._rollback_encode_locked(st, snap, blob)
                raise
            finally:
                self._end_reply()

    def _serve_compressed_pull(self, conn, worker_id: int) -> None:
        """Wire variant of ``pull(compressed=True)`` with a dropped-reply
        rollback (parity with dkps.cpp PULL_INT8): a reply the client
        never received must not advance its EF residual. The send runs
        OUTSIDE the residual lock — a stalled client must not wedge the
        worker id's lock against a same-id reconnect — so the rollback is
        guarded by the encode epoch: it applies only if no newer encode
        raced in between; losing that (rare) race degrades to the old
        bounded phantom-pull behavior instead of corrupting the newer
        encode's residual. The center-lock section is the same O(1)
        version-record + snapshot grab as ``pull``."""
        snap, st = self._begin_pull(worker_id, compressed=True)
        with st.lock:
            blob, nbytes = self._encode_pull(st, snap)
            epoch = st.epoch
        self._begin_reply()
        try:
            networking.send_data(conn, {"weights": blob})
            self._count(compressed_pulls=1, bytes_out=nbytes)
        except (ConnectionError, OSError):
            with st.lock:
                if st.epoch == epoch:
                    self._rollback_encode_locked(st, snap, blob)
            raise
        finally:
            self._end_reply()

    def stop(self) -> None:
        """Shut down, unblocking ``accept`` via the reference's self-connect
        trick (``cancel_accept``), with a socket close as backstop."""
        if not self._running:
            self._close_durability()
            return
        self._running = False
        try:
            with networking.connect(self.host, self.port, timeout=5) as s:
                networking.send_data(s, {"action": "bye"})
        except OSError:
            pass
        if self._server_sock is not None:
            self._server_sock.close()  # unblocks accept even if connect failed
        if self._service_thread is not None:
            self._service_thread.join(timeout=5)
        self._close_durability()

    def _crash(self) -> None:
        """Chaos seam: die like a SIGKILL'd process, not a clean stop.

        Rips the listener and every live connection out mid-flight (peers
        see resets/EOF) and abandons the WAL WITHOUT the close-time fsync
        — exactly the state a killed process leaves: whatever each
        append's flush already handed the OS is durable, nothing else.
        Recovery and failover are tested against THIS, not against
        ``stop()``'s tidy shutdown."""
        import socket as _socket

        self.crashed_ = True
        self._running = False
        if self._server_sock is not None:
            try:
                self._server_sock.close()
            except OSError:
                pass
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        # abandon the WAL without flush or fsync: a real kill loses the
        # user-space buffer and never syncs — whatever earlier flushes
        # (mode 1) or group fsyncs already made durable survives, and
        # every deferred-ACK waiter is woken to give up (their clients
        # never saw an ACK, so they replay)
        if self._wal is not None:
            self._wal.abandon()
        sock = self._replica_sock
        self._replica_sock = None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass


class StandbySocketParameterServer(SocketParameterServer):
    """Warm replica: applies the primary's replication stream, serves
    nothing until promoted.

    Lifecycle: construct + ``initialize()`` + ``start()`` like any socket
    PS (its address is known up front, so failover never waits on a
    bind), then the primary's ``attach_standby`` opens the replication
    connection: one full-state snapshot frame, then raw WAL-framed
    records (``resilience/wal.py``) applied sequentially through the SAME
    ``replay_record`` path crash recovery uses — stream-apply and
    disk-replay cannot diverge. Worker actions are refused with a
    ``standby`` error (retryable weather to a confused client) until
    ``promote(epoch)`` installs the replicated state under the center
    lock, stamps the new fencing epoch, and flips it into an ordinary
    serving PS. The replication connection is closed at promotion — a
    zombie primary's next streamed record fails its send and the zombie
    drops into standalone (and soon fenced) mode.
    """

    def __init__(self, center: Pytree, rule: MergeRule, num_workers: int,
                 host: str = "127.0.0.1", port: int = 0,
                 ema_decay: float | None = None,
                 lease_timeout: float | None = None,
                 wal_dir: str | None = None, snapshot_every: int = 100,
                 wal_group_window: int = 8,
                 wal_group_interval: float = 0.25):
        super().__init__(center, rule, num_workers, host=host, port=port,
                         ema_decay=ema_decay, lease_timeout=lease_timeout,
                         wal_dir=wal_dir, snapshot_every=snapshot_every,
                         wal_group_window=wal_group_window,
                         wal_group_interval=wal_group_interval)
        self.is_standby = True
        self._repl_lock = threading.Lock()
        self._repl_state: dict | None = None
        self._repl_records = 0
        self._repl_streaming = False
        self.promoted_ = False

    def _handle(self, conn) -> None:
        if not self.is_standby:
            return super()._handle(conn)
        # pre-promotion: only the replication stream and pings are served;
        # worker ops get a retryable "standby" refusal (a client that
        # found us too early just backs off until promotion)
        try:
            while True:
                msg = networking.recv_data(conn)
                action = msg.get("action")
                if action == "replicate_stream":
                    if self._serve_replication(conn, msg):
                        break
                elif action == "ping":
                    # read the state ref once: promote() nulls it from
                    # the supervisor thread, and a torn read here would
                    # kill the handler with a TypeError outside its
                    # caught exception set
                    state = self._repl_state
                    networking.send_data(conn, {
                        "ok": True, "epoch": self.fence_epoch,
                        "num_updates": (
                            state["num_updates"] if state is not None
                            else self.num_updates
                        ),
                        "standby": True,
                        "shard": self.shard_info,
                    })
                elif action == "shard_map":
                    networking.send_data(conn, {
                        "ok": True, "shard": self.shard_info,
                        "epoch": self.fence_epoch,
                    })
                elif action in ("stop", "bye"):
                    break
                elif not self.is_standby:
                    # promoted mid-connection: hand the rest of this
                    # client's session to the full handler loop... which
                    # reads its own frames; simplest is to drop the conn
                    # and let the client reconnect to the promoted server
                    break
                else:
                    networking.send_data(
                        conn, {"error": "standby", "standby": True}
                    )
        except (ConnectionError, EOFError, OSError):
            pass
        except pickle.UnpicklingError:
            pass
        finally:
            with self._conns_lock:
                if conn in self._conns:
                    self._conns.remove(conn)
            conn.close()

    def _serve_replication(self, conn, msg) -> bool:
        from distkeras_tpu.resilience import wal as _wal

        with self._repl_lock:
            self._repl_state = dict(msg["state"])
            self._repl_streaming = True
        networking.send_data(conn, {"ok": True})
        # raw record stream from here on: header + body frames straight
        # off the socket (no pickle-frame wrapper per record)
        hdr = _wal._HDR
        try:
            while True:
                head = networking._recv_exact(conn, hdr.size)
                rec_type, crc, ln = hdr.unpack(head)
                body = networking._recv_exact(conn, ln, expected=ln)
                recs = list(_wal.iter_records(head + body))
                if not recs:
                    raise networking.ProtocolError(
                        "corrupt replication record", retryable=False
                    )
                with self._repl_lock:
                    if not self.is_standby:
                        return True  # promoted: this stream is history
                    self._repl_records += 1
                    _wal.replay_record(
                        self._repl_state, recs[0][0], recs[0][1],
                        self.rule, self.num_workers, self.ema_decay,
                    )
                    # chain replication (distkeras_tpu/sharding): a middle
                    # link forwards the RAW frame to its own successor
                    # after applying it — under the same lock, so the
                    # down-chain order IS the apply order (= the primary's
                    # fold order). A wedged/dead successor is dropped
                    # (bounded by its send timeout), never wedging this
                    # link's apply loop for good.
                    self._forward_chain_locked(head, body)
        finally:
            # promote()'s drain loop watches this flag: stream-end (the
            # dead primary's kernel flushed its buffer and FIN'd) means
            # every ACKed record has been applied
            with self._repl_lock:
                self._repl_streaming = False

    def _forward_chain_locked(self, head: bytes, body: bytes) -> None:
        """Send one applied record to this link's own successor (call with
        ``_repl_lock`` held). Failure degrades to a shorter chain —
        counted, never fatal to the apply loop."""
        sock = self._replica_sock
        if sock is None:
            return
        try:
            sock.sendall(head)
            sock.sendall(body)
        except OSError:
            self._replica_sock = None
            self._n_standby_drops += 1
            try:
                sock.close()
            except OSError:
                pass

    def attach_standby(self, host: str, port: int,
                       timeout: float = 10.0) -> None:
        """Chain link: attach THIS standby's successor. The base state it
        sends is the replicated state if a stream is already running,
        else this server's constructor state — chains are attached
        TAIL-FIRST before traffic (see ``ShardedPSGroup.start``), where
        the two are identical, so the successor never misses a record.
        After promotion this server is an ordinary primary and the base
        implementation applies."""
        if not self.is_standby:
            return super().attach_standby(host, port, timeout=timeout)
        sock = networking.connect(host, int(port), timeout=timeout)
        sock.settimeout(timeout)
        with self._repl_lock:
            if self._repl_state is not None:
                base = {
                    k: v for k, v in self._repl_state.items()
                    if k != "replayed"
                }
            else:
                with self._lock:
                    base = self._capture_state_locked()
                self._attach_ema_state(base)
                base.setdefault("ema", None)
                base.setdefault("ema_version", 0)
            networking.send_data(
                sock, {"action": "replicate_stream", "state": base}
            )
            reply = networking.recv_data(sock)
            if not reply.get("ok"):
                sock.close()
                raise ConnectionError(
                    f"chain successor at {host}:{port} refused the "
                    f"replication stream: {reply}"
                )
            self._replica_sock = sock
        sock.settimeout(5.0)  # bounded per-record forward, like the base

    def promote(self, epoch: int, drain_timeout: float = 5.0) -> None:
        """Become the primary: drain the replication stream, install the
        replicated state, stamp the new fencing epoch, start answering
        worker ops. Safe without a stream too (a standby promoted before
        any attach serves its constructor state — a cold-start primary).

        The drain matters for exactly-once: the primary ACKs a commit
        after ``sendall``-ing its record, so at the moment of death
        ACKed records may still sit in this side's socket buffer or
        behind the apply loop. Promoting without draining would discard
        folds whose clients will never retry them. A dead primary's
        kernel flushes the buffer and FINs, so the stream reaches EOF in
        bounded time; waiting for EOF — or, against a still-alive zombie
        that keeps streaming, for ``drain_timeout`` of quiescence-free
        grace — closes the gap. (A zombie's post-promotion folds belong
        to the superseded history anyway; fencing rejects their clients'
        next commits.)"""
        self._promote_impl(epoch, drain_timeout)

    def _promote_impl(self, epoch: int, drain_timeout: float) -> None:
        deadline = time.monotonic() + float(drain_timeout)
        last = -1
        while time.monotonic() < deadline:
            with self._repl_lock:
                streaming = self._repl_streaming
                applied = self._repl_records
            if not streaming:
                break  # EOF: every record the primary sent is applied
            if applied == last:
                # stream still open but idle for one poll: the primary
                # is alive-but-presumed-dead; take what has arrived
                break
            last = applied
            time.sleep(0.05)
        with self._repl_lock:
            state = self._repl_state
            self._repl_state = None
            with self._lock:
                if state is not None:
                    self._adopt_state(state)
                self.fence_epoch = max(self.fence_epoch, int(epoch))
                if self._wal is not None:
                    # the promoted history gets its own durable log
                    self._wal.rotate(self.num_updates)
                    snap = self._capture_state_locked()
            self.is_standby = False
            self.promoted_ = True
        if self._wal is not None:
            self._attach_ema_state(snap)
            self._wal.publish_snapshot(snap)


class ParameterServerClient:
    """Worker-side proxy speaking the socket protocol (same call surface as
    the in-process PS, so workers are transport-agnostic)."""

    def __init__(self, host: str, port: int, worker_id: int,
                 pull_compression: str | None = None,
                 epoch: int | None = None,
                 connect_timeout: float | None = 30.0):
        from distkeras_tpu.parallel.compression import (
            validate_pull_compression,
        )

        self.pull_compression = validate_pull_compression(pull_compression)
        self.worker_id = worker_id
        # fencing token carried on every commit (None = legacy, never
        # fenced); a resilient client's endpoint resolver hands each
        # reconnect the CURRENT epoch, so failing over adopts the new one
        self.epoch = None if epoch is None else int(epoch)
        self._sock = networking.connect(host, port, timeout=connect_timeout)
        # Blocking ops: a pull may legitimately wait behind many commits
        # (GIL-contended host, slow DCN link) — don't time out mid-training.
        self._sock.settimeout(None)

    def pull(self, worker_id: int | None = None) -> Pytree:
        action = "pull_int8" if self.pull_compression == "int8" else "pull"
        networking.send_data(
            self._sock,
            {"action": action, "worker_id": self.worker_id},
        )
        reply = networking.recv_data(self._sock)
        if "weights" not in reply:
            # an unpromoted standby (or other typed refusal): retryable —
            # the failover completes or the resolver moves us
            raise networking.ProtocolError(
                f"pull refused: {reply.get('error', reply)}", retryable=True
            )
        return maybe_decode(reply["weights"])

    def ping(self, timeout: float | None = None) -> dict:
        """Liveness probe: ``{"ok", "epoch", "num_updates", "standby"}``.
        ``timeout`` bounds just this round-trip (restored after)."""
        old = self._sock.gettimeout()
        if timeout is not None:
            self._sock.settimeout(timeout)
        try:
            networking.send_data(self._sock, {"action": "ping"})
            return networking.recv_data(self._sock)
        finally:
            self._sock.settimeout(old)

    def fence(self, epoch: int) -> int:
        """Admin: raise the server's fencing epoch (the promoting
        supervisor's last word to a superseded primary)."""
        networking.send_data(
            self._sock, {"action": "fence", "epoch": int(epoch)}
        )
        return int(networking.recv_data(self._sock).get("epoch", epoch))

    def mark_epoch(self, epoch: int) -> None:
        """Log a training-epoch boundary into the server's WAL/replication
        stream (the deployer's epoch-snapshot cut point)."""
        networking.send_data(
            self._sock, {"action": "mark_epoch", "epoch": int(epoch)}
        )
        networking.recv_data(self._sock)

    def report_deploy_version(self, version: int) -> None:
        """Report the newest center version published to the serving tier
        (feeds the server's ``deploy_lag_folds`` gauge)."""
        networking.send_data(
            self._sock, {"action": "deploy_report", "version": int(version)}
        )
        networking.recv_data(self._sock)

    def shard_map(self) -> dict | None:
        """Shard-map handshake: the server's shard record
        (``{"shard_id", "num_shards", "ring"}``) or None when it serves
        an unsharded center. The sharded client verifies this against
        its plan before first use — see ``sharding.client``."""
        networking.send_data(self._sock, {"action": "shard_map"})
        return networking.recv_data(self._sock).get("shard")

    def commit(self, worker_id: int | None, payload: Pytree,
               seq: int | None = None) -> None:
        # codec blobs are already wire-shaped (and carry non-array fields
        # like the codec name) — only raw trees get the numpy coercion
        if not is_encoded(payload):
            payload = utils.tree_to_numpy(payload)
        msg = {
            "action": "commit",
            "worker_id": self.worker_id,
            "payload": payload,
        }
        if _trace.enabled() and (corr := _trace.current_corr()):
            # carry the correlation id in the wire frame so the server's
            # fold/WAL spans join this worker's timeline (ISSUE 11)
            msg["corr"] = corr
        if seq is not None:
            # per-worker commit seqno: the server folds each (worker, seq)
            # at most once — see ParameterServer.commit / resilience.retry
            msg["seq"] = int(seq)
        if self.epoch is not None:
            msg["epoch"] = self.epoch
        networking.send_data(self._sock, msg)
        ack = networking.recv_data(self._sock)
        err = ack.get("error") if isinstance(ack, dict) else None
        if err == "fenced":
            raise networking.FencedEpochError(
                "commit fenced by the server",
                client_epoch=self.epoch, server_epoch=ack.get("epoch"),
            )
        if err == "standby":
            # found a not-yet-promoted replica: weather, not a bug — back
            # off and retry (the promotion or a re-resolve fixes it)
            raise networking.ProtocolError(
                "server is an unpromoted standby", retryable=True
            )

    def exchange(self, worker_id: int | None, payload: Pytree,
                 seq: int | None = None, lag: bool = False) -> Pytree:
        """Fused commit + pull: ONE round trip folds ``payload`` and
        returns the fresh post-fold center (decoded). Carries the same
        seq/epoch resilience tokens as ``commit``; ``lag=True`` is the
        pipelined worker's honest-τ flag (price the fold from the
        previous pull version — the delta is one exchange stale)."""
        if not is_encoded(payload):
            payload = utils.tree_to_numpy(payload)
        msg = {
            "action": "exchange",
            "worker_id": self.worker_id,
            "payload": payload,
        }
        if _trace.enabled() and (corr := _trace.current_corr()):
            msg["corr"] = corr  # cross-process span stitching, see commit
        if self.pull_compression == "int8":
            msg["compressed"] = True
        if seq is not None:
            msg["seq"] = int(seq)
        if self.epoch is not None:
            msg["epoch"] = self.epoch
        if lag:
            msg["lag"] = True
        networking.send_data(self._sock, msg)
        reply = networking.recv_data(self._sock)
        err = reply.get("error") if isinstance(reply, dict) else None
        if err == "fenced":
            raise networking.FencedEpochError(
                "exchange fenced by the server",
                client_epoch=self.epoch, server_epoch=reply.get("epoch"),
            )
        if "weights" not in reply:
            # an unpromoted standby or other typed refusal: retryable
            raise networking.ProtocolError(
                f"exchange refused: {reply.get('error', reply)}",
                retryable=True,
            )
        return maybe_decode(reply["weights"])

    def heartbeat(self, retries: int = 0) -> bool:
        """Renew this worker's lease (auto-registers); ``retries`` is the
        cumulative client retry count. Returns the server's ``known`` flag
        (False = this heartbeat re-registered an evicted/new worker)."""
        networking.send_data(
            self._sock,
            {"action": "heartbeat", "worker_id": self.worker_id,
             "retries": int(retries)},
        )
        return bool(networking.recv_data(self._sock).get("known", False))

    def deregister(self) -> None:
        """Clean exit: drop this worker's lease without an eviction."""
        networking.send_data(
            self._sock,
            {"action": "deregister", "worker_id": self.worker_id},
        )
        networking.recv_data(self._sock)  # ack

    def join(self) -> dict:
        """Elastic live-join admission (resilience/elastic.py): lease
        this worker mid-run and read the pool gauge + current center
        version. The caller pulls right after — that pull initializes
        its server-side pull-version, so DynSGD prices its first commit
        at the true small τ."""
        networking.send_data(
            self._sock, {"action": "join", "worker_id": self.worker_id}
        )
        reply = networking.recv_data(self._sock)
        if not reply.get("ok"):
            raise networking.ProtocolError(
                f"join refused: {reply.get('error', reply)}", retryable=True
            )
        return reply

    def drain(self, timeout: bool = False) -> None:
        """Preemption drain: clean deregister (dedup seqno retired) plus
        the server's elastic counters; ``timeout=True`` reports a drain
        whose deadline lapsed (the coordinator's force-drain path)."""
        networking.send_data(
            self._sock,
            {"action": "drain", "worker_id": self.worker_id,
             "timeout": bool(timeout)},
        )
        networking.recv_data(self._sock)  # ack

    def close(self) -> None:
        try:
            networking.send_data(self._sock, {"action": "bye"})
        except OSError:
            pass
        self._sock.close()
