"""Shared-memory ring-buffer transport — the colocated fast lane (ISSUE 12).

The socket/native wires serialize behind syscalls, kernel socket
copies, and pickle passes that the colocated regime (workers and PS on one
host — CI, single-VM, the single-TPU-slice deployment) never needed.
This module attacks that constant factor: ``ps_transport="shm"`` moves
every frame through an mmap'd SPSC ring pair (one
``multiprocessing.shared_memory`` segment per worker↔PS connection), so a
steady-state exchange costs **zero syscalls** and the O(model) payload is
written **once** into the ring and folded by the server **directly from
the mapped view** — no pickle of the bulk tensor, no kernel copies.

Layout (one segment per connection, created and unlinked by the server)::

    [0..4096)          header: magic, ring capacity, pids, closed flags;
                       head/tail cursors on their own cache lines
    [4096 .. 4096+cap)       client→server ring (requests)
    [4096+cap .. 4096+2cap)  server→client ring (replies)

Each ring is a byte pipe (head/tail are monotonic u64 byte counters; the
writer owns head, the reader owns tail — SPSC, no locks) carrying
length-prefixed records: a u64 word (``flags<<56 | length``) followed by
the payload. Three record kinds:

- **pickle records** (``FLAG_PKL``): exactly the socket wire's frames —
  the 8-byte big-endian length prefix plus the restricted-pickle payload,
  decoded by :func:`networking.decode_frame`, the SAME function the TCP
  wire and WAL wire-frame replay use. Payloads stream through the ring
  with wraparound and progressive publication, so a record LARGER than
  the ring drains through it in chunks — the oversize **spill path**.
- **bulk records** (``FLAG_BULK``): the zero-copy lane. ndarray leaves
  are lifted out of the message, replaced by ``(tag, offset, dtype,
  shape)`` markers in a small pickled skeleton, and written once into a
  64-byte-aligned contiguous region of the ring; the receiver rebuilds
  the tree as numpy **views over the mapped ring** and releases the
  region only after the fold/copy consumed it. Bulk records must be
  contiguous (a PAD record skips the ring tail when they would wrap) and
  at most half the ring — anything bigger falls back to the spill path.
- **pad records** (``FLAG_PAD``): dead bytes both sides skip.

Wakeup is condvar-based with a bounded wait slice: in the colocated
regime both endpoints live in one process, so the writer bumps head/tail
and notifies a process-local per-segment condition — no futex syscall
from Python, immediate wakeup, and (crucially, under the GIL) no spin
loop starving the peer thread. A cross-process peer degrades to the same
loop's 0.5 ms timeout polling. Every wait slice re-checks liveness: the
peer's closed flag, and (cross-process) its pid — a worker that dies
mid-ring-write surfaces as a retryable
:class:`~distkeras_tpu.networking.PeerDeadError` instead of wedging the
server, and the PR 4 heartbeat eviction closes an abandoned worker's
connection so its handler exits and the segment is **unlinked** (no
/dev/shm leaks; pinned by test).

Everything above the framing is the existing PS stack, unchanged:
``_fault_hook`` chaos fires at the top of every send/recv (FaultPlan
drops/delays work verbatim), the server handler is the socket handler's
action dispatch over ``recv_msg``, commits carry the same seqno/epoch
resilience tokens, and a durable server's clients send commit/exchange
frames on the pickle lane so the WAL logs the wire bytes VERBATIM
(``REC_COMMIT_WIRE``) and replays through the one shared decode pipeline
— bit-identical recovery, same as TCP (the handshake advertises
``wal_frames`` so the client picks the lane).

Security posture: the segment is a private mmap named under /dev/shm with
the creating process's permissions — narrower exposure than a TCP port.
The skeleton still decodes through the restricted unpickler; bulk leaf
markers can only produce numpy views bounded by the record's extent.
"""

from __future__ import annotations

import itertools
import os
import pickle
import socket as _socket
import struct
import threading
import time
from multiprocessing import shared_memory
from typing import Any

import numpy as np

from distkeras_tpu import networking, utils
from distkeras_tpu.networking import PeerDeadError, ProtocolError
from distkeras_tpu.observability import trace as _trace
from distkeras_tpu.parameter_servers import (
    ParameterServerClient,
    SocketParameterServer,
)
from distkeras_tpu.parallel.compression import is_encoded, maybe_decode

Pytree = Any

#: Per-direction ring capacity (bytes). One exchange needs roughly
#: 2×model bytes of ring traffic (delta in, center out, on separate
#: rings); 8 MiB comfortably holds a ~1M-param f32 model's frames with
#: bulk-lane headroom, and /dev/shm is charged lazily (only touched
#: pages cost memory). Override per server via ``ring_bytes=``.
DEFAULT_RING_BYTES = 8 * 1024 * 1024

_HDR_BYTES = 4096
_MAGIC = 0x31304D48534B44  # "DKSHM01" little-endian
_OFF_MAGIC = 0
_OFF_CAP = 8
# cursors on their own cache lines: head/tail of each ring are written
# by different threads at frame rate — sharing a line would bounce it
_OFF_C2S_HEAD = 64
_OFF_C2S_TAIL = 128
_OFF_S2C_HEAD = 192
_OFF_S2C_TAIL = 256
_OFF_CLIENT_PID = 320
_OFF_SERVER_PID = 328
_OFF_CLIENT_CLOSED = 384
_OFF_SERVER_CLOSED = 448

_WORD = struct.Struct("<Q")
_U32 = struct.Struct("<I")
_FLAG_SHIFT = 56
_LEN_MASK = (1 << _FLAG_SHIFT) - 1
FLAG_PKL = 0
FLAG_BULK = 1
FLAG_PAD = 0x7F

#: bulk leaf marker tag in the skeleton tree (see module docstring)
_LEAF_TAG = "__dkshm__"

#: condvar wait slice: the notify path makes this latency irrelevant
#: in-process; cross-process peers poll at this cadence
_WAIT_SLICE = 0.0005
#: cadence of the cross-process peer-pid liveness probe during waits
_LIVENESS_PERIOD = 0.25

_seg_counter = itertools.count()

# process-local segment registry (observability satellite): every mint
# registers, every unlink path unregisters — segment_inventory() reads
# /dev/shm where it exists (the cross-process truth) and falls back to
# this registry elsewhere, so the no-leak property is operator-visible
# in health_snapshot, not just test-visible
_SEG_REGISTRY: dict[str, int] = {}
_SEG_REGISTRY_LOCK = threading.Lock()

# Cross-process rendezvous (ISSUE 15, ROADMAP item 5 residual): when a
# membership directory is configured, every mint publishes the segment
# name under the directory's "shm" role and every unlink withdraws it —
# SEPARATE trainer processes on one host can then find each other's ring
# segments by name (`DirectoryClient.shm_segments()`) instead of passing
# them by hand. The process-local registry above stays the fallback when
# no directory is installed. Installed via `set_rendezvous` (see
# `distkeras_tpu.directory.install_shm_rendezvous`); both callbacks are
# best-effort by design — a directory outage must never fail a mint.
_RENDEZVOUS: tuple | None = None   # (publish(name, size), withdraw(name))


def set_rendezvous(publish, withdraw) -> None:
    """Install the named-rendezvous callbacks for this process's shm
    segments (exactly one rendezvous at a time — the directory is a
    singleton per process by construction)."""
    global _RENDEZVOUS
    _RENDEZVOUS = (publish, withdraw)


def clear_rendezvous(publish=None) -> None:
    """Uninstall the rendezvous (matching ``publish`` when given, so a
    stale uninstaller cannot clobber a newer installation)."""
    global _RENDEZVOUS
    if publish is None or (_RENDEZVOUS is not None
                           and _RENDEZVOUS[0] is publish):
        _RENDEZVOUS = None


def unregister_segment(name: str) -> None:
    """Drop one segment from the live-inventory registry (called by
    every unlink path — Python lane and native lane)."""
    with _SEG_REGISTRY_LOCK:
        _SEG_REGISTRY.pop(name, None)
    rdv = _RENDEZVOUS
    if rdv is not None:
        try:
            rdv[1](name)
        except Exception:
            pass  # best-effort: the directory lease is the backstop


def segment_inventory() -> dict:
    """Live dkshm segment inventory: names + sizes, from a /dev/shm
    scan when the OS exposes one (covers segments OTHER processes on
    this host minted too — the colocated regime's whole truth) or from
    the process-local registry otherwise. An empty list after a run IS
    the no-/dev/shm-leak proof, now visible to operators via
    ``health_snapshot`` instead of only to the leak-check tests."""
    segs = []
    shm_dir = "/dev/shm"
    if os.path.isdir(shm_dir):
        for fn in sorted(os.listdir(shm_dir)):
            if not fn.startswith("dkshm"):
                continue
            try:
                size = os.stat(os.path.join(shm_dir, fn)).st_size
            except OSError:
                continue  # unlinked between listdir and stat
            segs.append({"name": fn, "bytes": int(size)})
    else:
        with _SEG_REGISTRY_LOCK:
            segs = [{"name": n, "bytes": b}
                    for n, b in sorted(_SEG_REGISTRY.items())]
    return {
        "count": len(segs),
        "total_bytes": sum(s["bytes"] for s in segs),
        "segments": segs,
    }


def mint_segment(name_prefix: str,
                 ring_bytes: int) -> shared_memory.SharedMemory:
    """Create one header-initialized dkshm segment (the ONE place the
    name scheme and header layout are written — the native lane's
    ``NativeSocketParameterServer.attach_shm`` mints through here too,
    so the two lanes cannot drift on the contract)."""
    seg = shared_memory.SharedMemory(
        create=True,
        name=f"{name_prefix}_{os.getpid()}_{next(_seg_counter)}",
        size=_HDR_BYTES + 2 * int(ring_bytes),
    )
    _WORD.pack_into(seg.buf, _OFF_MAGIC, _MAGIC)
    _WORD.pack_into(seg.buf, _OFF_CAP, int(ring_bytes))
    with _SEG_REGISTRY_LOCK:
        _SEG_REGISTRY[seg.name] = seg.size
    rdv = _RENDEZVOUS
    if rdv is not None:
        try:
            rdv[0](seg.name, seg.size)
        except Exception:
            pass  # best-effort: mint must not fail on a directory outage
    return seg


def _align64(n: int) -> int:
    return (n + 63) & ~63


def _resolve_dtype(name: str) -> np.dtype:
    """dtype by name, reaching through ml_dtypes for the extension
    floats (bfloat16/float8) jax environments register."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


# -- process-local wakeup registry -------------------------------------------
#
# Both endpoints of a segment in ONE process (the colocated regime this
# transport exists for) share a Condition keyed by segment name: bumping
# a cursor notifies it, so a blocked peer wakes immediately instead of
# polling. The lost-wakeup race is closed the classic way — the waiter
# re-checks its predicate INSIDE the condition lock before waiting, and
# the notifier publishes the cursor BEFORE taking that lock.

_WAKERS: dict[str, threading.Condition] = {}
_WAKERS_LOCK = threading.Lock()


def _waker_for(name: str) -> threading.Condition:
    with _WAKERS_LOCK:
        return _WAKERS.setdefault(name, threading.Condition())


def _waker_drop(name: str) -> None:
    with _WAKERS_LOCK:
        _WAKERS.pop(name, None)


def _pid_alive(pid: int) -> bool:
    if pid <= 0:
        return True  # never stamped: no verdict
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


class _ShmConn:
    """One endpoint of a segment's ring pair.

    Two API layers share the rings:

    - the **message layer** (``send_msg`` / ``recv_msg``): pickle-lane
      control frames and zero-copy bulk frames — the shm server handler
      and the bulk client paths live here;
    - a **socket-duck byte layer** (``sendall`` / ``sendmsg`` / ``recv``
      / ``settimeout`` / ``getpeername`` / ``close``), so
      ``networking.send_data`` / ``recv_data`` — and therefore every
      inherited :class:`ParameterServerClient` action and the
      ``_fault_hook`` chaos seam — run over the ring UNCHANGED. Byte
      reads transparently consume pickle records (a bulk record in a
      byte-stream read is a protocol violation and fails fast).
    """

    def __init__(self, seg: shared_memory.SharedMemory, side: str,
                 waker: threading.Condition):
        if side not in ("client", "server"):
            raise ValueError(f"side must be 'client' or 'server', got {side!r}")
        self._seg = seg
        self._buf = seg.buf
        self._name = seg.name
        self._side = side
        self._waker = waker
        (magic,) = _WORD.unpack_from(self._buf, _OFF_MAGIC)
        if magic != _MAGIC:
            raise ProtocolError(
                f"segment {seg.name} is not a dkshm segment", retryable=False
            )
        (self._cap,) = _WORD.unpack_from(self._buf, _OFF_CAP)
        if side == "client":
            self._tx_head, self._tx_tail = _OFF_C2S_HEAD, _OFF_C2S_TAIL
            self._rx_head, self._rx_tail = _OFF_S2C_HEAD, _OFF_S2C_TAIL
            self._my_closed, self._peer_closed = (
                _OFF_CLIENT_CLOSED, _OFF_SERVER_CLOSED)
            self._peer_pid_off = _OFF_SERVER_PID
            _WORD.pack_into(self._buf, _OFF_CLIENT_PID, os.getpid())
        else:
            self._tx_head, self._tx_tail = _OFF_S2C_HEAD, _OFF_S2C_TAIL
            self._rx_head, self._rx_tail = _OFF_C2S_HEAD, _OFF_C2S_TAIL
            self._my_closed, self._peer_closed = (
                _OFF_SERVER_CLOSED, _OFF_CLIENT_CLOSED)
            self._peer_pid_off = _OFF_CLIENT_PID
            _WORD.pack_into(self._buf, _OFF_SERVER_PID, os.getpid())
        self._tx_data = _HDR_BYTES if side == "client" \
            else _HDR_BYTES + self._cap
        self._rx_data = _HDR_BYTES + self._cap if side == "client" \
            else _HDR_BYTES
        self._timeout: float | None = None
        self._closed = False
        self._cur = 0  # bytes left in the current pickle record (byte reads)
        # bulk records at most half the ring: a full-ring record would
        # require exact lockstep; half guarantees forward progress with
        # one record in flight while the previous one drains
        self._bulk_max = max(0, self._cap // 2 - 64)

    # -- cursor primitives ---------------------------------------------------

    def _torn(self, exc: BaseException) -> PeerDeadError:
        """A released-mapping error (``SharedMemory.close`` ran while
        this op was in flight — server stop/crash/eviction racing a live
        peer) IS peer death: convert it to the typed retryable error the
        whole resilience stack already triages. Reads raise ValueError
        ("operation forbidden on released memoryview"), writes raise
        TypeError (the released view stops being read-write). Anything
        else re-raises untouched."""
        if isinstance(exc, (ValueError, TypeError)) \
                and "memoryview" in str(exc):
            return PeerDeadError(
                "shm segment torn down mid-operation", peer=self._name
            )
        raise exc

    def _u64(self, off: int) -> int:
        return _WORD.unpack_from(self._buf, off)[0]

    def _set_u64(self, off: int, v: int) -> None:
        _WORD.pack_into(self._buf, off, v)

    def _notify(self) -> None:
        cond = self._waker
        with cond:
            cond.notify_all()

    def _check_alive(self, what: str) -> None:
        if self._buf is None or self._u64(self._my_closed):
            raise PeerDeadError(
                f"shm connection closed during {what}", peer=self._name
            )
        if self._u64(self._peer_closed):
            raise PeerDeadError(
                f"shm peer closed its endpoint during {what}",
                peer=self._name,
            )
        pid = self._u64(self._peer_pid_off)
        if pid and pid != os.getpid() and not _pid_alive(pid):
            # cross-process peer died without flagging: the pid probe is
            # the liveness backstop (in-process thread death is covered
            # by close()/eviction setting the flag instead)
            raise PeerDeadError(
                f"shm peer pid {pid} is gone (died mid-{what})",
                peer=self._name,
            )

    def _wait(self, pred, what: str) -> None:
        """Block until ``pred()`` holds — condvar wait with liveness
        checks each slice and the socket-style timeout contract
        (``socket.timeout`` after ``settimeout`` lapses, so the retry
        triage sees exactly what a TCP stall produces)."""
        if pred():
            return
        deadline = (None if self._timeout is None
                    else time.monotonic() + self._timeout)
        t_live = time.monotonic() + _LIVENESS_PERIOD
        cond = self._waker
        while True:
            self._check_alive(what)
            with cond:
                if pred():
                    return
                cond.wait(_WAIT_SLICE)
            if pred():
                return
            now = time.monotonic()
            if deadline is not None and now >= deadline:
                raise _socket.timeout(
                    f"shm {what} timed out after {self._timeout}s"
                )
            if now >= t_live:
                self._check_alive(what)
                t_live = now + _LIVENESS_PERIOD

    # -- byte layer: writer --------------------------------------------------

    def _tx_free(self) -> int:
        return self._cap - (self._u64(self._tx_head) - self._u64(self._tx_tail))

    def _advance_head(self, n: int) -> None:
        self._set_u64(self._tx_head, self._u64(self._tx_head) + n)
        self._notify()

    def _skip_to_word_boundary_tx(self) -> None:
        """Record words never wrap: if fewer than 8 bytes remain to the
        ring's end, both sides skip them (dead bytes)."""
        pos = self._u64(self._tx_head) % self._cap
        rem = self._cap - pos
        if rem < 8:
            self._wait(lambda: self._tx_free() >= rem, "send")
            self._advance_head(rem)

    def _stream_tx(self, chunks) -> None:
        """Write raw bytes with wraparound, publishing progressively so
        the reader drains concurrently — the spill path for records
        bigger than the ring rides exactly this."""
        for chunk in chunks:
            mv = memoryview(chunk)
            if mv.ndim != 1 or mv.itemsize != 1:
                mv = mv.cast("B")
            i = 0
            n = len(mv)
            while i < n:
                self._wait(lambda: self._tx_free() > 0, "send")
                head = self._u64(self._tx_head)
                pos = head % self._cap
                k = min(n - i, self._tx_free(), self._cap - pos)
                self._buf[self._tx_data + pos:self._tx_data + pos + k] = \
                    mv[i:i + k]
                i += k
                self._advance_head(k)

    def _send_record(self, flags: int, chunks) -> None:
        total = sum(len(memoryview(c).cast("B")) for c in chunks)
        self._skip_to_word_boundary_tx()
        self._stream_tx([_WORD.pack((flags << _FLAG_SHIFT) | total)])
        self._stream_tx(chunks)

    # -- byte layer: reader --------------------------------------------------

    def _rx_avail(self) -> int:
        return self._u64(self._rx_head) - self._u64(self._rx_tail)

    def _advance_tail(self, n: int) -> None:
        self._set_u64(self._rx_tail, self._u64(self._rx_tail) + n)
        self._notify()

    def _read_exact(self, n: int) -> bytearray:
        """Copy exactly n bytes out of the ring (wrapping, progressive
        tail release so an oversize record streams through)."""
        out = bytearray(n)
        i = 0
        while i < n:
            self._wait(lambda: self._rx_avail() > 0, "recv")
            tail = self._u64(self._rx_tail)
            pos = tail % self._cap
            k = min(n - i, self._rx_avail(), self._cap - pos)
            out[i:i + k] = self._buf[self._rx_data + pos:
                                     self._rx_data + pos + k]
            i += k
            self._advance_tail(k)
        return out

    def _next_record(self) -> tuple[int, int]:
        """Consume pads/dead bytes up to the next record word; returns
        ``(flags, payload_length)`` with the word consumed."""
        while True:
            tail = self._u64(self._rx_tail)
            pos = tail % self._cap
            rem = self._cap - pos
            if rem < 8:
                self._wait(lambda: self._rx_avail() >= rem, "recv")
                self._advance_tail(rem)
                continue
            self._wait(lambda: self._rx_avail() >= 8, "recv")
            (word,) = _WORD.unpack_from(self._buf, self._rx_data + pos)
            flags, length = word >> _FLAG_SHIFT, word & _LEN_MASK
            if flags == FLAG_PAD:
                self._wait(lambda: self._rx_avail() >= 8 + length, "recv")
                self._advance_tail(8 + length)
                continue
            self._advance_tail(8)
            return flags, length

    # -- socket-duck surface (networking.send_data / recv_data) --------------

    def sendmsg(self, buffers) -> int:
        if self._closed:
            raise PeerDeadError("send on closed shm connection",
                                peer=self._name)
        try:
            self._send_record(FLAG_PKL, list(buffers))
            return sum(len(memoryview(b).cast("B")) for b in buffers)
        except (ValueError, TypeError) as e:
            raise self._torn(e) from e

    def sendall(self, data) -> None:
        self.sendmsg([data])

    def recv(self, n: int) -> bytes:
        try:
            if self._cur == 0:
                flags, length = self._next_record()
                if flags != FLAG_PKL:
                    raise ProtocolError(
                        f"bulk shm record (flags={flags}) in a byte-stream "
                        f"read — protocol violation", retryable=False,
                        peer=self._name,
                    )
                self._cur = length
            self._wait(lambda: self._rx_avail() > 0, "recv")
            tail = self._u64(self._rx_tail)
            pos = tail % self._cap
            k = min(n, self._cur, self._rx_avail(), self._cap - pos)
            out = bytes(
                self._buf[self._rx_data + pos:self._rx_data + pos + k]
            )
            self._advance_tail(k)
            self._cur -= k
            return out
        except (ValueError, TypeError) as e:
            raise self._torn(e) from e

    def settimeout(self, t: float | None) -> None:
        self._timeout = None if t is None else float(t)

    def gettimeout(self) -> float | None:
        return self._timeout

    def getpeername(self) -> str:
        return f"shm:{self._name}"

    def close(self) -> None:
        """Flag this endpoint closed and wake the peer; the segment's
        unlink is the SERVER'S job (it created the name)."""
        if self._closed:
            return
        self._closed = True
        buf = self._buf
        if buf is not None:
            try:
                self._set_u64(self._my_closed, 1)
            except (ValueError, TypeError):
                pass  # segment already torn down under us
        self._notify()

    def detach_buffer(self) -> None:
        """Mark this endpoint dead ahead of the segment's unlink. The
        buffer reference is deliberately KEPT: a concurrent op on the
        dying connection must fault through the closed-flag check (a
        typed, retryable PeerDeadError), never through a torn attribute
        — the mapping itself stays valid until the refs are dropped
        (unlink only removes the name)."""
        self._closed = True

    # -- message layer -------------------------------------------------------

    def send_msg(self, msg: dict, bulk: bool = False) -> None:
        """One framed message. ``bulk=True`` ships ndarray leaves on the
        zero-copy lane when they fit (≤ half the ring, written once into
        a contiguous aligned region); otherwise — and for all control
        frames — the pickle lane carries the socket wire's exact frame
        bytes (length prefix + restricted pickle), streamed through the
        ring with wraparound: the oversize spill path."""
        if networking._fault_hook is not None:
            networking._fault_hook("send", self)
        if self._closed:
            raise PeerDeadError("send on closed shm connection",
                                peer=self._name)
        try:
            if bulk:
                enc = self._encode_bulk(msg)
                if enc is not None:
                    skel, leaves, payload_len = enc
                    self._send_bulk(skel, leaves, payload_len)
                    return
            payload = pickle.dumps(msg, protocol=pickle.HIGHEST_PROTOCOL)
            self._send_record(
                FLAG_PKL, [networking._LEN.pack(len(payload)), payload]
            )
        except (ValueError, TypeError) as e:
            raise self._torn(e) from e

    def _encode_bulk(self, msg: dict):
        """Lift ndarray leaves out of ``msg`` into a placement plan:
        returns ``(skeleton_pickle, [(arr, rel_offset)...], payload_len)``
        or None when the record wouldn't fit the bulk lane (the caller
        falls back to the spill path)."""
        leaves: list[tuple[np.ndarray, int]] = []
        state = {"off": 0}

        def walk(o):
            if isinstance(o, np.ndarray):
                arr = np.ascontiguousarray(o)
                off = _align64(state["off"])
                state["off"] = off + arr.nbytes
                leaves.append((arr, off))
                return (_LEAF_TAG, off, arr.dtype.name, tuple(arr.shape))
            if isinstance(o, dict):
                return {k: walk(v) for k, v in o.items()}
            if isinstance(o, (list, tuple)):
                return type(o)(walk(v) for v in o)
            return o

        skel_tree = walk(msg)
        if not leaves:
            return None  # pure control frame: the pickle lane is cheaper
        skel = pickle.dumps(skel_tree, protocol=pickle.HIGHEST_PROTOCOL)
        leaf_base = _align64(_U32.size + len(skel))
        payload_len = leaf_base + state["off"]
        if 8 + payload_len > self._bulk_max:
            return None  # oversize: spill through the pickle lane
        return skel, leaves, payload_len

    def _send_bulk(self, skel: bytes, leaves, payload_len: int) -> None:
        total = 8 + payload_len
        # contiguity: pad to the ring's end when the record would wrap
        head = self._u64(self._tx_head)
        pos = head % self._cap
        rem = self._cap - pos
        if rem < total:
            self._wait(lambda: self._tx_free() >= rem, "send")
            if rem >= 8:
                _WORD.pack_into(
                    self._buf, self._tx_data + pos,
                    (FLAG_PAD << _FLAG_SHIFT) | (rem - 8),
                )
            self._advance_head(rem)
        self._wait(lambda: self._tx_free() >= total, "send")
        base = self._tx_data + (self._u64(self._tx_head) % self._cap)
        _WORD.pack_into(self._buf, base,
                        (FLAG_BULK << _FLAG_SHIFT) | payload_len)
        _U32.pack_into(self._buf, base + 8, len(skel))
        self._buf[base + 8 + _U32.size:base + 8 + _U32.size + len(skel)] = \
            skel
        leaf_base = base + 8 + _align64(_U32.size + len(skel))
        for arr, rel in leaves:
            if arr.nbytes == 0:
                continue
            view = np.frombuffer(
                self._buf, dtype=np.uint8, count=arr.nbytes,
                offset=leaf_base + rel,
            )
            # the ONE copy of the bulk payload: staging buffer → ring
            view[:] = arr.reshape(-1).view(np.uint8)
        self._advance_head(total)

    def recv_msg(self, copy: bool = False):
        """→ ``(msg, raw, release)``.

        ``raw`` is the frame's pickle bytes for pickle-lane records (the
        WAL's verbatim wire frame) and None for bulk records. ``release``
        is None unless the message holds live ring views (bulk,
        ``copy=False``): the caller MUST call it once the views are
        consumed — the ring space stays pinned (and the sender blocked
        past one in-flight record) until then. ``copy=True`` materializes
        views into fresh arrays and releases before returning."""
        if networking._fault_hook is not None:
            networking._fault_hook("recv", self)
        try:
            flags, length = self._next_record()
            if flags == FLAG_PKL:
                if length > networking.MAX_FRAME_BYTES + 8:
                    raise ProtocolError(
                        f"shm record of {length} bytes exceeds the frame "
                        f"cap", frame_size=int(length), peer=self._name,
                        retryable=False,
                    )
                prefix = self._read_exact(8)
                (n,) = networking._LEN.unpack(prefix)
                if n != length - 8:
                    raise ProtocolError(
                        f"shm pickle record length mismatch ({n} vs "
                        f"{length - 8})", peer=self._name, retryable=False,
                    )
                raw = bytes(self._read_exact(n))
                return networking.decode_frame(raw), raw, None
            if flags != FLAG_BULK:
                raise ProtocolError(
                    f"unknown shm record flags {flags}", peer=self._name,
                    retryable=False,
                )
            self._wait(lambda: self._rx_avail() >= length, "recv")
            base = self._rx_data + (self._u64(self._rx_tail) % self._cap)
            msg = self._decode_bulk(base, copy)
            if copy:
                self._advance_tail(length)
                return msg, None, None
        except (ValueError, TypeError) as e:
            raise self._torn(e) from e
        released = [False]

        def release():
            if not released[0]:
                released[0] = True
                try:
                    self._advance_tail(length)
                except (ValueError, TypeError) as e:
                    raise self._torn(e) from e

        return msg, None, release

    def _decode_bulk(self, base: int, copy: bool):
        (skel_len,) = _U32.unpack_from(self._buf, base)
        skel = bytes(self._buf[base + _U32.size:base + _U32.size + skel_len])
        tree = networking.decode_frame(skel)  # restricted unpickler
        leaf_base = base + _align64(_U32.size + skel_len)

        def rebuild(o):
            if (isinstance(o, tuple) and len(o) == 4
                    and o[0] == _LEAF_TAG):
                _, rel, dtname, shape = o
                dt = _resolve_dtype(dtname)
                count = int(np.prod(shape, dtype=np.int64))
                if count == 0:
                    return np.empty(shape, dt)
                view = np.frombuffer(
                    self._buf, dtype=dt, count=count,
                    offset=leaf_base + rel,
                ).reshape(shape)
                return np.array(view) if copy else view
            if isinstance(o, dict):
                return {k: rebuild(v) for k, v in o.items()}
            if isinstance(o, (list, tuple)):
                return type(o)(rebuild(v) for v in o)
            return o

        return rebuild(tree)


class ShmParameterServer(SocketParameterServer):
    """The PS served over shared-memory rings — ``ps_transport="shm"``.

    Colocated-only by design (the segment name is this process's), which
    is exactly the regime the socket wire was overpaying in. The action
    dispatch, fold path, WAL, fencing, heartbeats, elastic membership,
    stats, and trace spans are the inherited server's — only the framing
    differs: requests arrive through :meth:`_ShmConn.recv_msg` (pickle
    OR bulk lane), pull/exchange replies ship the center's leaves on the
    bulk lane (written once from the immutable snapshot into the mapped
    ring), and a durable server's commit frames arrive on the pickle
    lane so the WAL logs them VERBATIM (``REC_COMMIT_WIRE``) with the
    same replay pipeline as TCP.

    Connection lifecycle: :meth:`connect_shm` creates the segment and a
    dedicated handler thread; the segment is unlinked when the handler
    exits — client close, server stop/crash, or the heartbeat eviction
    of an abandoned worker (``_on_evict`` closes its connections), so
    /dev/shm never leaks.
    """

    def __init__(self, center: Pytree, rule, num_workers: int,
                 ring_bytes: int = DEFAULT_RING_BYTES,
                 ema_decay: float | None = None,
                 lease_timeout: float | None = None,
                 wal_dir: str | None = None, snapshot_every: int = 100,
                 fence_epoch: int = 0, wal_group_window: int = 8,
                 wal_group_interval: float = 0.25):
        super().__init__(center, rule, num_workers, host="shm", port=0,
                         ema_decay=ema_decay, lease_timeout=lease_timeout,
                         wal_dir=wal_dir, snapshot_every=snapshot_every,
                         fence_epoch=fence_epoch,
                         wal_group_window=wal_group_window,
                         wal_group_interval=wal_group_interval)
        if int(ring_bytes) < _HDR_BYTES:
            raise ValueError(
                f"ring_bytes must be >= {_HDR_BYTES}, got {ring_bytes}"
            )
        self.ring_bytes = int(ring_bytes)
        # segment records: {"seg", "conn", "wid", "released"} — guarded
        # by the inherited _conns_lock
        self._segments: list[dict] = []

    # -- lifecycle (no TCP anywhere) -----------------------------------------

    def initialize(self) -> None:
        self._running = True

    def start(self) -> None:
        pass  # no accept loop: connect_shm spawns handlers directly

    def run(self) -> None:
        pass

    def attach_standby(self, host: str, port: int,
                       timeout: float = 10.0) -> None:
        raise NotImplementedError(
            "the shm transport is colocated-only; replication streams "
            "(standby/chain) are the socket transport's job — "
            "trainers.py enforces ps_chain_length > 1 => socket"
        )

    def connect_shm(self, worker_id: int) -> tuple[_ShmConn, dict]:
        """Mint one worker↔PS connection: create the segment, spawn its
        handler thread, return the client endpoint plus the handshake
        record (``wal_frames``: send commit/exchange on the pickle lane
        so the WAL logs wire frames verbatim). Any worker id works —
        the elastic coordinator mints joiner clients through here."""
        if not self._running:
            raise ConnectionRefusedError("shm parameter server is stopped")
        seg = mint_segment("dkshm", self.ring_bytes)
        waker = _waker_for(seg.name)
        srv_conn = _ShmConn(seg, "server", waker)
        cli_conn = _ShmConn(seg, "client", waker)
        rec = {"seg": seg, "conn": srv_conn, "wid": int(worker_id),
               "released": False}
        with self._conns_lock:
            raced_stop = not self._running  # stop() raced the mint
            if not raced_stop:
                self._segments.append(rec)
        if raced_stop:
            self._release_segment(rec)
            raise ConnectionRefusedError("shm parameter server is stopped")
        t = threading.Thread(
            target=self._serve_shm, args=(srv_conn, rec), daemon=True,
            name=f"dkshm-handler-{worker_id}",
        )
        t.start()
        self._handlers.append(t)
        return cli_conn, {
            "wal_frames": self._wal is not None, "worker_id": int(worker_id),
        }

    def _release_segment(self, rec: dict) -> None:
        """Close + UNLINK one connection's segment (idempotent): flag
        both endpoints closed (waking any blocked peer), then remove the
        /dev/shm name — the no-leak contract. The client's mapping stays
        valid until it drops its own references (unlink only removes the
        name)."""
        with self._conns_lock:
            if rec.get("released"):
                return
            rec["released"] = True
            if rec in self._segments:
                self._segments.remove(rec)
        seg = rec["seg"]
        rec["conn"].close()
        rec["conn"].detach_buffer()
        try:
            _WORD.pack_into(seg.buf, _OFF_SERVER_CLOSED, 1)
            _WORD.pack_into(seg.buf, _OFF_CLIENT_CLOSED, 1)
        except (ValueError, TypeError):
            pass
        cond = _waker_for(seg.name)
        with cond:
            cond.notify_all()
        _waker_drop(seg.name)
        try:
            seg.close()
        except BufferError:
            # live numpy views into the mapping (a client mid-teardown):
            # the name still unlinks below; the pages unmap at GC
            pass
        try:
            seg.unlink()
        except FileNotFoundError:
            pass
        unregister_segment(seg.name)

    def ring_occupancy(self) -> list[dict]:
        """Per-connection ring occupancy read straight off the mapped
        headers (no locks, no syscalls): used bytes of each direction's
        ring and the fuller direction's used fraction. The watchtower's
        scraper samples the max across connections into
        ``shm.ring_occupancy_frac`` — near 1.0 means a writer is about
        to block on a stalled reader (or the ring is undersized)."""
        with self._conns_lock:
            recs = list(self._segments)
        out = []
        for rec in recs:
            seg = rec["seg"]
            try:
                buf = seg.buf
                cap = _WORD.unpack_from(buf, _OFF_CAP)[0]
                c2s = (_WORD.unpack_from(buf, _OFF_C2S_HEAD)[0]
                       - _WORD.unpack_from(buf, _OFF_C2S_TAIL)[0])
                s2c = (_WORD.unpack_from(buf, _OFF_S2C_HEAD)[0]
                       - _WORD.unpack_from(buf, _OFF_S2C_TAIL)[0])
            except (ValueError, TypeError):
                continue  # racing a release: this segment is going away
            if cap <= 0:
                continue
            out.append({
                "name": seg.name, "worker_id": rec["wid"],
                "cap": int(cap), "c2s_used": int(c2s),
                "s2c_used": int(s2c),
                "frac": max(int(c2s), int(s2c)) / int(cap),
            })
        return out

    def stop(self) -> None:
        if not self._running:
            self._close_durability()
            return
        self._running = False
        with self._conns_lock:
            recs = list(self._segments)
        for rec in recs:
            self._release_segment(rec)
        for t in self._handlers:
            t.join(timeout=5)
        self._close_durability()

    def _crash(self) -> None:
        """Chaos seam: tear every ring and abandon the WAL un-flushed,
        like the socket server's SIGKILL simulation. Segments are still
        unlinked — a REAL kill would leave /dev/shm entries for a
        restart janitor; the in-process simulation cleans up so chaos
        tests cannot leak them into the suite."""
        self.crashed_ = True
        self._running = False
        with self._conns_lock:
            recs = list(self._segments)
        for rec in recs:
            self._release_segment(rec)
        if self._wal is not None:
            self._wal.abandon()

    def _on_evict(self, worker_ids) -> None:
        """Lease expiry reclaims the zombie's transport too: close its
        connections so their handlers exit and the segments unlink —
        the heartbeat eviction IS the shm lane's abandoned-worker
        garbage collector (satellite: no /dev/shm leaks)."""
        super()._on_evict(worker_ids)
        wids = set(int(w) for w in worker_ids)
        with self._conns_lock:
            recs = [r for r in self._segments if r["wid"] in wids]
        for rec in recs:
            self._release_segment(rec)

    # -- the handler ---------------------------------------------------------

    def _serve_shm(self, conn: _ShmConn, rec: dict) -> None:
        """The socket handler's action dispatch over ring framing. Bulk
        commit/exchange payloads are folded DIRECTLY from the mapped
        ring views — the region is released only after the dispatch
        consumed it (request-reply keeps at most one record in flight,
        so pinning it never deadlocks the sender)."""
        try:
            while True:
                msg, raw, release = conn.recv_msg()
                try:
                    action = msg.get("action")
                    if _trace.enabled():
                        _trace.set_corr(msg.get("corr"))
                    if action == "pull":
                        self._serve_pull_shm(conn, msg["worker_id"])
                    elif action == "pull_int8":
                        self._serve_compressed_pull_shm(
                            conn, msg["worker_id"]
                        )
                    elif action == "commit":
                        try:
                            applied = self.commit(
                                msg["worker_id"], msg["payload"],
                                seq=msg.get("seq"), epoch=msg.get("epoch"),
                                wire_frame=raw,
                            )
                        except networking.FencedEpochError as fe:
                            conn.send_msg({
                                "error": "fenced", "epoch": fe.server_epoch,
                            })
                            continue
                        conn.send_msg({"ok": True, "dup": not applied})
                    elif action == "exchange":
                        self._serve_exchange_shm(conn, msg, raw)
                    elif action == "ping":
                        conn.send_msg({
                            "ok": True, "epoch": self.fence_epoch,
                            "num_updates": self.num_updates,
                            "standby": False,
                            "shard": self.shard_info,
                        })
                    elif action == "shard_map":
                        conn.send_msg({
                            "ok": True, "shard": self.shard_info,
                            "epoch": self.fence_epoch,
                        })
                    elif action == "fence":
                        conn.send_msg({
                            "ok": True,
                            "epoch": self.fence(int(msg["epoch"])),
                        })
                    elif action == "heartbeat":
                        known = self.heartbeat(
                            msg["worker_id"],
                            retries=msg.get("retries", 0),
                        )
                        conn.send_msg({"ok": True, "known": known})
                    elif action == "deregister":
                        self.deregister_worker(msg["worker_id"])
                        conn.send_msg({"ok": True})
                    elif action == "join":
                        out = self.join_worker(msg["worker_id"])
                        out["ok"] = True
                        conn.send_msg(out)
                    elif action == "drain":
                        self.drain_worker(msg["worker_id"],
                                          timeout=bool(msg.get("timeout")))
                        conn.send_msg({"ok": True})
                    elif action == "stats":
                        conn.send_msg({"ok": True, "stats": self.stats()})
                    elif action == "metrics":
                        from distkeras_tpu.observability.metrics import (
                            metrics_reply,
                            ps_metrics,
                        )

                        conn.send_msg(metrics_reply(
                            ps_metrics(self.stats()), self.watchtower,
                        ))
                    elif action in ("stop", "bye"):
                        break
                    else:
                        conn.send_msg({"error": f"bad action {action}"})
                finally:
                    if release is not None:
                        release()
        except (ConnectionError, EOFError, OSError):
            pass  # torn ring / dead peer / injected fault: drop the conn
        except pickle.UnpicklingError:
            pass  # garbled frame rejected by the restricted unpickler
        finally:
            self._release_segment(rec)

    def _serve_pull_shm(self, conn: _ShmConn, worker_id: int) -> None:
        """Bulk-lane pull reply: the immutable center snapshot's leaves
        written ONCE into the ring (no pickle pass); counters land after
        delivery — the same delivered-traffic semantics as TCP."""
        snap, _ = self._begin_pull(worker_id, compressed=False)
        self._begin_reply()
        try:
            conn.send_msg({"weights": snap}, bulk=True)
            self._count(pulls=1, bytes_out=self._center_nbytes)
        finally:
            self._end_reply()

    def _serve_compressed_pull_shm(self, conn: _ShmConn,
                                   worker_id: int) -> None:
        """int8 error-feedback pull with the dropped-reply residual
        rollback (epoch-guarded, same as the socket/native lanes)."""
        snap, st = self._begin_pull(worker_id, compressed=True)
        with st.lock:
            blob, nbytes = self._encode_pull(st, snap)
            epoch = st.epoch
        self._begin_reply()
        try:
            conn.send_msg({"weights": blob}, bulk=True)
            self._count(compressed_pulls=1, bytes_out=nbytes)
        except (ConnectionError, OSError):
            with st.lock:
                if st.epoch == epoch:
                    self._rollback_encode_locked(st, snap, blob)
            raise
        finally:
            self._end_reply()

    def _serve_exchange_shm(self, conn: _ShmConn, msg: dict,
                            raw: bytes | None) -> None:
        """Fused commit+pull over the rings: the commit half folds from
        the request's mapped views (or the pickle lane's decoded frame
        on durable servers, logged verbatim), the pull half ships the
        post-fold snapshot on the bulk lane."""
        compressed = bool(msg.get("compressed"))
        with _trace.span("ps.exchange"):
            try:
                applied, snap, st = self._commit_impl(
                    msg["worker_id"], msg["payload"], seq=msg.get("seq"),
                    epoch=msg.get("epoch"), wire_frame=raw, fused=True,
                    lag=bool(msg.get("lag")), compressed=compressed,
                )
            except networking.FencedEpochError as fe:
                conn.send_msg({"error": "fenced", "epoch": fe.server_epoch})
                return
            if not compressed:
                self._begin_reply()
                try:
                    conn.send_msg(
                        {"ok": True, "dup": not applied, "weights": snap},
                        bulk=True,
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
                conn.send_msg(
                    {"ok": True, "dup": not applied, "weights": blob},
                    bulk=True,
                )
                self._count(compressed_pulls=1, bytes_out=nbytes, fused=1)
            except (ConnectionError, OSError):
                with st.lock:
                    if st.epoch == epoch_:
                        self._rollback_encode_locked(st, snap, blob)
                raise
            finally:
                self._end_reply()


class ShmPSClient(ParameterServerClient):
    """Worker-side shm client — :class:`ParameterServerClient`'s exact
    surface over a ring pair. Control actions (ping/heartbeat/join/
    drain/fence/shard_map/deregister/close) run through the INHERITED
    implementations: ``networking.send_data``/``recv_data`` speak to the
    duck-socket, so the wire semantics (and the fault-injection seam)
    cannot drift from TCP. Only the O(model) paths are overridden:

    - ``pull``/``exchange`` replies arrive on the bulk lane and are
      materialized (one copy out of the mapped ring) before release;
    - ``commit``/``exchange`` requests ship staged delta leaves on the
      bulk lane — written once into the ring, folded server-side from
      the mapped view. Against a DURABLE server (handshake
      ``wal_frames``) they use the pickle lane instead, so the WAL's
      verbatim wire-frame logging and replay work unchanged.
    """

    def __init__(self, server: ShmParameterServer, worker_id: int,
                 pull_compression: str | None = None,
                 epoch: int | None = None):
        from distkeras_tpu.parallel.compression import (
            validate_pull_compression,
        )

        self.pull_compression = validate_pull_compression(pull_compression)
        self.worker_id = int(worker_id)
        self.epoch = None if epoch is None else int(epoch)
        conn, info = server.connect_shm(self.worker_id)
        self._sock = conn  # the duck-socket: inherited actions just work
        self._wal_frames = bool(info.get("wal_frames"))

    def _request(self, msg: dict, bulk: bool) -> dict:
        """One request-reply round trip on the message layer; bulk
        replies are materialized (copy) so the ring region frees before
        the caller holds the tree long-term."""
        self._sock.send_msg(msg, bulk=bulk)
        reply, _raw, _release = self._sock.recv_msg(copy=True)
        return reply

    def pull(self, worker_id: int | None = None) -> Pytree:
        action = "pull_int8" if self.pull_compression == "int8" else "pull"
        reply = self._request(
            {"action": action, "worker_id": self.worker_id}, bulk=False
        )
        if "weights" not in reply:
            raise ProtocolError(
                f"pull refused: {reply.get('error', reply)}", retryable=True
            )
        return maybe_decode(reply["weights"])

    def commit(self, worker_id: int | None, payload: Pytree,
               seq: int | None = None) -> None:
        if not is_encoded(payload):
            payload = utils.tree_to_numpy(payload)
        msg = {
            "action": "commit",
            "worker_id": self.worker_id,
            "payload": payload,
        }
        if _trace.enabled() and (corr := _trace.current_corr()):
            msg["corr"] = corr
        if seq is not None:
            msg["seq"] = int(seq)
        if self.epoch is not None:
            msg["epoch"] = self.epoch
        # durable servers get the pickle lane (verbatim WAL wire frames);
        # otherwise the payload leaves ride the zero-copy bulk lane
        ack = self._request(msg, bulk=not self._wal_frames)
        err = ack.get("error") if isinstance(ack, dict) else None
        if err == "fenced":
            raise networking.FencedEpochError(
                "commit fenced by the server",
                client_epoch=self.epoch, server_epoch=ack.get("epoch"),
            )
        if err is not None:
            raise ProtocolError(f"commit refused: {err}", retryable=True)

    def exchange(self, worker_id: int | None, payload: Pytree,
                 seq: int | None = None, lag: bool = False) -> Pytree:
        if not is_encoded(payload):
            payload = utils.tree_to_numpy(payload)
        msg = {
            "action": "exchange",
            "worker_id": self.worker_id,
            "payload": payload,
        }
        if _trace.enabled() and (corr := _trace.current_corr()):
            msg["corr"] = corr
        if self.pull_compression == "int8":
            msg["compressed"] = True
        if seq is not None:
            msg["seq"] = int(seq)
        if self.epoch is not None:
            msg["epoch"] = self.epoch
        if lag:
            msg["lag"] = True
        reply = self._request(msg, bulk=not self._wal_frames)
        err = reply.get("error") if isinstance(reply, dict) else None
        if err == "fenced":
            raise networking.FencedEpochError(
                "exchange fenced by the server",
                client_epoch=self.epoch, server_epoch=reply.get("epoch"),
            )
        if "weights" not in reply:
            raise ProtocolError(
                f"exchange refused: {reply.get('error', reply)}",
                retryable=True,
            )
        return maybe_decode(reply["weights"])
