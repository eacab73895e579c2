"""Socket front end for the generation engine.

Same transport discipline as the parameter-server tier: length-prefixed
restricted-pickle frames (``networking.py`` — a forged frame cannot execute
code), one handler thread per connection, typed :class:`ProtocolError`
triage so the reconnecting client can tell weather (peer died mid-frame —
retry) from protocol violations (fatal) from backpressure
(:class:`ServerBusyError` — back off and resubmit).

Wire protocol: the client sends ``{"action": "generate", "prompt":
int32 array, "max_new_tokens": n, ...sampling knobs...}`` and blocks for
``{"ok": True, "tokens": int32 array, "new_tokens": n}``. While a request
is in flight the handler polls the connection for liveness: a client that
dies mid-generation is detected by its EOF, its request is cancelled, and
the scheduler frees its cache blocks the next iteration — a dead
connection cannot leak pool memory (the resilience triage the integration
test kills a client to prove). ``stats`` returns the engine + server
counters; ``server.stop(drain=True)`` stops admission, lets in-flight
requests finish, then closes.

:class:`ResilientGenerationClient` mirrors ``ResilientPSClient``: a client
factory + :class:`~distkeras_tpu.resilience.retry.RetryPolicy`, reconnect
on retryable failure, jittered backoff on busy. Generation is one
idempotent request/response, so a replay after a dead server is safe —
no seqno machinery needed.
"""

from __future__ import annotations

import select
import socket
import threading
from typing import Callable

import numpy as np

from distkeras_tpu import networking
from distkeras_tpu.networking import ProtocolError, ServerBusyError
from distkeras_tpu.serving.scheduler import GenerationEngine, Request

_SAMPLING_KEYS = ("max_new_tokens", "temperature", "top_k", "top_p",
                  "seed", "eos_id", "request_id", "slo_class", "tenant")


class GenerationServer:
    """Threaded TCP service around a :class:`GenerationEngine`.

    ``initialize()`` binds (ephemeral port resolved into ``.port``),
    ``start()`` runs the accept loop and the engine thread; ``stop()``
    drains gracefully by default."""

    def __init__(self, engine: GenerationEngine, host: str = "127.0.0.1",
                 port: int = 0, poll_interval: float = 0.05,
                 trace: bool = False, trace_dir: str | None = None,
                 trace_sample: float = 1.0):
        self.engine = engine
        self.host = host
        self.port = int(port)
        self.poll_interval = float(poll_interval)
        # Flight recorder (ISSUE 11): trace=/trace_dir= arm the span
        # recorder for this server's lifetime (request lifecycle spans —
        # enqueue→admit→prefill→decode→retire — stitched by request id);
        # stop() writes the timeline to trace_dir (path in trace_path_).
        # Ownership mirrors the trainer's: only an enable WE performed
        # is disabled at stop, so a bench that already enabled tracing
        # keeps its recorder.
        self.trace = bool(trace) or trace_dir is not None
        self.trace_dir = trace_dir
        self.trace_sample = float(trace_sample)
        self.trace_path_: str | None = None
        self._trace_owner = False
        self._server_sock: socket.socket | None = None
        self._accept_thread: threading.Thread | None = None
        self._handlers: list[threading.Thread] = []
        self._conns: list[socket.socket] = []
        self._conns_lock = threading.Lock()
        self._running = False
        self.connections_ = 0
        self.dead_connections_ = 0
        # Watchtower (ISSUE 13): attach one and the `metrics` wire
        # action carries its alert ledger to remote scrapers (the CLI's
        # `health --watch` relays server-side alerts it cannot derive
        # from counters alone)
        self.watchtower = None
        # Membership directory (ISSUE 15): register_with() publishes this
        # replica under the "serve" role with a renewed lease, so a
        # RoutedGenerationClient discovers it — and a killed replica's
        # entry ages out instead of lying
        self._dir_reg: tuple | None = None   # (client, key, ttl, epoch)
        self._dir_renewer: threading.Thread | None = None
        self._dir_stop = threading.Event()
        # Live deployment (distkeras_tpu/deploy): a SnapshotStore of
        # streamed model versions. With one attached, the
        # deploy_activate wire action swaps the engine to any stored
        # version, and the directory registration meta carries the
        # CURRENT model_version (re-published by the renewer, so a swap
        # shows up fleet-wide within ttl/3).
        self.snapshots = None

    def initialize(self) -> None:
        self._server_sock = socket.socket(socket.AF_INET,
                                          socket.SOCK_STREAM)
        self._server_sock.setsockopt(socket.SOL_SOCKET,
                                     socket.SO_REUSEADDR, 1)
        self._server_sock.bind((self.host, self.port))
        self.port = self._server_sock.getsockname()[1]
        self._server_sock.listen(64)
        self._running = True

    def start(self) -> None:
        if self._server_sock is None:
            self.initialize()
        if self.trace:
            from distkeras_tpu.observability import trace as _trace

            if not _trace.enabled():
                _trace.enable(sample=self.trace_sample)
                self._trace_owner = True
        self.engine.start()
        self._accept_thread = threading.Thread(target=self._accept_loop,
                                               daemon=True)
        self._accept_thread.start()

    def _accept_loop(self) -> None:
        while self._running:
            try:
                conn, _ = self._server_sock.accept()
            except OSError:
                break
            if not self._running:
                conn.close()
                break
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            with self._conns_lock:
                self._conns.append(conn)
                self.connections_ += 1
            t = threading.Thread(target=self._handle, args=(conn,),
                                 daemon=True)
            t.start()
            # reap finished handlers: client connections are many and
            # short-lived here (unlike the PS tier's few long-lived
            # workers) — keeping every Thread ever accepted grows
            # memory linearly with total connections
            self._handlers = [h for h in self._handlers if h.is_alive()]
            self._handlers.append(t)

    @staticmethod
    def _peer_dead(conn: socket.socket) -> bool:
        """EOF probe without consuming data: readable + empty peek means
        the peer closed (readable with bytes would be a pipelined frame —
        left buffered; this protocol is strictly request/response, so data
        here just waits for the current reply). ``poll`` rather than
        ``select``: a loaded server holds more than FD_SETSIZE=1024
        descriptors and ``select()`` raises on any fd beyond it."""
        try:
            p = select.poll()
            p.register(conn, select.POLLIN)
            if not p.poll(0):
                return False
            return conn.recv(1, socket.MSG_PEEK) == b""
        except (OSError, ValueError):
            return True

    def _serve_generate(self, conn: socket.socket, msg: dict) -> None:
        try:
            prompt = np.asarray(msg["prompt"], np.int32)
            knobs = {k: msg[k] for k in _SAMPLING_KEYS if k in msg}
            req = self.engine.submit(prompt, **knobs)
        except ServerBusyError as e:
            networking.send_data(conn, {"error": "busy",
                                        "message": str(e)})
            return
        except (ValueError, TypeError, KeyError) as e:
            networking.send_data(conn, {"error": "bad_request",
                                        "message": str(e)})
            return
        # wait for completion, watching the connection: a client killed
        # mid-stream must free its blocks, not ride the batch to the end
        while not req.wait(self.poll_interval):
            if self._peer_dead(conn):
                self.engine.cancel(req)
                with self._conns_lock:
                    self.dead_connections_ += 1
                raise ConnectionResetError(
                    f"client died mid-generation ({req.id} cancelled)"
                )
        if req.state == "done":
            networking.send_data(conn, {
                "ok": True,
                "tokens": np.asarray(req.new_tokens, np.int32),
                "new_tokens": len(req.new_tokens),
                "request_id": req.id,
            })
        else:
            networking.send_data(conn, {
                "error": req.state,
                "message": req.error or req.state,
                "request_id": req.id,
                # a server-side cancel (stop/drain tearing the batch) is
                # retryable weather to a routed client — the request is
                # idempotent and a sibling replica can serve it; a
                # "failed" model error is deterministic and is not
                "retryable": req.state == "cancelled",
            })

    def _handle(self, conn: socket.socket) -> None:
        try:
            while True:
                msg = networking.recv_data(conn)
                action = msg.get("action")
                if action == "generate":
                    self._serve_generate(conn, msg)
                elif action == "stats":
                    networking.send_data(conn, {"ok": True,
                                                "stats": self.stats()})
                elif action == "deploy_activate":
                    # hot swap: stage a stored snapshot version onto the
                    # engine (applied between decode steps — the version
                    # gate). The rollout controller's activation path.
                    networking.send_data(
                        conn, self._deploy_activate(msg)
                    )
                elif action == "deploy_status":
                    store = self.snapshots
                    networking.send_data(conn, {
                        "ok": True,
                        "model_version": self.engine.model_version,
                        "staged_version": (
                            self.engine._staged_swap[1]
                            if self.engine._staged_swap else None
                        ),
                        "versions": (
                            store.versions() if store is not None else []
                        ),
                    })
                elif action == "metrics":
                    # unified metrics surface (ISSUE 11/13): the serving
                    # counters + per-class latency summary normalized
                    # into typed metrics — the ONE metrics_reply shape
                    # every server sends, plus the alert ledger when a
                    # watchtower is attached
                    from distkeras_tpu.observability.metrics import (
                        metrics_reply,
                        serving_metrics,
                    )

                    networking.send_data(conn, metrics_reply(
                        serving_metrics(self.stats()), self.watchtower,
                    ))
                else:
                    networking.send_data(conn, {
                        "error": "bad_request",
                        "message": f"unknown action {action!r}",
                    })
        except (ConnectionError, EOFError, OSError):
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._conns_lock:
                if conn in self._conns:
                    self._conns.remove(conn)

    def _deploy_activate(self, msg: dict) -> dict:
        version = int(msg["version"])
        policy = msg.get("policy", "drain")
        store = self.snapshots
        if store is None:
            return {"ok": False, "error": "no snapshot store attached"}
        snap = store.get(version)
        if snap is None:
            return {"ok": False, "error": f"unknown version {version}",
                    "versions": store.versions()}
        try:
            self.engine.swap_params(snap.tree, snap.version, policy=policy)
        except ValueError as e:
            return {"ok": False, "error": str(e)}
        return {"ok": True, "version": snap.version, "policy": policy}

    def register_with(self, directory, key: str | None = None,
                      ttl: float = 5.0, epoch: int = 0) -> str:
        """Publish this replica into a membership directory (ISSUE 15):
        ``("serve", key) → (host, port)`` with a ``ttl`` lease renewed
        by a background thread at a third of the lease, so the entry
        expires within one TTL of this replica's death and the router's
        next refresh drops it. The registration meta carries the
        engine's CURRENT ``model_version`` and is refreshed on every
        renewal — a hot swap is visible to routers within ``ttl/3``.
        ``stop()`` withdraws cleanly. Returns the registered key."""
        from distkeras_tpu.directory.client import DirectoryClient

        if not isinstance(directory, DirectoryClient):
            directory = DirectoryClient(directory)
        if key is None:
            key = f"{self.host}:{self.port}"

        def publish():
            # the meta rides every renewal, so a hot swap (version) and a
            # warming prefix cache (hit rate → router affinity weights,
            # ISSUE 17) are both fleet-visible within ttl/3
            directory.publish(
                "serve", key, self.host, self.port, epoch=int(epoch),
                ttl=float(ttl),
                meta={
                    "model_version": int(self.engine.model_version),
                    "prefix_hit_rate": float(
                        self.engine.prefix_hit_rate()),
                },
            )

        publish()
        self._dir_reg = (directory, key, float(ttl), int(epoch))
        self._dir_stop.clear()

        def renewer():
            while not self._dir_stop.wait(max(ttl / 3.0, 0.05)):
                try:
                    publish()
                except Exception:
                    pass  # directory weather; the next tick retries

        self._dir_renewer = threading.Thread(
            target=renewer, daemon=True, name="dk-serve-dir-renew",
        )
        self._dir_renewer.start()
        return key

    def _withdraw_registration(self) -> None:
        self._dir_stop.set()
        if self._dir_renewer is not None:
            self._dir_renewer.join(timeout=2)
            self._dir_renewer = None
        reg, self._dir_reg = self._dir_reg, None
        if reg is not None:
            directory, key, _ttl, epoch = reg
            try:
                directory.withdraw("serve", key, epoch=epoch)
            except Exception:
                pass  # the lease expiry is the backstop

    def stats(self) -> dict:
        s = self.engine.stats()
        with self._conns_lock:
            s["connections"] = self.connections_
            s["open_connections"] = len(self._conns)
            s["dead_connections"] = self.dead_connections_
        return s

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Graceful by default: stop accepting, let every admitted request
        finish and its reply flush, then tear down."""
        self._withdraw_registration()
        self._running = False
        if self._server_sock is not None:
            # shutdown first: close() alone does not wake an accept()
            # blocked in the accept thread, and the socket would go on
            # listening until the next connection arrived
            try:
                self._server_sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                self._server_sock.close()
            except OSError:
                pass
        self.engine.stop(drain=drain, timeout=timeout)
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        for t in self._handlers:
            t.join(timeout=2)
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=2)
        if self.trace:
            import os as _os
            import time as _time

            from distkeras_tpu.observability import trace as _trace

            if self.trace_dir is not None and _trace.enabled():
                self.trace_path_ = _trace.save(_os.path.join(
                    self.trace_dir,
                    f"serve-trace-{_os.getpid()}-{_time.time_ns()}.json",
                ))
            if self._trace_owner:
                _trace.disable()
                self._trace_owner = False


class GenerationClient:
    """Blocking request/response client for :class:`GenerationServer`."""

    def __init__(self, host: str, port: int,
                 connect_timeout: float | None = 30.0):
        self._sock = networking.connect(host, port,
                                        timeout=connect_timeout)
        self._sock.settimeout(None)

    def generate(self, prompt, *, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int | None = None,
                 top_p: float | None = None, seed: int = 0,
                 eos_id: int | None = None,
                 request_id: str | None = None,
                 slo_class: str = "default",
                 tenant: str = "default") -> np.ndarray:
        networking.send_data(self._sock, {
            "action": "generate",
            "prompt": np.asarray(prompt, np.int32),
            "max_new_tokens": int(max_new_tokens),
            "temperature": float(temperature),
            "top_k": top_k, "top_p": top_p, "seed": int(seed),
            "eos_id": eos_id, "request_id": request_id,
            "slo_class": str(slo_class), "tenant": str(tenant),
        })
        r = networking.recv_data(self._sock)
        if r.get("error") == "busy":
            raise ServerBusyError(r.get("message", "server busy"),
                                  peer=networking._peer_of(self._sock))
        if "error" in r:
            # bad_request / failed: replaying the same frame can only
            # fail the same way. A server-side "cancelled" (stop/drain)
            # carries retryable=True — a routed/resilient client replays
            # it against whoever serves next.
            raise ProtocolError(
                f"server rejected request: {r['error']}: "
                f"{r.get('message', '')}",
                peer=networking._peer_of(self._sock),
                retryable=bool(r.get("retryable")),
            )
        return np.asarray(r["tokens"], np.int32)

    def stats(self) -> dict:
        networking.send_data(self._sock, {"action": "stats"})
        r = networking.recv_data(self._sock)
        return r["stats"]

    def deploy_activate(self, version: int,
                        policy: str = "drain") -> dict:
        """Hot-swap the server to a stored snapshot ``version`` (the
        rollout controller's activation RPC). Returns the server's reply
        (``ok=False`` with the available versions on a miss)."""
        networking.send_data(self._sock, {
            "action": "deploy_activate", "version": int(version),
            "policy": str(policy),
        })
        return networking.recv_data(self._sock)

    def deploy_status(self) -> dict:
        """Current/staged model version + stored snapshot versions."""
        networking.send_data(self._sock, {"action": "deploy_status"})
        return networking.recv_data(self._sock)

    def wait_for_swap(self, timeout: float = 10.0,
                      poll: float = 0.02) -> dict:
        """Block until no swap is staged (``deploy_status()``'s
        ``staged_version`` is None — a drain landed, a refill applied)
        and return the final status. Replaces the hand-rolled
        staged-swap polling every deploy test used to write. Raises
        :class:`TimeoutError` with the stuck status when ``timeout``
        elapses first — e.g. a drain-policy swap behind a request that
        never finishes."""
        import time as _time

        deadline = _time.monotonic() + float(timeout)
        while True:
            status = self.deploy_status()
            if status.get("staged_version") is None:
                return status
            if _time.monotonic() >= deadline:
                raise TimeoutError(
                    f"swap still staged after {timeout}s: {status}"
                )
            _time.sleep(poll)

    def set_timeout(self, seconds: float | None) -> None:
        self._sock.settimeout(seconds)

    def close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass


class ResilientGenerationClient:
    """Reconnect-and-retry wrapper over a :class:`GenerationClient`
    factory — the serving sibling of ``ResilientPSClient``. Retryable
    failures (dead server mid-frame, connection refused during a restart,
    :class:`ServerBusyError` backpressure) reconnect under the
    ``RetryPolicy``'s jittered backoff and replay the request; generation
    is a pure request/response, so a replay is safe without seqnos. A
    fixed ``seed`` per request keeps the replayed stream identical."""

    def __init__(self, make_client: Callable[[], GenerationClient],
                 policy=None):
        from distkeras_tpu.resilience.retry import RetryPolicy

        self._make_client = make_client
        self.policy = policy if policy is not None else RetryPolicy()
        self._client = make_client()
        self.retries = 0
        self.reconnects = 0
        self._calls = 0

    def _reconnect(self, attempt: int, exc: BaseException) -> None:
        self.retries += 1
        if isinstance(exc, ServerBusyError):
            return      # server is healthy, just full: keep the connection
        try:
            self._client.close()
        except Exception:
            pass
        try:
            self._client = self._make_client()
            self.reconnects += 1
        except Exception:
            pass        # still down: next attempt fails fast, backs off

    def _run(self, fn):
        self._calls += 1
        return self.policy.run(fn, on_retry=self._reconnect,
                               salt=self._calls)

    def generate(self, prompt, **kw) -> np.ndarray:
        return self._run(lambda: self._client.generate(prompt, **kw))

    def stats(self) -> dict:
        return self._run(lambda: self._client.stats())

    def close(self) -> None:
        self._client.close()
