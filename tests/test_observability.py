"""ISSUE 11: the flight recorder — tracing, metrics surface, health.

Pins, per the acceptance criteria:

- span nesting/ordering and the Chrome-trace JSON shape (Perfetto
  loadable: ``ph: "X"`` complete events with µs timestamps + thread
  metadata);
- the off path is allocation-free on the hot-path entry points
  (``span``/``record``/``instant``/``set_corr``);
- cross-process correlation-id stitching: the worker-side exchange span
  and the PS-side fold/WAL-append spans share one id, over the socket
  frame corr AND the native wire's (wid, seqno);
- the Prometheus text exposition format of the unified metrics surface,
  and the ``metrics``/``stats`` wire actions serving it live;
- the stats settling barrier: end-of-run counter reads are EXACT (the
  PR 10 delivered-traffic ≤1-per-worker tolerance is retired);
- the acceptance run: seeded kill + drops, 2 workers, WAL on → ONE
  trace file in which the same fused EXCHANGE's worker-side span and
  PS-side fold/WAL-append spans share a correlation id.
"""

import gc
import json
import os
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from distkeras_tpu.observability import trace
from distkeras_tpu.observability.metrics import (
    MetricsRegistry,
    health_snapshot,
    ps_metrics,
    serving_metrics,
)
from distkeras_tpu.parallel.merge_rules import DownpourMerge
from distkeras_tpu.parameter_servers import (
    ParameterServer,
    ParameterServerClient,
    SocketParameterServer,
    build_ps_stats,
)


@pytest.fixture(autouse=True)
def _trace_off():
    """Every test starts and ends with tracing disabled — a leaked
    global tracer would silently contaminate later tests' off-path
    assertions."""
    trace.disable()
    yield
    trace.disable()


# -- the span API ------------------------------------------------------------


def test_span_nesting_and_ordering():
    trace.enable()
    with trace.span("outer"):
        with trace.span("inner.a"):
            pass
        with trace.span("inner.b"):
            pass
    evs = trace.events()
    by = {e["name"]: e for e in evs}
    assert set(by) == {"outer", "inner.a", "inner.b"}
    out, a, b = by["outer"], by["inner.a"], by["inner.b"]
    # containment: children start after the parent and end before it
    for child in (a, b):
        assert out["t0_ns"] <= child["t0_ns"]
        assert child["t0_ns"] + child["dur_ns"] \
            <= out["t0_ns"] + out["dur_ns"]
    # ordering: a before b, and events() is sorted by start time
    assert a["t0_ns"] + a["dur_ns"] <= b["t0_ns"]
    assert [e["t0_ns"] for e in evs] == sorted(e["t0_ns"] for e in evs)


def test_off_mode_is_allocation_free_on_the_hot_path():
    """The zero-cost-when-off contract: with tracing disabled, the hot
    call sites (span enter/exit, retroactive record, corr set, instant)
    allocate NOTHING — measured with the allocator's live-block count,
    GC off, after a warm-up pass."""
    assert not trace.enabled()

    def hot(n):
        s = trace.span
        for _ in range(n):
            with s("worker.fetch"):
                pass
            trace.record("worker.commit", 1, 2)
            trace.set_corr("w0:x1")
            trace.instant("ps.join")

    hot(16)  # warm-up: caches, code objects, int freelists
    gc.collect()
    gc.disable()
    try:
        before = sys.getallocatedblocks()
        hot(10_000)
        after = sys.getallocatedblocks()
    finally:
        gc.enable()
    # a single allocation per call would cost >= 40k live or transient
    # blocks here; the interpreter itself wanders by a handful (caches,
    # freelist growth), so the bound is "orders of magnitude below one
    # per call", not literal zero
    assert after - before < 100, \
        f"off-path allocated {after - before} blocks over 40k calls"


def test_corr_inheritance_at_close_and_explicit_override():
    trace.enable()
    trace.set_corr("w1:x1")
    with trace.span("a"):
        # corr resolves when the span CLOSES — a wire call that assigns
        # the seqno mid-span re-stamps it
        trace.set_corr("w1:s9")
    trace.record("b", 10, 20)                 # inherits current corr
    trace.record("c", 10, 20, corr="explicit")
    by = {e["name"]: e["corr"] for e in trace.events()}
    assert by == {"a": "w1:s9", "b": "w1:s9", "c": "explicit"}


def test_ring_overflow_drops_oldest():
    trace.enable(ring_size=16)
    for i in range(20):
        trace.record(f"s{i}", i, i + 1)
    evs = trace.events()
    assert len(evs) == 16
    assert [e["name"] for e in evs] == [f"s{i}" for i in range(4, 20)]
    assert trace._tracer.dropped() == 4


def test_deterministic_sampling_keeps_exact_fraction():
    trace.enable(sample=0.5)
    for i in range(100):
        trace.record(f"s{i}", i, i + 1)
    assert len(trace.events()) == 50


def test_save_writes_perfetto_loadable_chrome_trace(tmp_path):
    trace.enable()
    trace.set_corr("w0:s1")
    with trace.span("worker.commit", args={"k": 1}):
        pass
    path = trace.save(str(tmp_path / "t" / "trace.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    assert any(e["name"] == "thread_name" for e in meta)
    # the run log (what this process compiled and trained so far) is
    # saved with the ring
    xs = [e for e in evs if e["ph"] == "X"
          and e["name"].startswith("worker.")]
    assert len(xs) == 1
    x = xs[0]
    assert x["name"] == "worker.commit"
    assert x["args"]["corr"] == "w0:s1" and x["args"]["k"] == 1
    assert isinstance(x["ts"], float) and x["dur"] >= 0
    assert doc["otherData"]["dropped_events"] == 0


def test_save_without_enable_raises():
    with pytest.raises(RuntimeError):
        trace.save("/tmp/never-written.json")


def test_enable_is_idempotent_and_keeps_the_outer_recorder():
    t1 = trace.enable()
    trace.record("kept", 1, 2)
    t2 = trace.enable(ring_size=32)  # nested enable must NOT reset
    assert t1 is t2
    assert [e["name"] for e in trace.events()] == ["kept"]


# -- the metrics surface -----------------------------------------------------


def test_prometheus_exposition_format():
    s = build_ps_stats(10, 2, 8, 100, 200, 20, 5, 7, 2.0,
                       dup_commits=1, fused_exchanges=3, num_updates=8)
    s["exchange_phases"] = {
        "fetch": {"count": 4, "total_ms": 2.0, "max_ms": 1.0,
                  "hist_ms_le": [0.25, 0.5, "inf"], "hist": [1, 2, 1]},
    }
    text = ps_metrics(s).to_prometheus()
    lines = text.splitlines()
    # typed headers + exact sample values
    assert "# TYPE dk_ps_pulls_total counter" in lines
    assert "dk_ps_pulls_total 10" in lines
    assert "# TYPE dk_ps_num_updates gauge" in lines
    assert "dk_ps_num_updates 8" in lines
    assert "dk_ps_fused_exchanges_total 3" in lines
    # histogram expansion: cumulative buckets + +Inf + sum/count
    assert "# TYPE dk_worker_exchange_phase_ms histogram" in lines
    assert 'dk_worker_exchange_phase_ms_bucket{phase="fetch",le="0.25"} 1' \
        in lines
    assert 'dk_worker_exchange_phase_ms_bucket{phase="fetch",le="0.5"} 3' \
        in lines
    assert 'dk_worker_exchange_phase_ms_bucket{phase="fetch",le="+Inf"} 4' \
        in lines
    assert 'dk_worker_exchange_phase_ms_count{phase="fetch"} 4' in lines
    # every non-comment line parses as `name[{labels}] value`
    for ln in lines:
        if ln.startswith("#") or not ln:
            continue
        name, val = ln.rsplit(" ", 1)
        float(val)
        assert name[0].isalpha()


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.gauge("dk_test", 1, labels={"p": 'a"b\\c\nd'})
    assert r'dk_test{p="a\"b\\c\nd"} 1' in reg.to_prometheus()


def test_registry_rejects_kind_conflicts():
    reg = MetricsRegistry()
    reg.counter("dk_x_total", 1)
    with pytest.raises(ValueError):
        reg.gauge("dk_x_total", 2)


def test_ps_metrics_fans_out_per_shard_labels():
    shard0 = build_ps_stats(4, 0, 4, 1, 1, 8, 0, 0, 1.0)
    shard0["shard_id"] = 0
    shard1 = build_ps_stats(6, 0, 6, 1, 1, 12, 0, 0, 1.0)
    shard1["shard_id"] = 1
    agg = build_ps_stats(10, 0, 10, 2, 2, 20, 0, 0, 1.0)
    agg["per_shard"] = [shard0, shard1]
    text = ps_metrics(agg).to_prometheus()
    assert "dk_ps_pulls_total 10" in text            # the aggregate
    assert 'dk_ps_pulls_total{shard="0"} 4' in text  # labeled series
    assert 'dk_ps_pulls_total{shard="1"} 6' in text


def test_serving_metrics_normalization():
    stats = {"submitted": 5, "completed": 4, "queued": 1, "active": 2,
             "blocks_in_use": 7, "tokens_generated": 40}
    text = serving_metrics(stats).to_prometheus()
    assert "dk_serve_submitted_total 5" in text
    assert "dk_serve_queue_depth 1" in text
    assert "dk_serve_blocks_in_use 7" in text


def test_health_snapshot_one_document(tmp_path):
    wal_dir = str(tmp_path / "wal")
    ps = ParameterServer({"w": np.zeros(32, np.float32)}, DownpourMerge(),
                         2, wal_dir=wal_dir)
    for k in range(6):
        ps.pull(k % 2)
        ps.commit(k % 2, {"w": np.full(32, 0.1, np.float32)}, seq=k + 1)
    stats = ps.stats()
    ps.stop()
    doc = health_snapshot(wal_root=wal_dir, ps_stats=stats)
    json.dumps(doc)  # JSON-clean end to end
    assert doc["ok"]
    assert doc["wal"]["record_totals"]["commit"] == 6
    assert doc["membership"]["num_updates"] == 6
    assert "dk_ps_commits_total" in doc["metrics"]
    assert doc["metrics"]["dk_ps_commits_total"]["samples"][0]["value"] \
        == 6


def test_health_cli(tmp_path, capsys):
    from distkeras_tpu.observability.__main__ import main as obs_main

    wal_dir = str(tmp_path / "wal")
    ps = ParameterServer({"w": np.zeros(16, np.float32)}, DownpourMerge(),
                         1, wal_dir=wal_dir)
    ps.pull(0)
    ps.commit(0, {"w": np.ones(16, np.float32)}, seq=1)
    ps.stop()
    rc = obs_main(["health", "--wal-dir", wal_dir])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["ok"]
    assert doc["wal"]["record_totals"]["commit"] == 1


# -- live wire actions + the settling barrier --------------------------------


def _socket_ps(tmp_path=None, num_workers=1, **kw):
    ps = SocketParameterServer(
        {"w": np.zeros(8, np.float32)}, DownpourMerge(), num_workers,
        **kw,
    )
    ps.initialize()
    ps.start()
    return ps


def test_stats_settling_barrier_makes_end_of_run_reads_exact():
    """The ISSUE 11 counter-lag fix, unit level: the moment a client has
    RECEIVED a pull/exchange reply, a stats() read must count it — the
    server settles in-flight reply windows before reading."""
    ps = _socket_ps()
    try:
        c = ParameterServerClient("127.0.0.1", ps.port, 0)
        for _ in range(5):
            c.pull()
        for k in range(3):
            c.exchange(0, {"w": np.ones(8, np.float32)}, seq=k + 1)
        s = ps.stats()  # immediately — no sleep, no tolerance
        assert s["pulls"] == 8          # 5 standalone + 3 fused halves
        assert s["commits"] == 3
        assert s["fused_exchanges"] == 3
        assert s["exchange_rtts"] == 8
        c.close()
    finally:
        ps.stop()


def test_metrics_and_stats_wire_actions():
    from distkeras_tpu import networking

    ps = _socket_ps()
    try:
        c = ParameterServerClient("127.0.0.1", ps.port, 0)
        c.pull()
        sock = networking.connect("127.0.0.1", ps.port)
        networking.send_data(sock, {"action": "stats"})
        reply = networking.recv_data(sock)
        assert reply["ok"] and reply["stats"]["pulls"] == 1
        networking.send_data(sock, {"action": "metrics"})
        reply = networking.recv_data(sock)
        assert reply["ok"]
        assert "dk_ps_pulls_total 1" in reply["prom"]
        assert reply["metrics"]["dk_ps_pulls_total"]["kind"] == "counter"
        networking.send_data(sock, {"action": "bye"})
        sock.close()
        c.close()
    finally:
        ps.stop()


def test_observability_cli_dump_against_live_ps(capsys):
    from distkeras_tpu.observability.__main__ import main as obs_main

    ps = _socket_ps()
    try:
        rc = obs_main(["dump", "--port", str(ps.port), "--prom"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "# TYPE dk_ps_commits_total counter" in out
        rc = obs_main(["tail", "--port", str(ps.port), "--count", "2",
                       "--interval", "0.05"])
        out = capsys.readouterr().out
        assert rc == 0 and len(out.strip().splitlines()) == 2
    finally:
        ps.stop()


# -- cross-process correlation stitching -------------------------------------


def test_socket_correlation_stitching_with_wal(tmp_path):
    """One fused EXCHANGE over the socket wire: the worker-side span,
    the PS handler's fold span, and the WAL-append span all close under
    the resilient client's ``w<id>:s<seq>`` correlation id (the frame
    carries it; the handler thread adopts it)."""
    from distkeras_tpu.resilience.retry import ResilientPSClient

    trace.enable()
    ps = _socket_ps(wal_dir=str(tmp_path / "wal"))
    try:
        c = ResilientPSClient(
            lambda: ParameterServerClient("127.0.0.1", ps.port, 0), 0,
        )
        c.pull(0)
        with trace.span("worker.exchange"):
            c.exchange(0, {"w": np.ones(8, np.float32)})
        corr = trace.current_corr()
        assert corr is not None and corr.startswith("w0:s")

        def names_with(corr_):
            return {e["name"] for e in trace.events()
                    if e["corr"] == corr_}

        # The handler's ``ps.exchange`` span wraps the reply send, so it
        # closes AFTER the client's exchange() returns — give the server
        # thread a beat to land it before reading the event log.
        deadline = time.monotonic() + 5.0
        got = names_with(corr)
        while "ps.exchange" not in got and time.monotonic() < deadline:
            time.sleep(0.01)
            got = names_with(corr)
        assert "worker.exchange" in got
        assert "ps.fold" in got
        assert "ps.wal_append" in got
        assert "ps.exchange" in got  # the handler's serve span
        c.close()
    finally:
        ps.stop()


def test_native_correlation_stitching(tmp_path):
    """The same stitching over the native wire: the C++ span ring
    records (wid, seqno) per fold/WAL-wait section, and the scraper
    rebuilds the SAME ``w<id>:s<seq>`` id the resilient client stamped
    worker-side."""
    from distkeras_tpu.native import load_dkps

    if load_dkps() is None:
        pytest.skip("no C++ toolchain to build libdkps")
    from distkeras_tpu.native_ps import (
        NativePSClient,
        NativeSocketParameterServer,
    )
    from distkeras_tpu.resilience.retry import ResilientPSClient

    trace.enable()
    srv = NativeSocketParameterServer(
        {"w": np.zeros(32, np.float32)}, DownpourMerge(), 1,
        wal_dir=str(tmp_path / "wal"),
    )
    srv.initialize()
    srv.start()
    srv.set_trace(True)
    try:
        c = ResilientPSClient(
            lambda: NativePSClient("127.0.0.1", srv.port, 0, srv.spec),
            0,
        )
        c.pull(0)
        with trace.span("worker.exchange"):
            c.exchange(0, {"w": np.ones(32, np.float32)})
        corr = trace.current_corr()
        assert corr is not None and corr.startswith("w0:s")
        native = srv.scrape_trace_events()
        assert any(e["name"] == "ps.fold" and e["corr"] == corr
                   for e in native), native
        assert any(e["name"] == "ps.wal_wait" and e["corr"] == corr
                   for e in native), native
        assert any(e["name"] == "wal.fsync" for e in native), native
        # merged into ONE timeline next to the worker-side span
        trace.add_events(native)
        evs = trace.events()
        got = {e["name"] for e in evs if e["corr"] == corr}
        assert {"worker.exchange", "ps.fold", "ps.wal_wait"} <= got
        # a second scrape is empty: the ring drains on read
        assert srv.scrape_trace_events() == []
        c.close()
    finally:
        srv.stop()


# -- trainer integration + the acceptance run --------------------------------


def test_trainer_knob_validation():
    import distkeras_tpu as dk

    from tests.test_trainers import model_spec

    with pytest.raises(ValueError, match="backend='ps'"):
        dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                worker_optimizer="sgd", num_workers=2, trace=True)
    with pytest.raises(ValueError, match="trace_sample"):
        dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                worker_optimizer="sgd", num_workers=2, backend="ps",
                trace=True, trace_sample=0.0)


def test_inprocess_trainer_trace_writes_timeline(tmp_path):
    """A plain in-process PS run with trace_dir=: the timeline file
    exists, loads, and carries the worker phase spans + PS fold spans —
    and the recorder is disabled again once the run returns."""
    import distkeras_tpu as dk

    from tests.test_trainers import blobs_dataset, model_spec

    ds = blobs_dataset(n=256)
    t = dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                worker_optimizer="sgd", learning_rate=0.05,
                num_workers=2, batch_size=16, communication_window=2,
                num_epoch=1, backend="ps",
                trace_dir=str(tmp_path / "traces"))
    t.train(ds, shuffle=False)
    assert not trace.enabled()  # the run owned and released the recorder
    assert t.trace_path_ is not None and os.path.exists(t.trace_path_)
    with open(t.trace_path_) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"worker.fetch", "worker.compress", "worker.commit",
            "ps.fold"} <= names


def test_acceptance_chaos_trace_stitches_one_exchange(tmp_path):
    """THE acceptance criterion: a seeded kill + drops chaos run
    (2 workers, WAL on, socket transport) produces ONE Perfetto-loadable
    trace file in which the same fused EXCHANGE's worker-side span and
    the PS-side fold / WAL-append spans share a correlation id."""
    import distkeras_tpu as dk

    from distkeras_tpu.resilience.faults import FaultPlan
    from distkeras_tpu.resilience.retry import RetryPolicy
    from tests.test_trainers import blobs_dataset, model_spec

    ds = blobs_dataset(n=512)
    plan = FaultPlan(seed=13, drop_recv=0.02, delay=0.03, delay_s=0.002,
                     kill_ps_after_commits=6, max_faults=30)
    t = dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                worker_optimizer="sgd", learning_rate=0.05,
                num_workers=2, batch_size=16, communication_window=2,
                num_epoch=2, backend="ps", ps_transport="socket",
                ps_wal_dir=str(tmp_path / "wal"), ps_snapshot_every=5,
                ps_failover_timeout=0.4,
                retry_policy=RetryPolicy(max_attempts=100,
                                         base_delay=0.005,
                                         max_delay=0.2, deadline=120),
                heartbeat_interval=0.05, fault_plan=plan,
                trace_dir=str(tmp_path / "traces"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # failover warning expected
        with plan:
            t.train(ds, shuffle=True)
    assert plan.stats()["ps_kills"] == 1  # the kill really happened
    assert t.trace_path_ and os.path.exists(t.trace_path_)
    with open(t.trace_path_) as f:
        doc = json.load(f)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    by_corr: dict = {}
    for e in xs:
        corr = (e.get("args") or {}).get("corr")
        if corr:
            by_corr.setdefault(corr, set()).add(e["name"])
    stitched = [
        corr for corr, names in by_corr.items()
        if corr.startswith("w") and ":s" in corr
        and "worker.commit" in names and "ps.fold" in names
        and "ps.wal_append" in names
    ]
    assert stitched, (
        "no exchange stitched across worker + PS fold + WAL append: "
        f"{ {k: sorted(v) for k, v in list(by_corr.items())[:8]} }"
    )
    # the failover itself is on the timeline too
    assert any(e["name"] == "ps.failover" for e in xs)
    # and the run still holds the exactly-once oracle under tracing
    s = t.ps_stats_
    assert s["num_updates"] == t.resilience_stats_["logical_commits"]


def test_trace_disabled_run_records_nothing():
    """Tracing stays fully off by default: a traced-site workload leaves
    the module recorder empty and disabled."""
    ps = ParameterServer({"w": np.zeros(4, np.float32)}, DownpourMerge(),
                         1)
    ps.pull(0)
    ps.exchange(0, {"w": np.ones(4, np.float32)}, seq=1)
    assert not trace.enabled()
    assert trace.events() == []
    assert trace.current_corr() is None
