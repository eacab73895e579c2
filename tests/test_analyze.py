"""The analyst (ISSUE 14): interval algebra on hand-built span streams
(known critical paths, overlap fractions, lock-wait attribution, the
degraded verdict on dropped spans), the Perfetto counter-track and
gzip/rotation satellites, the analyze CLI, the BottleneckShiftRule, and
the end-to-end acceptance runs — a seeded straggler is NAMED, a
per-record-fsync durable run classifies fsync-bound while the
group-commit window does not, and a pipelined run's overlap fraction
matches the serial/pipelined oracle."""

import json
import os
import time

import pytest

import distkeras_tpu as dk
from distkeras_tpu.observability import analyze as an
from distkeras_tpu.observability import trace
from distkeras_tpu.observability.timeseries import TimeSeriesStore
from tests.test_trainers import blobs_dataset, model_spec

MS = 1_000_000  # ns per ms


def ev(name, t0_ms, dur_ms, corr=None, tid=1, cat="", args=None):
    return {"name": name, "cat": cat, "corr": corr,
            "t0_ns": int(t0_ms * MS), "dur_ns": int(dur_ms * MS),
            "tid": tid, "tname": f"t{tid}", "args": args}


def serial_window(base_ms, wid=0, n=1, compute_ms=5.0, wire_ms=1.5,
                  decode_ms=1.0, lock_ms=1.5, fold_ms=2.0,
                  append_ms=1.0, wait_ms=3.0):
    """One serial-loop window's spans: compute/fetch, compress, commit
    with a corr-stitched server-side decomposition. Returns (events,
    end_ms)."""
    xc, sc = f"w{wid}:x{n}", f"w{wid}:s{n}"
    t = base_ms
    evs = [
        ev("worker.compute", t - 0.5, compute_ms + 0.5, corr=xc,
           tid=10 + wid),
        ev("worker.fetch", t, compute_ms, corr=xc, tid=10 + wid),
        ev("worker.compress", t + compute_ms, 1.0, corr=xc, tid=10 + wid),
    ]
    c0 = t + compute_ms + 1.0
    commit = decode_ms + lock_ms + fold_ms + append_ms + wait_ms + wire_ms
    evs.append(ev("worker.commit", c0, commit, corr=sc, tid=10 + wid))
    s = c0 + wire_ms / 2
    evs.append(ev("ps.decode", s, decode_ms, corr=sc, tid=99))
    s += decode_ms + lock_ms                   # the decode→fold gap
    evs.append(ev("ps.fold", s, fold_ms, corr=sc, tid=99))
    s += fold_ms
    evs.append(ev("ps.wal_append", s, append_ms, corr=sc, tid=99))
    s += append_ms
    evs.append(ev("ps.wal_wait", s, wait_ms, corr=sc, tid=99))
    return evs, c0 + commit


# -- interval algebra ---------------------------------------------------------


def test_interval_primitives():
    assert an.merge_intervals([(5, 7), (0, 3), (2, 4)]) == [(0, 4), (5, 7)]
    assert an.union_length([(0, 10), (5, 15), (20, 21)]) == 16
    assert an.intersect_intervals([(0, 10)], [(5, 20), (25, 30)]) \
        == [(5, 10)]
    assert an._subtract([(0, 10)], [(2, 4), (6, 20)]) == [(0, 2), (4, 6)]
    assert an._subtract([(0, 5)], []) == [(0, 5)]


def test_regime_code_roundtrip():
    for i, name in enumerate(an.REGIMES):
        assert an.regime_code(name) == i


# -- window assembly + waterfall ---------------------------------------------


def test_serial_waterfall_decomposition():
    evs, _ = serial_window(100.0, wid=0, n=1)
    rep = an.analyze_events(evs, host_cores=8)
    tr = rep["training"]
    assert tr["windows"] == 1
    w = tr["workers"]["0"]
    assert w["windows"] == 1
    # known critical path: each phase lands in its own bucket
    assert w["compute_ms"] == pytest.approx(5.5, abs=0.01)
    assert w["decode_ms"] == pytest.approx(1.0, abs=0.01)
    assert w["lock_wait_ms"] == pytest.approx(1.5, abs=0.01)
    assert w["fold_ms"] == pytest.approx(2.0, abs=0.01)
    assert w["wal_ms"] == pytest.approx(4.0, abs=0.01)   # append + wait
    assert w["wire_ms"] == pytest.approx(1.5, abs=0.01)
    # nothing hidden in a serial stream
    assert tr["overlap"]["fraction"] == 0.0
    assert rep["degraded"] is False and rep["dropped_spans"] == 0


def test_lock_wait_attributed_to_the_worker_that_waited():
    evs = []
    e, _ = serial_window(0.0, wid=0, n=1, lock_ms=0.1)
    evs += e
    e, _ = serial_window(0.0, wid=1, n=1, lock_ms=40.0)  # queued behind 0
    evs += e
    tr = an.analyze_events(evs, host_cores=8)["training"]
    assert tr["workers"]["1"]["lock_wait_ms"] == pytest.approx(40.0,
                                                               rel=0.01)
    assert tr["workers"]["0"]["lock_wait_ms"] == pytest.approx(0.1,
                                                               abs=0.05)


def test_fold_lock_regime_on_hand_built_stream():
    evs = []
    t = 0.0
    for n in range(1, 5):
        e, t = serial_window(t + 0.5, wid=0, n=n, compute_ms=1.0,
                             lock_ms=30.0, fold_ms=10.0, wire_ms=0.5,
                             wait_ms=0.2, append_ms=0.2, decode_ms=0.3)
        evs += e
    rep = an.analyze_events(evs, host_cores=8)
    assert rep["verdict"]["regime"] == "fold-lock-bound"


def test_fsync_regime_on_hand_built_stream():
    evs = []
    t = 0.0
    for n in range(1, 5):
        e, t = serial_window(t + 0.5, wid=0, n=n, compute_ms=1.0,
                             wait_ms=25.0, append_ms=5.0, lock_ms=0.2,
                             fold_ms=0.5, wire_ms=0.5, decode_ms=0.2)
        evs += e
    rep = an.analyze_events(evs, host_cores=8)
    assert rep["verdict"]["regime"] == "fsync-bound"
    assert any("ps_wal_group_window" in r
               for r in rep["verdict"]["recommendations"])


def test_overlap_fully_hidden_pipelined_stream():
    """Pipelined shape: window N's commit runs inside window N+1's
    dispatch→fetch-return span and the fetch still waits afterwards →
    the exchange is hidden (overlap ~1.0) and charged nothing."""
    evs = []
    # window 1: fetch [10,18]; its commit [21,25] hides under window
    # 2's compute [20,40] (dispatch at 20); window 2's fetch [25,40]
    # still waits 15ms → device-critical
    evs.append(ev("worker.compute", 2, 16, corr="w0:x1", tid=10))
    evs.append(ev("worker.fetch", 10, 8, corr="w0:x1", tid=10))
    evs.append(ev("worker.compress", 18, 1, corr="w0:x1", tid=10))
    evs.append(ev("worker.compute", 20, 20, corr="w0:x2", tid=10))
    evs.append(ev("worker.commit", 21, 4, corr="w0:s1", tid=10))
    evs.append(ev("worker.fetch", 25, 15, corr="w0:x2", tid=10))
    evs.append(ev("worker.compress", 40, 1, corr="w0:x2", tid=10))
    evs.append(ev("worker.commit", 41.5, 4, corr="w0:s2", tid=10))
    rep = an.analyze_events(evs, host_cores=8)
    tr = rep["training"]
    # commit 1 hidden (4ms of 8ms total exchange)
    assert tr["overlap"]["fraction"] == pytest.approx(0.5, abs=0.01)
    # the hidden, device-critical exchange is charged nothing: worker
    # wire total is only window 2's EXPOSED commit
    assert tr["workers"]["0"]["wire_ms"] == pytest.approx(4.0, abs=0.1)


def test_hidden_but_exchange_critical_window_is_charged():
    """Hidden commit whose following fetch returned immediately: the
    exchange was the constraint — its decomposition IS charged and the
    enveloping window only counts its fetch residue as compute."""
    evs = [
        ev("worker.compute", 2, 6, corr="w0:x1", tid=10),
        ev("worker.fetch", 4, 4, corr="w0:x1", tid=10),
        ev("worker.compress", 8, 0.5, corr="w0:x1", tid=10),
        # window 2 dispatched at 9; commit of window 1 runs [9.5, 29.5]
        ev("worker.compute", 9, 21, corr="w0:x2", tid=10),
        ev("worker.commit", 9.5, 20, corr="w0:s1", tid=10),
        # fetch residue ~0: the device finished long before the wire did
        ev("worker.fetch", 29.96, 0.04, corr="w0:x2", tid=10),
        ev("worker.compress", 30.0, 0.5, corr="w0:x2", tid=10),
        ev("worker.commit", 30.5, 20, corr="w0:s2", tid=10),
    ]
    tr = an.analyze_events(evs, host_cores=8)["training"]
    w = tr["workers"]["0"]
    # both commits charged as wire (no server spans): 40ms total
    assert w["wire_ms"] == pytest.approx(40.0, rel=0.05)
    # window 2's compute evidence is its ~0 fetch residue, not the 21ms
    # span that merely enveloped window 1's exchange
    assert w["compute_ms"] < 15.0


def test_dropped_spans_degrade_never_invent(tmp_path):
    evs, _ = serial_window(0.0, wid=0, n=1)
    # a commit whose fetch anchor was dropped: skipped, not guessed
    orphan = ev("worker.commit", 500.0, 4.0, corr="w3:s9", tid=13)
    rep = an.analyze_events(evs + [orphan], dropped=7, host_cores=8)
    assert rep["degraded"] is True
    assert rep["verdict"]["degraded"] is True
    assert rep["dropped_spans"] == 7
    assert rep["skipped_windows"] >= 1
    assert "3" not in rep["training"]["workers"]
    assert any("dropped" in r.lower()
               for r in rep["verdict"]["recommendations"])
    # rc contract: the CLI exits 2 on a degraded verdict
    trace.enable(ring_size=4096)
    try:
        for e in evs:
            trace.record(e["name"], e["t0_ns"], e["t0_ns"] + e["dur_ns"],
                         corr=e["corr"])
        path = trace.save(str(tmp_path / "t.json"))
    finally:
        trace.disable()
    from distkeras_tpu.observability.__main__ import main
    assert main(["analyze", path]) == 0


def test_host_core_bound_classification():
    totals = {"compute": 900.0, "compress": 0.0, "wire": 10.0,
              "decode": 0.0, "lock_wait": 0.0, "fold": 5.0, "wal": 5.0}
    regime, _ = an.classify(totals, host_cores=1, n_workers=4,
                            wall_ms=500.0, busy_ms=950.0)
    assert regime == "host-core-bound"
    # ample cores: plain compute-bound
    regime2, _ = an.classify(totals, host_cores=64, n_workers=4,
                             wall_ms=500.0, busy_ms=950.0)
    assert regime2 == "compute-bound"
    assert an.classify({}, host_cores=1)[0] == "idle"


def test_serving_report_and_queue_regime():
    evs = [
        ev("serve.request", 0, 100, corr="r1", tid=5,
           args={"state": "done"}),
        ev("serve.queued", 0, 70, corr="r1", tid=5),
        ev("serve.prefill", 70, 10, corr="r1", tid=5),
        ev("serve.request", 5, 95, corr="r2", tid=5,
           args={"state": "done"}),
        ev("serve.queued", 5, 60, corr="r2", tid=5),
        ev("serve.decode_step", 80, 5, tid=5, args={"rows": 4}),
        ev("serve.decode_step", 85, 15, tid=5, args={"rows": 8}),
    ]
    rep = an.analyze_events(evs, host_cores=8)
    sv = rep["serving"]
    assert sv["requests"] == 2 and sv["dominant"] == "queue"
    # duration-weighted rows: (4*5 + 8*15) / 20
    assert sv["mean_rows_in_flight"] == pytest.approx(7.0)
    assert rep["verdict"]["regime"] == "queue-bound"
    assert any("admission" in r
               for r in rep["verdict"]["recommendations"])


def test_convoyed_lock_waits_do_not_eclipse_wire():
    """Review regression: four workers convoyed on the center lock for
    the SAME 100 ms stretch, each with ~90 ms of genuine wire — the
    classifier must union the shared lock stretch (100 ms, once), not
    subtract the 400 ms per-worker sum from the wire bucket."""
    evs = []
    for wid in range(4):
        sc = f"w{wid}:s1"
        evs += [
            ev("worker.compute", 0.5 + wid, 2.0, corr=f"w{wid}:x1",
               tid=10 + wid),
            ev("worker.fetch", 1 + wid, 1.5, corr=f"w{wid}:x1",
               tid=10 + wid),
            ev("worker.compress", 2.5 + wid, 0.5, corr=f"w{wid}:x1",
               tid=10 + wid),
            # commit spans [5, 200]: decode 2ms, a ~50ms lock wait on
            # the SHARED wall stretch [·, 60], fold 1ms, the rest wire
            ev("worker.commit", 5 + wid, 195, corr=sc, tid=10 + wid),
            ev("ps.decode", 6 + wid, 2, corr=sc, tid=99),
            ev("ps.fold", 60, 1, corr=sc, tid=99),
        ]
    rep = an.analyze_events(evs, host_cores=8)
    tr = rep["training"]
    # per-worker sums still say who waited ~50 ms each (~200 summed)
    assert tr["totals_ms"]["lock_wait"] == pytest.approx(200, rel=0.1)
    # but the classifier sees ONE ~50 ms lock stretch vs ~140 ms wire
    # (the old sum-subtraction zeroed wire entirely: 190 - 200 < 0)
    assert tr["union_ms"]["lock_wait"] == pytest.approx(52, rel=0.1)
    assert rep["verdict"]["regime"] == "wire-bound", \
        rep["verdict"]["fractions"]


def test_two_worker_straggler_is_still_named():
    """Review regression: with exactly two workers the (upper) median
    was the straggler's own cadence/stall, so it could never exceed
    2× itself — the lower median keeps the smallest pool honest."""
    evs = []
    t0, t1 = 0.0, 0.0
    for n in range(1, 5):
        e, t0 = serial_window(t0 + 1.0, wid=0, n=n)
        evs += e
        e, t1 = serial_window(t1 + 200.0, wid=1, n=n)  # 200ms stalls
        evs += e
    tr = an.analyze_events(evs, host_cores=8)["training"]
    assert tr["dominant_wait_worker"] == 1
    assert tr["stragglers"] == [1]


def test_regime_tracker_end_cursor_keeps_long_spans():
    """Review regression: spans land in the ring at CLOSE, so a
    start-time cursor would permanently drop a long compute span whose
    dispatch predates short commit spans an earlier tick consumed —
    classifying a 2 s-compute / 30 ms-wire pipelined run as wire-bound
    forever. The end-time cursor keeps it compute-bound."""
    store = TimeSeriesStore()
    tracker = an.RegimeTracker()
    # tick 1 sees only the short spans that closed mid-window (the
    # compute span is still open): commit + fold of the previous window
    tick1 = [
        ev("worker.commit", 100, 30, corr="w0:s1", tid=10),
        ev("ps.fold", 115, 2, corr="w0:s1", tid=10),
    ]
    tracker.observe(tick1, store, 1.0)
    # tick 2 delivers the 2000 ms compute span that closed AFTER tick 1
    # — its t0 (0) predates everything already observed
    tick2 = tick1 + [
        ev("worker.compute", 0, 2000, corr="w0:x2", tid=10),
        ev("worker.fetch", 1900, 100, corr="w0:x2", tid=10),
    ]
    tracker.observe(
        [e for e in tick2 if e["t0_ns"] + e["dur_ns"] > tracker._cursor],
        store, 2.0)
    codes = [v for _, v in store.get("analyze.regime_code").points()]
    assert codes[-1] == an.regime_code("compute-bound"), codes


def test_elastic_pull_before_fetch_keeps_stall_and_is_not_double_charged():
    """Review regression: the elastic (EASGD) loop pulls BEFORE its
    window's fetch, so the pull span attaches to the previous window —
    it must neither extend that window's end (erasing the straggler's
    boundary stall) nor be charged on top of the compute span that
    envelops it."""
    def window(base, n, wid=0):
        xc = f"w{wid}:x{n}"
        return [
            # dispatch at base; pull rides INSIDE the compute span
            ev("worker.compute", base, 14, corr=xc, tid=10),
            ev("worker.pull", base + 0.5, 3, corr=xc, tid=10),
            ev("worker.fetch", base + 4, 10, corr=xc, tid=10),
            ev("worker.compress", base + 14, 1, corr=xc, tid=10),
            ev("worker.commit", base + 15, 4, corr=xc, tid=10),
        ]

    evs = []
    base = 0.0
    for n in range(1, 4):
        evs += window(base, n)
        base += 19 + 200.0          # 200 ms boundary sleep per window
    tr = an.analyze_events(evs, host_cores=8)["training"]
    w = tr["workers"]["0"]
    # the boundary sleeps survive as stall (2 gaps × 200 ms)...
    assert w["stall_ms"] == pytest.approx(400.0, rel=0.05)
    # ...and the compute-enveloped pulls are charged nothing (the
    # dispatch→fetch-return span already covers that wall)
    assert w["pull_ms"] == 0.0
    # the overlap metric agrees with the charging rule: hidden pulls
    # count as hidden exchange even though the commits stay exposed.
    # Window N's pull precedes its fetch anchor so it attaches to
    # window N-1 (the first one, before any anchor, is dropped): 2
    # hidden pulls × 3 ms over 3 commits × 4 ms + 2 pulls × 3 ms = 1/3.
    assert tr["overlap"]["fraction"] == pytest.approx(1 / 3, abs=0.02)


def test_regime_tracker_accumulates_subthreshold_evidence():
    """Review regression: sub-threshold fresh spans must stay
    unconsumed (the cursor holds) so sparse runs accumulate evidence
    across ticks instead of shedding it and never sampling."""
    store = TimeSeriesStore()
    tracker = an.RegimeTracker(min_span_ms=1.0)
    # 0.4 ms of compute per tick: below threshold alone, ample in three
    drip = []
    for i in range(3):
        drip.append(ev("worker.fetch", i * 10, 0.4, corr="w0:x1",
                       tid=10))
        tracker.observe([e for e in drip
                         if e["t0_ns"] + e["dur_ns"] > tracker._cursor],
                        store, float(i))
    s = store.get("analyze.regime_code")
    assert s is not None and len(s) == 1     # sampled once, on tick 3
    assert [v for _, v in s.points()] == [an.regime_code("compute-bound")]


def test_regime_code_series_never_averages_codes():
    """Review regression: the code series is categorical — ring
    downsampling must keep true observed codes (counter semantics),
    never average 0 and 2 into a phantom wire-bound 1."""
    store = TimeSeriesStore(capacity=16)
    tracker = an.RegimeTracker(min_span_ms=0.1)
    for i in range(40):   # force several downsample passes
        name = ("worker.fetch" if i % 2 == 0 else "ps.wal_wait")
        evs = [ev(name, i * 100, 5, corr="w0:x1", tid=10),
               ev("wal.fsync" if i % 2 else "worker.fetch",
                  i * 100 + 6, 5, corr=None if i % 2 else "w0:x1",
                  tid=20)]
        tracker.observe(evs, store, float(i))
    s = store.get("analyze.regime_code")
    assert s is not None and s.kind == "counter"
    codes = {v for _, v in s.points()}
    valid = {float(an.regime_code(r)) for r in an.REGIMES}
    assert codes <= valid, codes


def test_union_accounting_counts_shared_waits_once():
    """Four workers waiting on the SAME group fsync cost the run one
    fsync of wall, not four — the classifier's union accounting."""
    evs = []
    for wid in range(4):
        e, _ = serial_window(0.0, wid=wid, n=1, compute_ms=30.0,
                             wait_ms=0.0, append_ms=0.0, lock_ms=0.0,
                             fold_ms=0.1, wire_ms=0.4, decode_ms=0.1)
        evs += e
        # every worker waits the same wall interval [40, 60] — all four
        # convoyed behind ONE flusher fsync covering the same stretch
        evs.append(ev("ps.wal_wait", 40.0, 20.0, corr=f"w{wid}:s1",
                      tid=99 + wid))
    evs.append(ev("wal.fsync", 40.0, 20.0, tid=200))
    rep = an.analyze_events(evs, host_cores=8)
    # per-worker sums see 20ms of durability wait each...
    assert rep["training"]["totals_ms"]["wal"] == pytest.approx(
        80.0, rel=0.05)
    # ...but the union (the classifier's input) counts the log device's
    # ONE fsync once
    assert rep["training"]["union_ms"]["wal"] == pytest.approx(
        20.0, rel=0.05)
    assert rep["verdict"]["regime"] == "compute-bound"


# -- satellites: counter tracks, gzip, rotation -------------------------------


def test_counter_tracks_save_load_roundtrip(tmp_path):
    trace.enable(ring_size=4096)
    try:
        with trace.span("ps.fold"):
            time.sleep(0.001)
        trace.counter("ps.tau_p95", 3.5)
        trace.counter("ps.tau_p95", 7.25)
        trace.counter("serve.rows_in_flight", 4)
        path = trace.save(str(tmp_path / "trace.json"))
    finally:
        trace.disable()
    doc = json.loads(open(path).read())
    counters = [r for r in doc["traceEvents"] if r.get("ph") == "C"]
    assert len(counters) == 3
    assert counters[0]["args"] == {"value": 3.5}
    assert doc["otherData"]["host_cores"] == (os.cpu_count() or 1)
    events, meta = an.load_trace(path)
    cs = [e for e in events if e["cat"] == "__counter__"]
    assert [e["args"] for e in cs] == [3.5, 7.25, 4.0]
    assert meta["host_cores"] == (os.cpu_count() or 1)
    # counters feed the report's counter summary
    rep = an.analyze_events(events)
    assert rep["counters"]["ps.tau_p95"] == {"last": 7.25, "max": 7.25}


def test_counters_are_never_sampled_out():
    trace.enable(ring_size=4096, sample=0.01)
    try:
        for i in range(20):
            trace.counter("c", i)
        cs = [e for e in trace.events() if e["cat"] == "__counter__"]
        assert len(cs) == 20
    finally:
        trace.disable()


def test_save_gzip_and_transparent_read(tmp_path):
    trace.enable(ring_size=1024)
    try:
        with trace.span("worker.fetch", corr="w0:x1"):
            time.sleep(0.001)
        gz = trace.save(str(tmp_path / "trace.json.gz"))
    finally:
        trace.disable()
    with open(gz, "rb") as f:
        assert f.read(2) == b"\x1f\x8b"  # actually gzipped
    events, meta = an.load_trace(gz)
    assert any(e["name"] == "worker.fetch" for e in events)
    # suffix-free gz (a rotated rename) still reads — magic sniffing
    renamed = str(tmp_path / "trace.rotated")
    os.rename(gz, renamed)
    events2, _ = an.load_trace(renamed)
    assert len(events2) == len(events)


def test_save_rotation_caps_growth(tmp_path):
    path = str(tmp_path / "trace.json")
    for k in range(3):
        trace.enable(ring_size=1024)
        try:
            with trace.span("ps.fold"):
                pass
            trace.save(path, max_bytes=1, keep=2)  # always rotate
        finally:
            trace.disable()
    assert os.path.exists(path)
    assert os.path.exists(path + ".1")
    assert os.path.exists(path + ".2")
    assert not os.path.exists(path + ".3")  # capped at keep
    an.load_trace(path + ".2")              # rotated files stay readable


def test_store_dump_gz_roundtrip(tmp_path):
    st = TimeSeriesStore()
    st.sample("ps.commits", 1.0, 5, "counter")
    path = st.dump(str(tmp_path / "series.json.gz"))
    with open(path, "rb") as f:
        assert f.read(2) == b"\x1f\x8b"
    st2 = TimeSeriesStore.load(path)
    assert st2.last("ps.commits") == 5.0


def test_cli_analyze_json_and_series(tmp_path, capsys):
    from distkeras_tpu.observability.__main__ import main

    trace.enable(ring_size=4096)
    try:
        evs, _ = serial_window(0.0, wid=0, n=1)
        for e in evs:
            trace.record(e["name"], e["t0_ns"], e["t0_ns"] + e["dur_ns"],
                         corr=e["corr"])
        path = trace.save(str(tmp_path / "t.json.gz"))
    finally:
        trace.disable()
    st = TimeSeriesStore()
    st.sample("ps.tau_p95", 1.0, 21.0)
    series = st.dump(str(tmp_path / "s.json.gz"))
    rc = main(["analyze", path, "--series", series, "--json"])
    out = capsys.readouterr().out
    rep = json.loads(out)
    assert rc == 0
    assert rep["training"]["windows"] == 1
    assert rep["counters"]["ps.tau_p95"]["last"] == 21.0
    # human-readable mode prints the verdict line
    rc2 = main(["analyze", path])
    assert rc2 == 0
    assert "regime:" in capsys.readouterr().out
    with pytest.raises(SystemExit):
        main(["analyze", str(tmp_path / "missing.json")])


# -- the watchtower bridge ----------------------------------------------------


def test_regime_tracker_and_bottleneck_shift_rule():
    from distkeras_tpu.observability.watch import (
        BottleneckShiftRule,
        Watchdog,
    )

    store = TimeSeriesStore()
    tracker = an.RegimeTracker()
    # four compute-bound slices, then the run turns fsync-bound
    t_ms = 0.0
    for tick in range(4):
        evs, _ = serial_window(t_ms, wid=0, n=tick + 1, compute_ms=50.0,
                               wait_ms=0.5, append_ms=0.2, wire_ms=0.5,
                               lock_ms=0.1, fold_ms=0.5, decode_ms=0.2)
        t_ms += 200.0
        tracker.observe(evs, store, float(tick))
    for tick in range(4, 6):
        evs, _ = serial_window(t_ms, wid=0, n=tick + 1, compute_ms=1.0,
                               wait_ms=80.0, append_ms=10.0,
                               wire_ms=0.5, lock_ms=0.1, fold_ms=0.5,
                               decode_ms=0.2)
        t_ms += 200.0
        tracker.observe(evs, store, float(tick))
    codes = [v for _, v in store.get("analyze.regime_code").points()]
    assert codes[0] == an.regime_code("compute-bound")
    assert codes[-1] == an.regime_code("fsync-bound")

    rule = BottleneckShiftRule(persistence=1)
    dog = Watchdog(store, rules=[rule])
    fired = dog.evaluate(now=10.0)
    assert [a["kind"] for a in fired] == ["bottleneck_shift"]
    assert fired[0]["detail"]["from"] == "compute-bound"
    assert fired[0]["detail"]["to"] == "fsync-bound"


def test_shift_rule_quiet_on_stable_regime():
    from distkeras_tpu.observability.watch import (
        BottleneckShiftRule,
        Watchdog,
    )

    store = TimeSeriesStore()
    for i in range(6):
        store.sample("analyze.regime_code", float(i),
                     an.regime_code("compute-bound"))
    dog = Watchdog(store, rules=[BottleneckShiftRule(persistence=1)])
    assert dog.evaluate(now=7.0) == []
    # too few points: no judgment either way
    st2 = TimeSeriesStore()
    st2.sample("analyze.regime_code", 0.0, 0.0)
    dog2 = Watchdog(st2, rules=[BottleneckShiftRule(persistence=1)])
    assert dog2.evaluate(now=1.0) == []


# -- end-to-end acceptance ----------------------------------------------------


@pytest.mark.filterwarnings("ignore")
def test_analyze_knob_end_to_end():
    """analyze=True implies tracing, runs post-hoc, lands the report in
    analysis_, and releases the recorder (a no-trace run pays nothing —
    the off-path allocation-freeness itself is pinned in
    test_observability)."""
    ds = blobs_dataset(n=256)
    t = dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                worker_optimizer="sgd", learning_rate=0.05,
                num_workers=2, batch_size=16, communication_window=2,
                num_epoch=2, backend="ps", ps_transport="inprocess",
                analyze=True)
    assert t.trace is True          # implied
    t.train(ds, shuffle=True)
    rep = t.analysis_
    assert rep is not None and rep["verdict"]["regime"] in an.REGIMES
    assert rep["training"]["windows"] == 16       # 2 workers × 8
    assert rep["degraded"] is False
    assert not trace.enabled()      # recorder released
    # a run WITHOUT the knob leaves analysis_ empty
    t2 = dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                 worker_optimizer="sgd", learning_rate=0.05,
                 num_workers=2, batch_size=16, communication_window=2,
                 num_epoch=1, backend="ps", ps_transport="inprocess")
    t2.train(ds, shuffle=True)
    assert t2.analysis_ is None
    assert not trace.enabled()


def test_analyze_knob_validation():
    with pytest.raises(ValueError, match="analyze"):
        dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                worker_optimizer="sgd", num_workers=2, batch_size=16,
                num_epoch=1, backend="collective", analyze=True)


@pytest.mark.filterwarnings("ignore")
def test_straggler_is_named_end_to_end():
    """Acceptance: a FaultPlan.straggle={wid: s} run names that worker
    as the dominant wait source — its boundary sleeps land in the stall
    attribution, not in invented phase time."""
    from distkeras_tpu.resilience.faults import FaultPlan

    ds = blobs_dataset(n=512)
    plan = FaultPlan(straggle={1: 0.2})
    t = dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                worker_optimizer="sgd", learning_rate=0.05,
                num_workers=4, batch_size=16, communication_window=2,
                num_epoch=2, backend="ps", ps_transport="inprocess",
                fault_plan=plan, analyze=True)
    with plan:
        t.train(ds, shuffle=True)
    assert plan.stats()["straggles"] > 0
    tr = t.analysis_["training"]
    assert tr["dominant_wait_worker"] == 1
    assert 1 in tr["stragglers"]
    # the sleeps are attributed as stall, dwarfing the healthy workers'
    assert tr["workers"]["1"]["stall_ms"] > \
        10 * max(tr["workers"]["0"]["stall_ms"],
                 tr["workers"]["2"]["stall_ms"], 1.0)
    # and the top recommendation names the straggler
    assert any("worker 1" in r
               for r in t.analysis_["verdict"]["recommendations"])


def _durable_exchange_run(tmp_path, window, per_record_fsync,
                          workers=8, rounds=6, compute_s=0.05):
    """Drive the REAL ParameterServer + CommitLog + flight recorder with
    the worker loop's span protocol — real folds, real WAL
    appends/waits/fsyncs — and analyze the recording. Compute is a
    sleep-simulated device (each worker owns its accelerator, so
    windows run in parallel and commits arrive together — bench's
    exchange leg simulates the device the same way, and it is what
    makes group-commit batching realistic instead of serialized by the
    suite host's single core). The trainer variant of this scenario
    drowns in per-device XLA compile time under the 8-fake-device
    conftest; this harness is the same PS/WAL/trace/analyze pipeline
    with the compile confound removed."""
    import threading

    import numpy as np

    from distkeras_tpu.parallel.merge_rules import DynSGDMerge
    from distkeras_tpu.parameter_servers import ParameterServer

    ps = ParameterServer(
        {"w": np.zeros(8192, np.float32)}, DynSGDMerge(), workers,
        wal_dir=str(tmp_path / f"wal-{window}"),
        wal_group_window=window,
    )
    if per_record_fsync:
        ps._wal.fsync_every = 1   # the PR 5 per-record durability cadence
    delta = {"w": np.full(8192, 0.01, np.float32)}
    # synchronized window boundaries: commits arrive as a burst, the
    # data-parallel shape that is the per-record log's worst case and
    # group commit's best — exactly the contrast the knob exists for
    gate = threading.Barrier(workers)
    trace.enable(ring_size=65536)
    try:
        def work(wid):
            ps.pull(wid)
            for r in range(1, rounds + 1):
                gate.wait()
                trace.set_corr(f"w{wid}:x{r}")
                t0 = time.perf_counter()
                time.sleep(compute_s)     # the simulated device window
                t1 = time.perf_counter()
                trace.record("worker.compute", int(t0 * 1e9),
                             int(t1 * 1e9))
                trace.record("worker.fetch", int(t0 * 1e9),
                             int(t1 * 1e9))
                t2 = time.perf_counter()
                ps.commit(wid, delta, seq=r)
                trace.record("worker.commit", int(t2 * 1e9),
                             int(time.perf_counter() * 1e9))

        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        events = trace.events()
        dropped = trace.live_dropped()
    finally:
        trace.disable()
        ps._wal.close()
    return an.analyze_events(events, dropped=dropped)


@pytest.mark.filterwarnings("ignore")
def test_fsync_bound_w1_vs_w8_end_to_end(tmp_path, monkeypatch):
    """Acceptance: on the same (deterministically slowed) log device, a
    per-record-fsync durable run classifies fsync-bound while the w8
    group-commit run does not — one fsync per batch amortizes the tail
    below the compute bill. The fsync sleep stands in for a slow disk
    (tmpfs CI disks would otherwise make fsync free and the leg
    meaningless)."""
    from distkeras_tpu.resilience import wal as walmod

    real_fsync = walmod.os.fsync

    def slow_fsync(fd):
        time.sleep(0.010)
        return real_fsync(fd)

    monkeypatch.setattr(walmod.os, "fsync", slow_fsync)
    rep1 = _durable_exchange_run(tmp_path, window=1,
                                 per_record_fsync=True)
    rep8 = _durable_exchange_run(tmp_path, window=8,
                                 per_record_fsync=False)
    assert rep1["verdict"]["regime"] == "fsync-bound", \
        rep1["training"]["union_ms"]
    assert rep8["verdict"]["regime"] != "fsync-bound", \
        rep8["training"]["union_ms"]
    # the structural claim behind the flip: grouping amortized the
    # durable wall (union accounting — shared waits count once)
    assert rep1["training"]["union_ms"]["wal"] > \
        1.5 * rep8["training"]["union_ms"]["wal"]
    assert any("ps_wal_group_window" in r
               for r in rep1["verdict"]["recommendations"])


@pytest.mark.filterwarnings("ignore")
def test_pipelined_overlap_end_to_end():
    """Acceptance: ps_pipeline_depth=1 reports a high hidden-exchange
    fraction, depth 0 reports ~none — the per-run measurement of PR
    10's overlap claim (bench's RTT oracle pins the wire-count half)."""
    ds = blobs_dataset(n=256)
    kw = dict(loss="sparse_softmax_cross_entropy",
              worker_optimizer="sgd", learning_rate=0.05,
              num_workers=2, batch_size=16, communication_window=2,
              num_epoch=2, backend="ps", ps_transport="socket",
              analyze=True)
    t1 = dk.DOWNPOUR(model_spec(), ps_pipeline_depth=1, **kw)
    t1.train(ds, shuffle=True)
    t0 = dk.DOWNPOUR(model_spec(), **kw)
    t0.train(ds, shuffle=True)
    f1 = t1.analysis_["training"]["overlap"]["fraction"]
    f0 = t0.analysis_["training"]["overlap"]["fraction"]
    # nominal ~0.9 alone; the tail-flush window (never hidden — there
    # is no next window to hide under) plus full-suite GIL scramble has
    # been observed to pull it to ~0.54, so the bound sits below that
    # with the serial run's ~0.0 still an order of magnitude away
    assert f1 > 0.4, t1.analysis_["training"]["overlap"]
    assert f0 < 0.1, t0.analysis_["training"]["overlap"]


@pytest.mark.filterwarnings("ignore")
def test_traced_watched_run_feeds_regime_series(tmp_path):
    """watch=True + trace=True wires the analyst's online shadow: the
    dump carries analyze.regime_code samples and the default rule set
    includes the shift rule without firing on a stable run."""
    ds = blobs_dataset(n=512)
    t = dk.ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
                worker_optimizer="sgd", learning_rate=0.05,
                num_workers=2, batch_size=16, communication_window=2,
                num_epoch=2, backend="ps", ps_transport="inprocess",
                trace=True, watch=True, scrape_interval=0.05,
                watch_dir=str(tmp_path / "watch"))
    t.train(ds, shuffle=True)
    doc = json.loads(open(t.watch_path_).read())
    assert "analyze.regime_code" in doc["series"], sorted(doc["series"])
    assert not any(a["kind"] == "bottleneck_shift"
                   for a in t.watch_alerts_["log"])
