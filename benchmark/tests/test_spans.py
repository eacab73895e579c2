"""The readers of the program's own spans: on hand-made events, on a tiny
traced run of the train driver on the CPU, and on a trace recorded on the chip."""

import os
import time

import jax
import pytest

from benchmark import loader, spans, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.json")
TRAIN_READERS = ("setup_weights_s", "setup_trace_lower_s", "setup_compile_s",
                 "setup_named_pct", "host_dispatch_ms.train", "input_wait_ms.train",
                 "idle_named_pct.train")


def reader(name):
    return loader.load_reader(os.path.join(loader.ROOT, "benchmark", "metrics",
                                           name + ".py"))


def entry(name, t0, dur, **args):
    return {"name": name, "cat": "", "corr": None, "t0_ns": t0, "dur_ns": dur,
            "tid": 1, "tname": "MainThread", "args": args or None}


def test_every_new_metric_is_listed_for_the_train_cell_alone():
    bench = loader.load_benchmark()
    mine = {m["name"]: m for m in bench["per_layer"] if m["name"] in TRAIN_READERS}
    assert sorted(mine) == sorted(TRAIN_READERS)
    assert all(m["workloads"] == ["xglm-564m.train"] for m in mine.values())
    cell = loader.load_cell("xglm-564m.train")
    assert set(TRAIN_READERS) <= {m["name"] for m in cell["per_layer"]}


# -- hand-made events ------------------------------------------------------------


def test_innermost_span_names_a_gap_and_what_no_span_covers():
    anns = [("train.epoch", 0.0, 1000.0, {}), ("train.epoch_end", 600.0, 300.0, {}),
            ("train.drain", 650.0, 100.0, {}), ("train.input", 1100.0, 50.0, {})]
    assert spans.innermost(anns, 700.0, 1200.0) == {
        "train.drain": 50.0, "train.epoch_end": 150.0, "train.epoch": 100.0,
        spans.UNNAMED: 150.0, "train.input": 50.0}
    assert spans.innermost(anns, 2000.0, 2100.0) == {spans.UNNAMED: 100.0}


def test_idle_by_span_with_a_gap_that_no_span_covers():
    ops = [(10_000.0, 20_000.0), (20_000.5, 30_000.0),        # half a ns: no gap
           (35_000.0, 40_000.0), (52_000.0, 60_000.0)]
    anns = [("train.step", 8_000.0, 3_000.0, {"step_num": 0}),
            ("train.epoch_end", 29_000.0, 4_000.0, {}),       # 3000 of the first gap
            ("train.drain", 30_500.0, 1_000.0, {}),
            ("train.log_metrics", 59_000.0, 3_000.0, {})]     # 2000 past the last op
    by = spans.idle_by_span(anns, ops)
    assert by == {"train.epoch_end": 2_000.0, "train.drain": 1_000.0,
                  "train.step": 2_000.0, "train.log_metrics": 2_000.0,
                  spans.UNNAMED: 2_000.0 + 12_000.0}
    # the profiler ran 70 us by the host's clock: 20 us at the two ends, of
    # which only 4 us lie inside the trace's own extent
    whole = spans.idle_by_span(anns, ops, slice_s=70e-6)
    assert whole[spans.UNNAMED] == 14_000.0 + 16_000.0
    assert sum(whole.values()) == 17_000.0 + 20_000.0
    run_named = 100.0 * 7_000.0 / 37_000.0
    assert 100.0 * (1 - whole[spans.UNNAMED] / sum(whole.values())) == pytest.approx(run_named)


def test_set_up_union_with_overlapping_spans(monkeypatch, capsys):
    ms = 1_000_000
    log = [
        entry("jax.compile", 0, 50 * ms, fun="jit(make)", cache="miss"),
        entry("train.build_engine", 100 * ms, 10 * ms),
        entry("train.init_weights", 120 * ms, 80 * ms),
        entry("jax.trace", 210 * ms, 20 * ms, fun="train_init_state"),
        entry("jax.lower", 230 * ms, 10 * ms, fun="jit(train_init_state)"),
        entry("jax.compile", 240 * ms, 30 * ms, fun="jit(train_init_state)", cache="hit"),
        entry("train.init_state", 200 * ms, 100 * ms),
        entry("jax.trace", 320 * ms, 400 * ms, fun="train_step"),
        entry("jax.trace", 350 * ms, 100 * ms, fun="probe"),        # inside the other
        entry("jax.lower", 720 * ms, 80 * ms, fun="jit(train_step)"),
        entry("jax.compile", 800 * ms, 100 * ms, fun="jit(train_step)", cache="hit"),
        entry("train.epoch", 310 * ms, 1690 * ms, epoch=0, steps=4),
        entry("train.epoch_end", 2005 * ms, 4 * ms, epoch=1),       # ends in the window
        entry("jax.compile", 2100 * ms, 5 * ms, fun="jit(late)", cache="miss"),
        entry("train.epoch", 2000 * ms, 500 * ms, epoch=1, steps=4),
    ]
    monkeypatch.setattr(spans, "run_log", lambda: log)
    run = {"end_to_end": {"setup_s": 2.5}}
    # 120-300 ms, less the 60 ms that JAX traced, lowered and compiled inside
    assert reader("setup_weights_s")(run) == pytest.approx(0.120)
    assert reader("setup_trace_lower_s")(run) == pytest.approx(0.020 + 0.010 + 0.400 + 0.080)
    assert reader("setup_compile_s")(run) == pytest.approx(0.050 + 0.030 + 0.100)
    # 0-50, 100-110, 120-300 and 310-2000 ms of 2500
    assert reader("setup_named_pct")(run) == pytest.approx(100 * (50 + 10 + 180 + 1690) / 2500)
    err = capsys.readouterr().err
    assert "jit(train_step) (cache hit)" in err and "jit(late)" not in err
    # an earlier train() call in the process is not this run's
    earlier = [entry("train.build_engine", -900 * ms, 10 * ms),
               entry("train.init_state", -800 * ms, 700 * ms),
               entry("train.epoch", -90 * ms, 10 * ms, epoch=1, steps=1)]
    monkeypatch.setattr(spans, "run_log", lambda: earlier + log)
    assert reader("setup_weights_s")(run) == pytest.approx(0.120)
    # nor is what began before the process did, by the set-up it clocked
    assert reader("setup_compile_s")({"end_to_end": {"setup_s": 1.95}}) == pytest.approx(0.130)


@pytest.mark.parametrize("log", [None, [], [entry("train.build_engine", 0, 5)],
                                 [entry("train.build_engine", 0, 5),
                                  entry("train.epoch", 10, 5, epoch=0, steps=4)]])
def test_a_program_without_the_spans_reads_as_nothing(monkeypatch, log):
    """The parent commit keeps no run log and annotates nothing: no reader
    raises, none returns a zero."""
    monkeypatch.setattr(spans, "run_log", lambda: log)
    run = {"end_to_end": {"setup_s": 40.0}, "trace_dir": None, "chips": 1}
    assert [reader(name)(run) for name in TRAIN_READERS] == [None] * len(TRAIN_READERS)
    assert reader("idle_named_pct.serve")(run) is None


def test_prefill_padding_from_the_engines_counters():
    read = reader("prefill_padding_pct")
    run = {"counters": {"before": {"chunk_rows": 10, "chunk_rows_padded": 16},
                        "after": {"chunk_rows": 903, "chunk_rows_padded": 1140}}}
    assert read(run) == pytest.approx(100 * (1 - 893 / 1124))
    assert read({"counters": {"before": {}, "after": {"steps": 4}}}) is None
    same = {"chunk_rows": 3, "chunk_rows_padded": 4}
    assert read({"counters": {"before": same, "after": same}}) is None


# -- a tiny traced run on the CPU -----------------------------------------------


def test_the_readers_on_a_traced_run_of_the_train_driver():
    from benchmark.drivers import train

    loaded = loader.load_cell("tiny-sincos.tiny-train", TINY)
    facts = train.drive(loaded, 2 ** 31 + 9, 1.0, True, jax.devices(),
                        t0=time.perf_counter())
    facts.update(chips=1)
    values = {name: reader(name)(facts) for name in TRAIN_READERS}
    setup_s = facts["end_to_end"]["setup_s"]
    parts = [values[n] for n in ("setup_weights_s", "setup_trace_lower_s", "setup_compile_s")]
    assert all(v > 0 for v in parts) and sum(parts) < setup_s
    assert 20.0 < values["setup_named_pct"] <= 100.0
    anns, ops = spans.of_run(facts)
    steps = [a for a in anns if a[0] == "train.step"]
    per = loaded["traffic"]["steps_per_epoch"]
    assert len(steps) == per * loaded["traffic"]["trace_epochs"]
    first = steps[0][3]["step_num"]
    assert [a[3]["step_num"] for a in steps] == list(range(first, first + len(steps)))
    assert values["host_dispatch_ms.train"] == pytest.approx(
        sum(a[2] for a in steps) / len(steps) / 1e6)
    assert 0 < values["input_wait_ms.train"]
    # no chip, no device plane: nothing to lay the spans over
    assert ops == [] and values["idle_named_pct.train"] is None


# -- a trace recorded on the chip --------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """Two epochs of two steps of a two-layer decoder (dim 256, 2 heads of 128,
    4 x 256 tokens) traced on a TPU v5e by ``benchmark/drivers/train.py`` with
    the program's spans (PR 25)."""
    path = os.path.join(HERE, "data", "small_spans.xplane.pb.gz")
    return spans.read_annotations(path), spans.busiest_ops(xplane.read_planes(path), 1)


def test_recorded_annotations_bracket_the_device(recorded):
    anns, ops = recorded
    steps = [a for a in anns if a[0] == "train.step"]
    assert [a[3]["step_num"] for a in steps] == [4, 5, 6, 7]
    names = {a[0] for a in anns}
    assert {"train.input", "train.epoch_end", "train.drain", "train.log_metrics",
            "train.epoch"} <= names
    planes = xplane.read_planes(os.path.join(HERE, "data", "small_spans.xplane.pb.gz"))
    modules = sorted((s, s + d) for n, s, d in
                     xplane.device_planes(planes)[0][xplane.MODULES_LINE]
                     if n.startswith("jit_train_step"))
    assert len(modules) == 4
    # one clock, to the profiler's own alignment of the device's lines with
    # the host's: a step this small seems to begin on the chip 0.5-0.6 ms
    # BEFORE the host's span that dispatches it, and the drain returns 2.3-2.5
    # ms after the epoch's last program ends. In the cell's own trace (1.14 s
    # steps) the step's span begins 1.6-2.4 ms before its program (PERF.md).
    for step, (m0, _) in zip(steps, modules):
        assert -1e6 < m0 - step[1] < 3e6
    drains = [a for a in anns if a[0] == "train.drain"]
    for drain, (_, m1) in zip(drains, modules[1::2]):
        assert 0 <= drain[1] + drain[2] - m1 < 3e6


def test_recorded_idle_is_named(recorded):
    anns, ops = recorded
    by = spans.idle_by_span(anns, ops)
    idle = sum(by.values())
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    gaps = sum(b - a for a, b in xplane.gaps(ops, lo, hi) if b - a >= 1000.0)
    assert idle >= gaps
    named = idle - by.get(spans.UNNAMED, 0.0)
    assert named / idle > 0.9
    assert {"train.step", "train.drain"} <= set(by)
