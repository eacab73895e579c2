"""The trace reduction: on hand-made events, and on a trace recorded on the chip."""

import glob
import os

import pytest

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def test_union_self_time_and_gaps_on_hand_made_events():
    assert xplane.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    # a while holds two fusions; one op stands alone
    events = [("while", 0.0, 100.0), ("fusion.1", 10.0, 30.0), ("fusion.2", 50.0, 40.0),
              ("copy", 150.0, 50.0)]
    own = {n: s for n, _, _, s in xplane.self_times(events)}
    assert own == {"while": 30.0, "fusion.1": 30.0, "fusion.2": 40.0, "copy": 50.0}
    assert xplane.gaps([(0, 100), (150, 200)], 0, 220) == [(100, 150), (200, 220)]


def test_one_device_busy_idle_and_exposed_collective():
    lines = {xplane.OPS_LINE: [
        ("fusion.1", 0.0, 100.0),
        ("all-gather.3", 50.0, 100.0),      # 50 under the fusion, 50 exposed
        ("fusion.2", 200.0, 100.0),         # after a gap of 50
        ("all-reduce.1", 300.0, 20.0),      # wholly exposed
    ], xplane.MODULES_LINE: [("jit_step(1)", 0.0, 320.0)]}
    r = xplane.reduce_device(lines)
    assert r["window_ns"] == 320 and r["busy_ns"] == 270
    assert r["collective_ns"] == 120 and r["collective_exposed_ns"] == 70
    assert r["idle_gaps"] == [("before fusion.2", 50.0)]
    out = xplane.reduce({"/device:TPU:0": lines, "/host:CPU": {"python": []}}, 1)
    assert out["busy_s"] == pytest.approx(270e-9) and out["window_s"] == pytest.approx(320e-9)
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(100e-9)]
    assert xplane.op_seconds(out, r"^fusion") == (pytest.approx(200e-9), 2)
    assert xplane.idle_pct(out) == pytest.approx(100 * 50 / 320)
    # the profiler ran 5000 ns by the host's clock: what lies before the first
    # operation and after the last is idle too, and is named among the gaps
    whole = xplane.reduce({"/device:TPU:0": lines}, 1, slice_s=5000e-9)
    assert whole["window_s"] == 5000e-9 and whole["busy_s"] == pytest.approx(270e-9)
    assert xplane.idle_pct(whole) == pytest.approx(100 * 4730 / 5000)
    assert whole["breakdown"]["idle_gaps"][0][1] == pytest.approx(4680e-9)
    with pytest.raises(ValueError, match="TPU planes"):
        xplane.reduce({"/device:TPU:0": lines}, 4)


@pytest.fixture(scope="module")
def recorded():
    """Two steps of a two-layer decoder (dim 256, 2 heads of 128, 4 x 256
    tokens) traced on a TPU v5e by ``benchmark/drivers/train.py`` (PR 24)."""
    planes = xplane.read_planes(os.path.join(HERE, "data", "small_train.xplane.pb.gz"))
    return planes, xplane.reduce(planes, 1)


def test_recorded_trace_busy_idle_and_programs(recorded):
    planes, trace = recorded
    assert list(xplane.device_planes(planes)) == [0]
    assert trace["window_s"] == pytest.approx(1.661203e-3, rel=1e-6)
    assert trace["busy_s"] == pytest.approx(0.565650e-3, rel=1e-6)
    assert 0 < trace["busy_s"] < trace["window_s"]
    chip = trace["busiest"]
    assert [n.split("(")[0] for n, _ in chip["modules"]] == ["jit_step", "jit_step"]
    # a model this small waits for the host between its steps: the longest gap
    assert max(ns for _, ns in chip["idle_gaps"]) / 1e9 > 0.5 * (
        trace["window_s"] - trace["busy_s"])
    assert chip["collective_ns"] == 0 and chip["collective_exposed_ns"] == 0
    own = sum(ns for ns, _ in trace["ops"].values()) / 1e9
    assert own == pytest.approx(trace["busy_s"], rel=0.02)   # nothing counted twice


def test_recorded_trace_kernel_time(recorded):
    _, trace = recorded
    calls = xplane.kernel_calls(trace)
    kinds = sorted((len(outs), outs[-1].split("[")[0], n) for outs, _, _, n in calls)
    # two layers, two steps: forward twice a layer under remat, dQ and dK/dV once
    assert kinds == [(1, "bf16", 2)] * 2 + [(2, "bf16", 2)] * 2 + [(2, "f32", 2)] * 4
    assert all(ops[0] == "bf16[8,256,128]" for _, ops, _, _ in calls)
    seconds = sum(ns for _, _, ns, _ in calls) / 1e9
    assert seconds == pytest.approx(159.233e-6, rel=1e-4)
    assert trace["breakdown"]["device_ops"][0] == [
        "blocks_*._attn_full (kernel)", pytest.approx(seconds)]
    assert len(trace["breakdown"]["device_ops"]) == 10
    assert xplane.op_seconds(trace, xplane.KERNEL)[1] == 16


def test_the_trace_readers_on_the_recorded_trace(recorded):
    from benchmark import loader

    _, trace = recorded
    run = {"trace": trace, "chips": 1, "peaks": loader.load_peaks("TPU v5 lite"),
           "model": {"dim": 256, "heads": 2, "kv_heads": None, "attn_window": None},
           "traffic": {"batch_size": 4, "seq_len": 256}}
    readers = {name: loader.load_reader(os.path.join(
        loader.ROOT, "benchmark", "metrics", name + ".py"))
        for name in ("flash_roofline", "device_idle_pct.train")}
    assert readers["device_idle_pct.train"](run) == pytest.approx(65.95, abs=0.01)
    # 8 forward, 4 dQ, 4 dK/dV calls of 8 x 256 x 128 in 159 us; at this
    # length memory bounds them: 2.1, 3.1 and 3.7 MB a call at 819 GB/s
    least = (8 * 2.097152e6 + 4 * 3.145728e6 + 4 * 3.670016e6) / 819e9
    share = readers["flash_roofline"](run)
    assert share == pytest.approx(100 * least / 159.233e-6, rel=1e-3)
    # nothing to read: nothing returned, never a zero
    assert readers["flash_roofline"](dict(run, trace=None)) is None
    other = dict(run, traffic={"batch_size": 4, "seq_len": 512})
    assert readers["flash_roofline"](other) is None
