"""``benchmark/parts.py`` on a pair recorded on the chip in ONE run of the tiny
ZAYA1 step (``tiny-zaya-chip.tiny-train-moe-chip``, PR 35): the profiler's
trace of a slice of two steps and the program's own table of the step it ran
(``observability.programs.save``). The older recorded traces have no table and
cannot be given one."""

import importlib.util
import os

import pytest

from benchmark import loader, parts, spans, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "small_parts.xplane.pb.gz")
TABLE = os.path.join(HERE, "data", "small_parts.op_scopes.json")
METRICS = os.path.join(loader.ROOT, "benchmark", "metrics")


@pytest.fixture(scope="module")
def pair():
    trace = xplane.reduce(xplane.read_planes(TRACE), 1)
    steps = sum(1 for a in spans.read_annotations(TRACE) if a[0] == parts.STEP)
    return trace, parts.load_table(TABLE), steps


def test_the_parts_sum_to_the_traces_busy_time(pair):
    trace, table, steps = pair
    assert steps == 2
    laid = parts.lay(trace, table, steps)
    assert laid["sum_ns"] == pytest.approx(trace["busy_s"] * 1e9, rel=1e-3)
    assert laid["found_pct"] > parts.FOUND_MIN_PCT and not laid["missing"]
    assert 90.0 < laid["scoped_pct"] <= laid["found_pct"] <= 100.0
    paths = {path for path, _ in laid["parts"]}
    for scope in ("fused_ce_fwd", "fused_ce_bwd", "moe_route", "moe_experts", "cca", "optimizer",
                  "flash_fwd", "flash_dq", "flash_dkv", "ragged-dot-none"):
        assert any(scope in p for p in paths), scope
    passes = {which for _, which in laid["parts"]}
    assert passes >= {"forward", "remat", "backward"}
    # every flash kernel of the slice is under CCA, the forward in two passes
    fwd = {which: n for (p, which), (_, n) in laid["parts"].items() if p[-1:] == ("flash_fwd",)}
    assert set(fwd) == {"forward", "remat"} and min(fwd.values()) > 0


def test_an_event_missing_from_the_table_lowers_the_scoped_share(pair):
    trace, table, steps = pair
    whole = parts.lay(trace, table, steps)
    longest = max((e for e in trace["ops"] if table[xplane.short_name(e)][0]),
                  key=lambda e: trace["ops"][e][0])
    name, ns = xplane.short_name(longest), trace["ops"][longest][0]
    less = parts.lay(trace, {k: v for k, v in table.items() if k != name}, steps)
    assert less["missing"] == {name: ns}
    assert less["parts"][parts.NOT_IN_TABLE][0] == ns
    assert less["scoped_pct"] == pytest.approx(
        whole["scoped_pct"] - 100.0 * ns / (trace["busy_s"] * 1e9))
    assert less["found_pct"] < whole["found_pct"]
    assert less["sum_ns"] == pytest.approx(whole["sum_ns"])


def _reader(name):
    return loader.load_reader(os.path.join(METRICS, name + ".py"))


def _run(trace, tmp_path, tag):
    return {"trace": trace, "trace_dir": str(tmp_path / tag), "chips": 1}


@pytest.fixture
def on_the_pair(pair, monkeypatch):
    """The readers' two ways in, answered from the recorded pair."""
    trace, table, steps = pair
    anns = [a for a in spans.read_annotations(TRACE)]
    monkeypatch.setattr(spans, "of_run", lambda run: (anns, []))
    monkeypatch.setattr(parts, "_made", {})
    return trace, table, steps


def test_the_five_readers_read_the_pair(on_the_pair, monkeypatch, tmp_path, capsys):
    trace, table, steps = on_the_pair
    monkeypatch.setattr(parts, "program_table", lambda: table)
    run = _run(trace, tmp_path, "whole")
    values = {n: _reader(n)(run) for n in (
        "step_scoped_pct.train", "loss_ms.train", "attn_outside_flash_ms.train",
        "moe_route_ms.train", "remat_forward_ms.train")}
    assert all(v is not None and v > 0 for v in values.values()), values
    step_ms = trace["busy_s"] * 1e3 / steps
    assert values["step_scoped_pct.train"] == pytest.approx(
        parts.lay(trace, table, steps)["scoped_pct"])
    assert sum(v for n, v in values.items() if n in (
        "loss_ms.train", "attn_outside_flash_ms.train", "moe_route_ms.train")) < step_ms
    assert 0.1 * step_ms < values["remat_forward_ms.train"] < 0.4 * step_ms
    err = capsys.readouterr().err
    assert err.count("device time by part and pass") == 1, "the table is shown once a run"
    for said in ("fused_ce_bwd [backward]", "moe_route [remat]", "cca_conv",
                 "programs in the slice", "operations under no path"):
        assert said in err, said


def test_under_the_found_share_no_reader_reports(on_the_pair, monkeypatch, tmp_path, capsys):
    """A table that names under 99.5 % of the busy time is another program's."""
    trace, table, steps = on_the_pair
    by_time = sorted(trace["ops"], key=lambda e: -trace["ops"][e][0])
    gone = {xplane.short_name(e) for e in by_time[:3]}
    lost = sum(trace["ops"][e][0] for e in by_time[:3]) / (trace["busy_s"] * 1e9)
    assert lost > 1.0 - parts.FOUND_MIN_PCT / 100.0
    monkeypatch.setattr(parts, "program_table",
                        lambda: {k: v for k, v in table.items() if k not in gone})
    run = _run(trace, tmp_path, "short")
    for name in ("step_scoped_pct.train", "loss_ms.train", "remat_forward_ms.train"):
        assert _reader(name)(run) is None, name
    assert "not this trace's program" in capsys.readouterr().err


def test_without_a_table_or_a_trace_the_readers_leave_their_metric_out(on_the_pair, monkeypatch,
                                                                       tmp_path):
    trace, _, _ = on_the_pair
    monkeypatch.setattr(parts, "program_table", lambda: None)     # the parent commit
    assert _reader("loss_ms.train")(_run(trace, tmp_path, "none")) is None
    assert _reader("step_scoped_pct.train")({"trace": None, "trace_dir": None}) is None


def test_program_table_is_none_where_the_program_has_no_such_module(monkeypatch):
    import sys

    import distkeras_tpu.observability.programs     # noqa: F401  (the attribute to take away)

    monkeypatch.delattr(distkeras_tpu.observability, "programs")
    monkeypatch.setitem(sys.modules, "distkeras_tpu.observability.programs", None)
    assert parts.program_table() is None


def test_the_command_prints_a_saved_pair(capsys):
    parts._main(TRACE, TABLE)
    out = capsys.readouterr().out
    assert "device time by part and pass, ms a step over 2 steps" in out
    assert "blocks_*/moe/moe_route" in out and "fused_ce_bwd" in out
    spec = importlib.util.find_spec("benchmark.parts")
    assert spec is not None and spec.origin.endswith("benchmark/parts.py")
