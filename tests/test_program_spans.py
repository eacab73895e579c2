"""The program's own spans at the boundaries that feed the chip (ISSUE 25):
the run log, the compile listener, ``MeshTrainer.train``'s spans, the
profiler sink and the serving engine's loop. All on the CPU."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu.observability import trace


@pytest.fixture(autouse=True)
def _tracing_off():
    trace.disable()
    yield
    trace.disable()


def _mark():
    """The run log from now on: entries another test left are skipped."""
    t = time.perf_counter_ns()

    def since():
        log = [e for e in trace.run_log() if e["t0_ns"] >= t]
        assert len(log) < trace.RUN_LOG_SIZE, "the run log turned over"
        return log

    return since


def _tiny_lm_job(rng, **kw):
    from distkeras_tpu.models.lm import next_token_dataset, transformer_lm
    from distkeras_tpu.trainers import MeshTrainer

    spec = transformer_lm(vocab=8, maxlen=16, dim=32, heads=4, depth=1,
                          dtype=jnp.float32)
    rows = np.stack([(np.arange(13) + s) % 8
                     for s in rng.integers(0, 8, 64)]).astype(np.int32)
    kw.setdefault("input_mode", "stream")
    t = MeshTrainer(spec, loss="sparse_softmax_cross_entropy",
                    worker_optimizer="adam", learning_rate=5e-3,
                    mesh_shape={"dp": 1}, batch_size=16, **kw)
    return t, next_token_dataset(rows)


# -- the run log --------------------------------------------------------------


def test_run_log_records_with_tracing_off():
    since = _mark()
    assert not trace.enabled()
    with trace.span("train.build_engine", cat="train", log=True,
                    args={"k": 1}):
        pass
    (ev,) = [e for e in since() if e["name"] == "train.build_engine"]
    assert set(ev) == {"name", "cat", "corr", "t0_ns", "dur_ns", "tid",
                       "tname", "args"}
    assert ev["args"] == {"k": 1} and ev["dur_ns"] >= 0
    assert trace.events() == []            # the ring stays off


def test_run_log_is_bounded_and_counts_what_it_drops():
    with trace.span("first", log=True):
        pass
    before = trace.dropped_spans()
    have = len(trace.run_log())
    n = trace.RUN_LOG_SIZE + 10
    for _ in range(n):
        with trace.span("filler", log=True):
            pass
    log = trace.run_log()
    assert len(log) == trace.RUN_LOG_SIZE
    assert all(e["name"] == "filler" for e in log)       # oldest went first
    assert trace.dropped_spans() - before == have + n - trace.RUN_LOG_SIZE


def test_logged_span_is_saved_with_the_ring_and_not_twice(tmp_path):
    from distkeras_tpu.observability.trace import load_json_maybe_gz

    trace.enable()
    with trace.span("train.finish", log=True, profile=True):
        with trace.span("ps.fold"):
            pass
    assert [e["name"] for e in trace.events()] == ["ps.fold"]
    doc = load_json_maybe_gz(trace.save(str(tmp_path / "t.json")))
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert names.count("train.finish") >= 1 and names.count("ps.fold") == 1
    mine = [e for e in doc["traceEvents"] if e["name"] == "train.finish"]
    assert len({e["ts"] for e in mine}) == len(mine)


# -- MeshTrainer.train --------------------------------------------------------


def _inside(child, parent):
    return (parent["t0_ns"] <= child["t0_ns"]
            and child["t0_ns"] + child["dur_ns"]
            <= parent["t0_ns"] + parent["dur_ns"])


def test_mesh_trainer_spans_in_order_nested_with_epoch_args(rng, capsys):
    since = _mark()
    t, ds = _tiny_lm_job(rng, num_epoch=2, log_metrics=True)
    t.train(ds)
    evs = [e for e in since() if e["name"].startswith("train.")]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    order = ["train.build_engine", "train.init_weights", "train.init_state",
             "train.epoch", "train.finish", "train.fetch_params"]
    starts = [by[n][0]["t0_ns"] for n in order]
    assert starts == sorted(starts)
    ends = [by[n][-1]["t0_ns"] + by[n][-1]["dur_ns"] for n in order]
    assert ends == sorted(ends)
    assert [e["args"] for e in by["train.epoch"]] == [
        {"epoch": 0, "steps": 4}, {"epoch": 1, "steps": 4}]
    assert [e["args"] for e in by["train.epoch_end"]] == [
        {"epoch": 0}, {"epoch": 1}]
    for epoch, end in zip(by["train.epoch"], by["train.epoch_end"]):
        assert _inside(end, epoch)
    for name in ("train.drain", "train.log_metrics"):
        assert len(by[name]) == 2
        for child, end in zip(by[name], by["train.epoch_end"]):
            assert _inside(child, end)
    # where nothing runs, nothing is recorded
    assert "train.validate" not in by and "train.checkpoint" not in by
    assert "train.stage_epoch" not in by
    assert all(e["cat"] == "train" for e in evs)


def test_mesh_trainer_resident_and_checkpoint_spans(rng, tmp_path):
    since = _mark()
    t, ds = _tiny_lm_job(rng, num_epoch=2, input_mode="resident",
                         checkpoint_dir=str(tmp_path / "ck"),
                         validation_data=None)
    t.train(ds)
    names = [e["name"] for e in since() if e["name"].startswith("train.")]
    assert names.count("train.stage_epoch") == 1
    assert names.count("train.epoch") == 2
    assert names.count("train.checkpoint") == 2
    assert "train.drain" not in names          # log_metrics is off: no sync
    since = _mark()
    t2, _ = _tiny_lm_job(rng, num_epoch=3, input_mode="resident",
                         checkpoint_dir=str(tmp_path / "ck"), resume=True)
    t2.train(ds)
    evs = [e for e in since() if e["name"].startswith("train.")]
    (w,) = [e for e in evs if e["name"] == "train.init_weights"]
    assert w["args"] == {"source": "checkpoint"}
    assert [e["args"]["epoch"] for e in evs
            if e["name"] == "train.epoch"] == [2]


def test_an_epoch_handed_no_batch_leaves_no_entry(rng):
    from distkeras_tpu.data import Dataset

    class Two(Dataset):
        turns = 0

        def batches(self, *a, **kw):
            self.turns += 1
            return super().batches(*a, **kw) if self.turns <= 2 else iter(())

    since = _mark()
    t, ds = _tiny_lm_job(rng, num_epoch=40)
    t.train(Two({c: ds[c] for c in ("features", "label")}))
    evs = since()
    assert sum(e["name"] == "train.epoch" for e in evs) == 2
    assert sum(e["name"] == "train.epoch_end" for e in evs) == 2


def test_compile_listener_names_train_step_once_over_three_epochs(rng):
    since = _mark()
    t, ds = _tiny_lm_job(rng, num_epoch=3)
    t.train(ds)
    evs = since()
    for name in ("jax.trace", "jax.lower", "jax.compile"):
        hits = [e for e in evs if e["name"] == name
                and "train_step" in (e["args"]["fun"] or "")]
        assert len(hits) == 1, (name, [e["args"] for e in hits])
    (compiled,) = [e for e in evs if e["name"] == "jax.compile"
                   and "train_step" in e["args"]["fun"]]
    epochs = [e for e in evs if e["name"] == "train.epoch"]
    assert len(epochs) == 3
    assert _inside(compiled, epochs[0])        # and never again
    assert any(e["name"] == "jax.compile"
               and "train_init_state" in e["args"]["fun"] for e in evs)
    # eager key folds and adds are counted, not kept
    assert all(e["dur_ns"] >= trace.MIN_TRACE_NS for e in evs
               if e["name"] == "jax.trace")
    counts = trace.jax_counts()
    assert set(counts) == {"cache_hits", "cache_misses", "short_traces",
                           "short_trace_ns"}
    assert counts["short_traces"] > 0


# -- the profiler sink ---------------------------------------------------------


def _host_annotations(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.duration_ns, dict(e.stats))
                       for e in line.events)
    return sorted(out, key=lambda e: e[1])


def test_profiler_slice_holds_step_annotations_with_step_num(rng, tmp_path):
    """The driver's own profiler options; two tiny epochs inside the slice."""
    t, ds = _tiny_lm_job(rng, num_epoch=2, log_metrics=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t.train(ds)
    finally:
        jax.profiler.stop_trace()
    anns = _host_annotations(str(tmp_path))
    steps = [a for a in anns if a[0] == "train.step"]
    assert [a[3]["step_num"] for a in steps] == list(range(8))
    names = {a[0] for a in anns}
    assert {"train.input", "train.epoch", "train.epoch_end", "train.drain",
            "train.init_state", "train.fetch_params"} <= names
    epochs = [a for a in anns if a[0] == "train.epoch"]
    assert [a[3]["epoch"] for a in epochs] == [0, 1]
    # a step lies inside its epoch, on the one clock
    for s in steps[:4]:
        assert epochs[0][1] <= s[1] and s[1] + s[2] <= epochs[0][1] + epochs[0][2]
    assert trace.events() == []                # no ring was switched on


# -- the serving engine's loop -------------------------------------------------


@pytest.fixture(scope="module")
def chunked_engine():
    from distkeras_tpu.models.lm import transformer_lm
    from distkeras_tpu.serving import GenerationEngine

    spec = transformer_lm(vocab=64, maxlen=64, dim=32, heads=4, depth=1,
                          dtype=jnp.float32, pos_embedding="rope",
                          kv_heads=2)
    params, _ = spec.init_np(0)
    return GenerationEngine(spec, params, max_batch=4, block_size=8,
                            prefill_chunk=8)


def test_engine_step_is_one_span_with_its_chunk_and_counters(
        chunked_engine, tmp_path, capsys):
    eng = chunked_engine
    trace.enable()
    reqs = [eng.submit(np.arange(3 + 5 * i, dtype=np.int32) % 64,
                       max_new_tokens=2) for i in range(3)]
    before = eng.stats()
    assert eng.step()
    after = eng.stats()
    evs = trace.events()
    (step,) = [e for e in evs if e["name"] == "serve.step"]
    (chunk,) = [e for e in evs if e["name"] == "serve.chunk"]
    assert _inside(chunk, step)
    assert step["args"]["step_num"] >= 1
    a = chunk["args"]
    assert (a["rows"], a["padded_rows"], a["tpad"]) == (3, 4, 8)
    assert a["key"] == str((8, 4, a["width"]))
    assert after["chunk_rows"] - before["chunk_rows"] == a["rows"]
    assert (after["chunk_rows_padded"] - before["chunk_rows_padded"]
            == a["padded_rows"])
    assert after["programs_built"] - before["programs_built"] >= 1
    for name in ("serve.retire", "serve.admit"):
        (child,) = [e for e in evs if e["name"] == name]
        assert _inside(child, step)
    # a request's prefill record is the step's one chunk interval
    prefills = [e for e in evs if e["name"] == "serve.prefill"]
    assert len(prefills) == 3
    assert {(e["t0_ns"], e["dur_ns"]) for e in prefills} == {
        (chunk["t0_ns"], chunk["dur_ns"])}
    assert sorted(e["corr"] for e in prefills) == sorted(r.id for r in reqs)
    eng.run_until_idle()
    decode = [e for e in trace.events()
              if e["name"] == "serve.decode_step"]
    assert decode and all(set(e["args"]) == {"rows"} for e in decode)
    assert all(r.result().shape == (2,) for r in reqs)

    # the operator's reading of it
    from distkeras_tpu.observability.__main__ import main

    path = trace.save(str(tmp_path / "serve.json.gz"))
    assert main(["steps", path, "--top", "2"]) == 0
    out = capsys.readouterr().out
    assert "serve.step" in out and "serve.chunk" in out
    assert f"key={a['key']}" in out and "serve.prefill" not in out
    assert main(["steps", path, "--json"]) == 0
    import json

    rep = json.loads(capsys.readouterr().out)
    assert rep["steps"] == len([e for e in trace.events()
                                if e["name"] == "serve.step"])
    longest = rep["longest"][0]
    assert longest["dur_ns"] == max(s["dur_ns"] for s in rep["longest"])
    keyed = [t for t in rep["totals"] if t["name"] == "serve.chunk"]
    assert sum(t["count"] for t in keyed) >= 2      # 13 tokens: two chunks
    assert any(t["key"] == a["key"] for t in keyed)


def test_engine_loop_annotates_a_profiler_slice(chunked_engine, tmp_path):
    eng = chunked_engine
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        req = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=4)
        eng.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    assert req.result().shape == (4,)
    anns = _host_annotations(str(tmp_path))
    steps = [a for a in anns if a[0] == "serve.step"]
    nums = [a[3]["step_num"] for a in steps]
    assert len(steps) >= 2 and nums == sorted(nums)
    (chunk,) = [a for a in anns if a[0] == "serve.chunk"]
    assert chunk[3]["rows"] == 1 and chunk[3]["padded_rows"] == 1
    assert steps[0][1] <= chunk[1] \
        and chunk[1] + chunk[2] <= steps[0][1] + steps[0][2]
    assert any(a[0] == "serve.decode_step" and a[3]["rows"] == 1
               for a in anns)
    assert trace.events() == []
