"""Each driver end to end at a tiny size, and ``correct`` coming out false when
the timed path is broken underneath or a lower precision takes its place."""

import os
import time

import jax
import numpy as np
import pytest

from benchmark import checks, loader, reference, traffic_gen
from benchmark.drivers import serve, train

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.json")


def run_cell(name, seed=2 ** 31 + 5, seconds=2.0, trace=False):
    loaded = loader.load_cell(name, TINY)
    driver = {"train": train, "serve": serve}[loaded["traffic"]["driver"]]
    facts = driver.drive(loaded, seed, seconds, trace, jax.devices(),
                         t0=time.perf_counter())
    return loaded, facts


@pytest.fixture(scope="module")
def trained():
    return run_cell("tiny-sincos.tiny-train")


def test_train_driver_runs_its_window_and_is_correct(trained):
    loaded, facts = trained
    job = loaded["traffic"]
    assert checks.holds(facts["checks"]), facts["checks"]
    assert facts["failed"] == 0
    assert facts["window"]["seconds"] >= 2.0
    assert facts["window"]["tokens"] == (facts["window"]["steps"] * job["batch_size"]
                                         * job["seq_len"])
    assert facts["attempted"] == job["warmup_steps"] + facts["window"]["steps"]
    assert facts["compiles_in_window"] == 0
    assert facts["end_to_end"]["train_tokens_per_s"] > 0
    assert facts["end_to_end"]["setup_s"] > 0
    assert set(facts["checks"]) == {"loss1_gap", "loss2_gap", "loss3_gap",
                                    "grad_norm_gap", "delta_norm_gap"}
    # the key's bias has no gradient under softmax: left out by the rule
    assert facts["checks"]["delta_norm_gap"]["leaves_left_out"] == 2


def _unchanged(self, params, nt, opt_state, batch):
    time.sleep(0.01)        # a step takes time, or the epochs run out
    return params, nt, opt_state, jax.numpy.float32(6.0)


def _half_batch(inner):
    def run_step(self, params, nt, opt_state, batch):
        return inner(self, params, nt, opt_state,
                     tuple(np.asarray(b)[: len(b) // 2] for b in batch))
    return run_step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(monkeypatch, fault):
    from distkeras_tpu.parallel.tensor import SPMDEngine

    broken = (_unchanged if fault == "state_unchanged"
              else _half_batch(SPMDEngine.run_step))
    monkeypatch.setattr(SPMDEngine, "run_step", broken)
    _, facts = run_cell("tiny-sincos.tiny-train", seconds=0.5)
    assert not checks.holds(facts["checks"]), facts["checks"]


def test_the_fp8_control_in_the_programs_place_is_not_correct(trained):
    """The reference in float8 stands where the program stood: at least one
    number passes its limit (at this size the limits are the test mix's own)."""
    loaded, _ = trained
    m, job = loaded["config"]["model"], loaded["traffic"]
    x, y = train.token_pool(m, job, 11)
    b = job["batch_size"]
    first = [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b]) for i in range(3)]
    ref = reference.train_steps(m, 11, first, job["learning_rate"], rows_per_block=2)
    control = reference.train_steps(m, 11, first, job["learning_rate"],
                                    precision="fp8", rows_per_block=2)
    assert checks.holds(checks.train(ref, ref, job["limits"]))
    assert not checks.holds(checks.train(control, ref, job["limits"]))


@pytest.fixture(scope="module")
def served():
    return run_cell("tiny-rope-gqa.tiny-serve")


def test_serve_driver_runs_its_window_and_is_correct(served):
    loaded, facts = served
    assert checks.holds(facts["checks"]), facts["checks"]
    assert facts["failed"] == 0 and facts["attempted"] > 10
    assert facts["window"]["requests_done"] > 10
    assert facts["compiles_in_window"] == 0
    assert facts["end_to_end"]["serve_tokens_per_s"] > 0
    assert facts["end_to_end"]["request_p95_ms"] > 0
    assert set(facts["checks"]) == {"served_token_gap", "served_gap_mean", "short_replies"}
    assert facts["checks"]["served_token_gap"]["tokens"] >= 16
    assert facts["counters"]["latency"]["default"]["count"] > 10
    queue = loader.load_reader(os.path.join(loader.ROOT, "benchmark", "metrics",
                                            "queue_mean_ms.py"))
    assert queue(facts) > 0


def test_the_fp8_control_in_the_served_tokens_place_is_not_correct(served):
    """The serve control that sets the limit: the token that the reference in
    float8 puts first, at every served position of the run's own sample."""
    from benchmark import controls

    loaded, facts = served
    m, mix = loaded["config"]["model"], loaded["traffic"]
    control = controls.control_checks(m, mix, 2 ** 31 + 5, facts["sample"], "fp8")
    assert control["served_token_gap"]["tokens"] == facts["checks"]["served_token_gap"]["tokens"]
    assert not checks.holds(control), control


def test_the_programs_int8_path_is_read_as_a_control(served):
    """``quantize_lm`` and ``q_matmul`` in the program's place. At this size it
    reads within twice the bfloat16 program (PERF.md section 6 has the chip's
    readings), so the test holds it to running and to a sound reading."""
    from benchmark import controls

    loaded, facts = served
    m, mix = loaded["config"]["model"], loaded["traffic"]
    control = controls.control_checks(m, mix, 2 ** 31 + 5, facts["sample"], "int8")
    assert control["served_token_gap"]["tokens"] == facts["checks"]["served_token_gap"]["tokens"]
    assert 0 <= control["served_gap_mean"]["value"] <= control["served_token_gap"]["value"] < 1.0


def test_an_altered_token_is_not_correct(monkeypatch):
    from distkeras_tpu.serving.scheduler import GenerationEngine

    emit = GenerationEngine._emit

    def altered(self, b, tokens):
        emit(self, b, [(t + 1) % self._module.vocab for t in tokens])

    monkeypatch.setattr(GenerationEngine, "_emit", altered)
    _, facts = run_cell("tiny-rope-gqa.tiny-serve", seconds=0.5)
    assert not checks.holds(facts["checks"]), facts["checks"]


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = loader.load_cell("tiny-rope-gqa.tiny-serve", TINY)["traffic"]
    cycle = mix["cycle"]
    a, b = traffic_gen.Plan(mix, 512, 1), traffic_gen.Plan(mix, 512, 2 ** 31 + 9)

    def sizes(plan, c, col):        # one cycle's prompt or output lengths
        return [plan.sizes(i)[col] for i in range(c * cycle, (c + 1) * cycle)]

    # every cycle of every seed holds the distribution's mid-point quantiles
    want = sorted(traffic_gen.quantiles(mix["prompt_len"], cycle))
    assert sorted(sizes(a, 0, 0)) == sorted(sizes(a, 3, 0)) == sorted(sizes(b, 1, 0)) == want
    assert sorted(sizes(a, 0, 1)) == sorted(sizes(b, 2, 1))
    assert sizes(a, 0, 0) != sizes(b, 0, 0) and sizes(a, 0, 0) != sizes(a, 1, 0)
    assert min(want) >= 40 and max(want) <= 150 and len(set(want)) > cycle // 2
    assert not np.array_equal(a.prompt(0)[:40], b.prompt(0)[:40])
    assert np.array_equal(a.prompt(3), traffic_gen.Plan(mix, 512, 1).prompt(3))
    assert len(a.prompt(3)) == a.sizes(3)[0]
    shared = traffic_gen.Plan(dict(mix, shared_prefix={"groups": 2, "tokens": 32}), 512, 1)
    assert np.array_equal(shared.prompt(0)[:32], shared.prompt(2)[:32])
    assert not np.array_equal(shared.prompt(0)[:32], shared.prompt(1)[:32])
    opened = traffic_gen.Plan(dict(mix, loop="open", rate_per_s=50.0), 512, 1)
    due = [opened.sizes(i)[2] for i in range(3 * cycle)]
    assert np.all(np.diff(due) > 0)
    # a cycle's gaps add up to the same whatever their order: the rate is exact
    assert due[2 * cycle - 1] == pytest.approx(2 * cycle / 50.0, rel=0.02)


def test_a_lognormal_is_fixed_by_its_median_and_mean():
    q = traffic_gen.quantiles({"dist": "lognormal", "median": 1500, "mean": 2048,
                               "min": 1, "max": 10 ** 6}, 4096)
    assert np.median(q) == pytest.approx(1500, rel=0.01)
    assert q.mean() == pytest.approx(2048, rel=0.02)


def test_train_driver_sharded_over_four_virtual_devices():
    """The four-chip cell's path: the same driver, ZeRO-3 over dp = 4."""
    _, facts = run_cell("tiny-sincos.tiny-train-fsdp4", seconds=1.0)
    assert checks.holds(facts["checks"]), facts["checks"]
    assert facts["compiles_in_window"] == 0
