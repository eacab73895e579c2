"""The block-diffusion cell's files: its counts against the published sizes,
its configuration against the catalog, its driver end to end at a tiny size,
and its readers' arithmetic."""

import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import checks, flops_sdar, limits_bd, loader, weights_sdar
from benchmark.drivers import train_bd

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.sdar.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cut():
    with open(os.path.join(loader.ROOT, "benchmark", "configs", "sdar-30b-a3b.json")) as f:
        return json.load(f)


def test_counts_at_the_published_sizes_and_at_the_cut():
    """30.5 B parameters of which 3.3 B are active a token as published;
    ISSUE 31's 645.6 M (9.62 GiB at 16 B) at the cut."""
    config = cut()
    m, pub = config["model"], config["published"]
    whole = dict(m, depth=pub["num_hidden_layers"], vocab=pub["vocab_size"],
                 experts_held=[0, pub["num_experts"]])
    assert flops_sdar.param_count(whole) == pytest.approx(30.5e9, rel=5e-3)
    active = flops_sdar.param_count(whole, experts=m["experts_per_token"])
    assert active == pytest.approx(3.3e9, rel=0.03)
    assert flops_sdar.param_count(m) == 645_623_296
    assert flops_sdar.param_count(m) * 16 / 2 ** 30 == pytest.approx(9.62, abs=0.01)
    assert flops_sdar.attention_params(m) == 2 * 2048 * 4096 + 2 * 2048 * 512
    assert flops_sdar.expert_params(m) == 3 * 2048 * 768
    pairs = flops_sdar.admitted_pairs(4096, 4)
    assert sum(pairs.values()) == 16_793_600            # 2,050 keys a query on average
    assert pairs["nn"] == 4 * 4096 and pairs["cc"] - pairs["nc"] == 4 * 4096
    step = flops_sdar.step_flops(m, 4, 4096, [32768.0] * 6, 8192.0)
    share = {k: v / sum(step.values()) for k, v in step.items()}
    assert share["scores"] == pytest.approx(0.394, abs=0.005)
    assert share["projections"] == pytest.approx(0.447, abs=0.005)
    assert share["experts"] == pytest.approx(0.111, abs=0.005)
    assert sum(step.values()) / (4 * 4096) / 1e9 == pytest.approx(2.81, abs=0.02)


def test_the_configuration_keeps_every_published_number_it_does_not_list_as_reduced():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        entry = next(json.loads(line) for line in f if '"name": "SDAR-30B-A3B-Chat"' in line)
    config = cut()
    assert config["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and config["published"][key] == value, key
        else:
            assert config[key] == value, key
    m = config["model"]
    assert (m["depth"], m["experts_held"], m["vocab"], m["maxlen"]) == (6, [0, 16], 18992, 4096)
    assert (m["dim"], m["heads"], m["kv_heads"], m["head_dim"], m["experts"],
            m["experts_per_token"], m["expert_dim"]) == (2048, 32, 4, 128, 128, 8, 768)
    assert len(config["assumed"]) >= 7 and len(config["departures"]) >= 4 and config["deployment"]


def test_the_cell_finds_every_file():
    loaded = loader.load_cell("sdar-30b-a3b.train")
    job = loaded["traffic"]
    assert job["driver"] == "train_bd" and loaded["cell"]["chips"] == 1
    assert (job["batch_size"], job["seq_len"], job["learning_rate"]) == (4, 4096, 1e-5)
    assert set(job["limits"]) == {"loss_gap", "grad_norm_gap", "expert_grad_gap",
                                  "delta_norm_gap", "route_count_gap", "own_block_gap"}
    assert set(job["limits_why"]) == set(job["limits"])
    names = [m["name"] for m in loaded["per_layer"]]
    assert names[-2:] == ["mfu.train.bd", "flash_roofline.bd"]
    # the expert layer's two readers are the sparse cell's own, on pairs
    assert {"moe_expert_roofline", "moe_load_max_over_mean"} <= set(names)
    assert len(names) == 13
    for m in loaded["per_layer"]:
        assert callable(loader.load_reader(m["reader"]))
    for other in ("xglm-564m.train", "zaya1-8b.train"):
        theirs = {m["name"] for m in loader.load_cell(other)["per_layer"]}
        assert not theirs & set(names[-2:])


def test_the_two_layouts_hold_the_same_leaves_and_an_expert_is_known_by_its_number():
    m = loader.load_cell("tiny-sdar.tiny-train-bd", TINY)["config"]["model"]
    key = weights_sdar.seed_key(2 ** 31 + 7)
    flat = weights_sdar.layered(m, key)
    back = weights_sdar.from_program_tree(m, weights_sdar.program_tree(m, key))
    assert set(flat) == set(back)
    for name in weights_sdar.block_leaves(m):
        for a, b in zip(back[name], flat[name]):
            assert np.array_equal(a, b), name
    other = weights_sdar.layered(dict(m, experts_held=[5, 4]), key)
    assert np.array_equal(other["ex_in"][0][0], flat["ex_in"][0][1])     # expert 5, either way
    state = weights_sdar.counters_tree(m, key)["counters"]
    assert state["bd_key"].dtype == np.uint32 and state["bd_key"].shape == (2,)
    assert int(state["bd_step"]) == 0 and not np.any(state["blocks_2"]["moe"]["moe_tokens"])
    norms = weights_sdar.leaf_norms(m, flat)
    assert {"ex_gate.4", "ex_up.5", "ex_down.7", "wr", "qn_g", "head"} <= set(norms)


@pytest.fixture(scope="module")
def trained():
    loaded = loader.load_cell("tiny-sdar.tiny-train-bd", TINY)
    facts = train_bd.drive(loaded, 2 ** 31 + 5, 1.0, False, jax.devices(),
                           t0=time.perf_counter())
    return loaded, facts


def test_the_driver_runs_its_window_and_is_correct(trained):
    loaded, facts = trained
    job, m = loaded["traffic"], loaded["config"]["model"]
    assert checks.holds(facts["checks"]), facts["checks"]
    assert set(facts["checks"]) == {"loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
                                    "expert_grad_gap", "delta_norm_gap", "route_count_gap",
                                    "own_block_gap"}
    # the counters the first timed step left are the reference's routes, counted
    assert facts["checks"]["route_count_gap"]["pairs_a_layer"] == (
        2 * job["batch_size"] * job["seq_len"] * m["experts_per_token"])
    assert facts["failed"] == 0 and facts["compiles_in_window"] == 0
    steps = facts["window"]["steps"]
    assert facts["window"]["tokens"] == steps * job["batch_size"] * job["seq_len"]
    pairs = np.asarray(facts["moe"]["window_tokens"])
    assert pairs.sum(1).tolist() == [2 * facts["window"]["tokens"] * m["experts_per_token"]] * 3
    assert 0 < facts["bd"]["window_masked"] < facts["window"]["tokens"]


@pytest.mark.parametrize("name", ["mfu.train.bd", "moe_load_max_over_mean"])
def test_the_counter_readers_on_the_tiny_run(trained, name):
    loaded, facts = trained
    run = dict(facts, model=loaded["config"]["model"], traffic=loaded["traffic"], chips=1,
               peaks=PEAKS)
    read = loader.load_reader(os.path.join(loader.ROOT, "benchmark", "metrics", name + ".py"))
    value = read(run)
    assert value > 0
    if name == "mfu.train.bd":
        m, job, steps = run["model"], run["traffic"], facts["window"]["steps"]
        need = sum(flops_sdar.step_flops(
            m, job["batch_size"], job["seq_len"],
            [p / steps for p in flops_sdar.held_pairs(m, facts["moe"]["window_tokens"])],
            facts["bd"]["window_masked"] / steps).values()) * steps
        assert value == pytest.approx(100 * need / facts["window"]["seconds"] / 197e12)
    # a program without the counters (the parent's): nothing to read, no error
    assert read(dict(run, moe={}, bd=None)) is None or name.startswith("moe_load")


def test_the_trace_readers_return_nothing_without_what_they_read():
    metrics = os.path.join(loader.ROOT, "benchmark", "metrics")
    m = cut()["model"]
    for name in ("flash_roofline.bd", "moe_expert_roofline"):
        read = loader.load_reader(os.path.join(metrics, name + ".py"))
        assert read({"model": m, "trace": None}) is None
    pairs = loader.load_reader(os.path.join(metrics, "moe_expert_roofline.py"))
    assert pairs({"model": m, "trace": {"ops": {}}}) is None         # no counters: the parent


def test_the_controls_script_prints_a_line_a_control(capsys):
    assert limits_bd.main(["tiny-sdar.tiny-train-bd", "11", "unweighted,half_batch"], TINY) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [(r["seed"], r["control"]) for r in lines] == [(11, "unweighted"), (11, "half_batch")]
    assert lines[0]["loss1_gap"] > 0.1 and lines[0]["route_count_gap"] == 0
    assert lines[1]["loss1_gap"] > 1e-4 and lines[1]["grad_leaf"]
