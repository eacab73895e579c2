"""The ZAYA1 cell's files: its counts against the model card's, its driver end
to end at a tiny size, its controls, its balanced routers and its readers on a
trace recorded on the chip."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import checks, flops_zaya, limits_moe, loader, reference_zaya, weights_zaya, xplane
from benchmark.drivers import train, train_moe

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "BENCHMARK.zaya.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def cut():
    with open(os.path.join(loader.ROOT, "benchmark", "configs", "zaya1-8b.json")) as f:
        return json.load(f)


def test_counts_at_the_published_sizes_and_at_the_cut():
    """The model card's two numbers: 8.3 B parameters beside the 537 M table,
    about 0.76 B of them active a token; and ISSUE 27's 709 M at the cut."""
    config = cut()
    m = config["model"]
    pub = config["published"]
    whole = dict(m, depth=pub["num_hidden_layers"], vocab=pub["vocab_size"],
                 experts_held=[0, pub["num_experts"]])
    assert flops_zaya.table_params(whole) == 262272 * 2048
    assert flops_zaya.param_count(whole) - flops_zaya.table_params(whole) == pytest.approx(
        8.30e9, rel=2e-3)
    assert flops_zaya.active_params(whole) == pytest.approx(0.76e9, rel=0.02)
    assert flops_zaya.param_count(m) == 708_644_972          # the program's own count
    # by hand: CCA 5.24 M, convolutions 0.33 M, router 0.66 M, an expert 12.58 M
    assert flops_zaya.cca_params(m) == 2048 * (1024 + 256 + 256) + 1024 * 2048
    assert flops_zaya.conv_params(m) == 2 * 1024 + 2 * 8 * 128 * 128 + 2 * 256 + 2 * 2 * 128 * 128
    assert flops_zaya.router_params(m) == 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert flops_zaya.expert_params(m) == 3 * 2048 * 2048
    # a token: 6 a weight it passes, 3 x the causal scores, an expert if held
    per_layer = 6 * (5_242_880 + 330_240 + 659_456) + 3 * 4 * (4097 / 2) * 8 * 128
    assert flops_zaya.dense_flops_per_token(m, 4096) == pytest.approx(
        6 * per_layer + 6 * 32784 * 2048, rel=1e-12)
    assert flops_zaya.train_flops_per_token(m, 4096, 3.0) / 1e9 == pytest.approx(1.005, abs=0.002)
    ops, moved = flops_zaya.grouped_call(m, 16384, wide=True)
    assert ops == 2 * 16384 * 2048 * 4096 and moved == 2 * (8 * 2048 * 4096 + 16384 * 6144)


def test_the_configuration_keeps_every_published_number_it_does_not_list_as_reduced():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        entry = next(json.loads(line) for line in f if '"name": "ZAYA1-8B"' in line)
    config = cut()
    assert config["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and config["published"][key] == value, key
        else:
            assert config[key] == value, key
    m = config["model"]
    assert (m["depth"], m["experts_held"], m["vocab"], m["maxlen"]) == (6, [0, 8], 32784, 4096)
    assert (m["dim"], m["heads"], m["kv_heads"], m["head_dim"], m["router_dim"], m["experts"],
            m["expert_dim"]) == (2048, 8, 2, 128, 256, 16, 2048)
    assert len(config["assumed"]) >= 10 and config["departures"] and config["deployment"]


def test_the_cell_finds_every_file():
    loaded = loader.load_cell("zaya1-8b.train")
    assert loaded["traffic"]["driver"] == "train_moe" and loaded["cell"]["chips"] == 1
    assert loaded["traffic"]["batch_size"] * loaded["traffic"]["seq_len"] == 32768
    assert set(loaded["traffic"]["limits"]) == {"loss_gap", "grad_norm_gap", "delta_norm_gap",
                                                "route_gap"}
    names = [m["name"] for m in loaded["per_layer"]]
    # the trainer's spans are the same MeshTrainer's: their seven readers, then this cell's four
    assert names == ["device_idle_pct.train", "compiles_in_window.train", "setup_weights_s",
                     "setup_trace_lower_s", "setup_compile_s", "setup_named_pct",
                     "host_dispatch_ms.train", "input_wait_ms.train", "idle_named_pct.train",
                     "mfu.train.moe", "moe_expert_roofline", "flash_roofline.cca",
                     "moe_load_max_over_mean"]
    for m in loaded["per_layer"]:
        assert callable(loader.load_reader(m["reader"]))
    # the dense cell keeps its own readers and gets none of the new ones
    dense = [m["name"] for m in loader.load_cell("xglm-564m.train")["per_layer"]]
    assert "mfu.train" in dense and "flash_roofline" in dense
    assert not set(dense) & {"mfu.train.moe", "moe_expert_roofline", "flash_roofline.cca"}


def test_the_two_layouts_hold_the_same_leaves_and_an_expert_is_known_by_its_number():
    m = loader.load_cell("tiny-zaya.tiny-train-moe", TINY)["config"]["model"]
    key = weights_zaya.seed_key(2 ** 31 + 7)
    flat = weights_zaya.layered(m, key)
    back = weights_zaya.from_program_tree(m, weights_zaya.program_tree(m, key))
    assert set(flat) - set(back) == {"rbias"}          # state, not a parameter
    for name in back:
        if name in weights_zaya.block_leaves(m):
            for a, b in zip(back[name], flat[name]):
                assert np.array_equal(a, b), name
    state = weights_zaya.counters_tree(m, key)["counters"]
    assert np.array_equal(state["blocks_1"]["moe"]["router_bias"], flat["rbias"][1])
    assert not np.any(state["blocks_1"]["moe"]["moe_tokens"])
    other = weights_zaya.layered(dict(m, experts_held=[1, 2]), key)
    assert np.array_equal(other["ex_in"][0][0], flat["ex_in"][0][1])     # expert 1, either way
    assert not np.array_equal(other["ex_in"][0][1], flat["ex_in"][0][0])
    assert not np.array_equal(flat["wq"][0], flat["wq"][1])
    assert not np.array_equal(flat["embed"],
                              weights_zaya.layered(m, weights_zaya.seed_key(8))["embed"])
    norms = weights_zaya.leaf_norms(m, flat)
    assert {"ex_gate.0", "ex_up.1", "ex_down.1", "cq1", "wv_b", "w3", "tau"} <= set(norms)
    assert "rbias" not in norms
    assert all(np.shape(v) == (m["depth"],) for k, v in norms.items()
               if k not in ("embed", "lnf_g"))


def test_a_steps_balancing_gives_every_expert_its_share():
    """``reference_zaya.step_balancer``: from the seed's bias, which loads the
    experts unevenly, two sweeps on the step's own tokens leave every expert
    of every layer within a few tokens of an equal share; more sweeps, or the
    next step's from where this one ended, tighten it."""
    m = loader.load_cell("tiny-zaya.tiny-train-moe", TINY)["config"]["model"]
    key = weights_zaya.seed_key(5)
    tokens = np.random.default_rng(5).integers(0, m["vocab"], (4, 128)).astype(np.int32)
    flat = weights_zaya.layered(m, key)
    share = tokens.size // m["experts"]

    def worst(w):
        with jax.default_matmul_precision("highest"):
            chosen = np.asarray(reference_zaya.hidden(m, w, jnp.asarray(tokens))[1])
        return max(np.abs(np.bincount(c.ravel(), minlength=m["experts"]) - share).max()
                   for c in chosen)

    with jax.default_matmul_precision("highest"):
        once = reference_zaya.step_balancer(m)(flat, tokens)
        twice = reference_zaya.step_balancer(m)(dict(flat, rbias=once), tokens)
    assert worst(flat) > 0.1 * share                       # the seed's bias does not balance
    assert worst(dict(flat, rbias=once)) <= 0.1 * share
    assert worst(dict(flat, rbias=twice)) <= 2             # ties at the cut
    assert all(abs(float(np.sum(b))) < 1e-5 for b in once)


@pytest.fixture(scope="module")
def trained():
    loaded = loader.load_cell("tiny-zaya.tiny-train-moe", TINY)
    facts = train_moe.drive(loaded, 2 ** 31 + 5, 1.0, False, jax.devices(),
                            t0=time.perf_counter())
    return loaded, facts


def test_the_driver_runs_its_window_and_is_correct(trained):
    loaded, facts = trained
    job, m = loaded["traffic"], loaded["config"]["model"]
    assert checks.holds(facts["checks"]), facts["checks"]
    assert set(facts["checks"]) == {"loss1_gap", "loss2_gap", "loss3_gap", "grad_norm_gap",
                                    "delta_norm_gap", "route_gap"}
    assert facts["failed"] == 0 and facts["compiles_in_window"] == 0
    steps = facts["window"]["steps"]
    assert facts["attempted"] == job["warmup_steps"] + steps
    tokens = np.asarray(facts["moe"]["window_tokens"])
    assert tokens.shape == (m["depth"], m["experts"])
    assert tokens.sum(1).tolist() == [facts["window"]["tokens"]] * m["depth"]
    run = dict(facts, model=m, traffic=job, chips=1,
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    reader = lambda name: loader.load_reader(os.path.join(loader.ROOT, "benchmark", "metrics",
                                                          name + ".py"))
    assert 0 < reader("mfu.train.moe")(run) < 100
    assert 1.0 <= reader("moe_load_max_over_mean")(run) < 2.0
    # no trace, or a program that counts nothing: the readers return nothing
    for name in ("moe_expert_roofline", "flash_roofline.cca"):
        assert reader(name)(run) is None
    for name in ("mfu.train.moe", "moe_load_max_over_mean", "moe_expert_roofline"):
        assert reader(name)({k: v for k, v in run.items() if k != "moe"}) is None


def _first_batches(loaded, seed):
    m, job = loaded["config"]["model"], loaded["traffic"]
    x, y = train.token_pool(m, job, seed)
    b = job["batch_size"]
    first = [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b]) for i in range(3)]
    return m, job, first


@pytest.mark.parametrize("control", ["fp8", "half_batch", "other_experts"])
def test_a_control_in_the_programs_place_is_not_correct(trained, control):
    """The reference in float8, on half the rows, or holding experts 2-3 in
    place of 0-1, where the program stood: at least one number passes its
    limit (at this size the limits are the test mix's own)."""
    loaded, _ = trained
    m, job, first = _first_batches(loaded, 11)
    kwargs = limits_moe.planted(m, control)
    assert control != "other_experts" or kwargs == dict(held=(2, 2))
    ref = reference_zaya.train_steps(m, 11, first, job["learning_rate"], rows_per_block=2)
    planted = reference_zaya.train_steps(m, 11, first, job["learning_rate"], rows_per_block=2,
                                         **kwargs)
    limits = dict(job["limits"], loss_gap=1e-3, grad_norm_gap=0.05, delta_norm_gap=0.05,
                  route_gap=0.02)
    assert checks.holds(train_moe.moe_checks(ref, ref, limits))
    if control == "half_batch":      # half the rows were routed: the five numbers judge it
        planted["routes"] = ref["routes"]
    assert not checks.holds(train_moe.moe_checks(planted, ref, limits)), control


def test_the_controls_script_prints_a_line_a_control(capsys):
    assert limits_moe.main(["tiny-zaya.tiny-train-moe", "11", "fp8,half_batch"], TINY) == 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [(r["seed"], r["control"]) for r in lines] == [(11, "fp8"), (11, "half_batch")]
    assert lines[0]["route_gap"] > 0 and lines[1]["route_gap"] == 0
    assert lines[1]["loss1_gap"] > 1e-4 and lines[1]["grad_leaf"]


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(monkeypatch):
    from distkeras_tpu.parallel.tensor import SPMDEngine

    def unchanged(self, params, nt, opt_state, batch):
        time.sleep(0.01)        # a step takes time, or the epochs run out
        return params, nt, opt_state, jnp.float32(6.0)

    monkeypatch.setattr(SPMDEngine, "run_step", unchanged)
    loaded = loader.load_cell("tiny-zaya.tiny-train-moe", TINY)
    facts = train_moe.drive(loaded, 3, 0.3, False, jax.devices(), t0=time.perf_counter())
    assert not checks.holds(facts["checks"]), facts["checks"]
    # 1 to within the last bits of weights made twice (1e-5 of Adam's change)
    assert facts["checks"]["delta_norm_gap"]["value"] == pytest.approx(1.0, abs=1e-3)
    assert not np.any(facts["moe"]["window_tokens"])          # and nothing was counted


@pytest.fixture(scope="module")
def recorded():
    """One epoch of two steps of ``tiny-zaya-chip`` (2 layers, 2 heads of 128,
    4 x 256 tokens, 4 experts of which 2 are held) traced on a TPU v5e by
    ``drivers/train_moe.py`` (PR 27), and the facts of that run."""
    with open(os.path.join(HERE, "data", "small_train_moe.facts.json")) as f:
        facts = json.load(f)
    planes = xplane.read_planes(os.path.join(HERE, "data", "small_train_moe.xplane.pb.gz"))
    loaded = loader.load_cell("tiny-zaya-chip.tiny-train-moe-chip", TINY)
    return dict(trace=xplane.reduce(planes, 1, facts["trace_slice_s"]), moe=facts["moe"],
                window=facts["window"], model=loaded["config"]["model"],
                traffic=loaded["traffic"], chips=1,
                peaks=loader.load_peaks("TPU v5 lite")), facts


@pytest.mark.parametrize("name", ["moe_expert_roofline", "flash_roofline.cca", "mfu.train.moe",
                                  "moe_load_max_over_mean"])
def test_each_reader_on_the_recorded_trace(recorded, name):
    run, facts = recorded
    read = loader.load_reader(os.path.join(loader.ROOT, "benchmark", "metrics", name + ".py"))
    value = read(run)
    assert value == pytest.approx(facts[name], rel=1e-9)
    assert 0 < value <= (100 if name != "moe_load_max_over_mean" else 4)


def test_the_recorded_trace_holds_the_grouped_products_and_the_flash_kernels(recorded):
    run, _ = recorded
    steps, depth = run["moe"]["slice_steps"], run["model"]["depth"]
    spent, events = xplane.op_seconds(run["trace"], r"^%?ragged-dot-none")
    assert events == 8 * depth * steps and spent > 0
    q = f"bf16[{4 * run['model']['heads']},256,128]"
    flash = [c for c in xplane.kernel_calls(run["trace"]) if c[1] and c[1][0] == q]
    assert sum(c[3] for c in flash) == 4 * depth * steps      # forward twice, dq, dkv
    # the dense cell's readers find nothing of theirs in it and say so
    dense = loader.load_reader(os.path.join(loader.ROOT, "benchmark", "metrics",
                                            "flash_roofline.cca.py"))
    assert dense(dict(run, model={k: v for k, v in run["model"].items()
                                  if k != "head_dim"})) is None
