"""The seam between the package and ``benchmark/``, held on the CPU.

``benchmark/`` is what the driver measures, and only a ``benchmark`` PR may
edit it. It stands on package internals: the parameter tree by name, the shape
of ``MeshTrainer._build_engine()`` and ``engine.run_step``, the names of
programs and scopes, the keys of ``GenerationEngine.stats()``. A refactor that
moves one of them would otherwise be found on the chip, after the session that
could have mended it. Every failure here names the file under ``benchmark/``
that depends on what changed; nothing under ``benchmark/`` is edited.
"""

import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (checks, harness, loader, weights, weights_kanana, weights_sdar,
                       weights_zaya)
from benchmark.drivers import serve, train, train_bd, train_kanana, train_moe

DATA = os.path.join(loader.ROOT, "benchmark", "tests", "data")
DRIVERS = {"train": train, "train_moe": train_moe, "train_bd": train_bd,
           "train_kanana": train_kanana, "serve": serve}


def _json(*parts):
    with open(os.path.join(loader.ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def _leaves(tree) -> dict:
    return {jax.tree_util.keystr(path): (tuple(leaf.shape), str(leaf.dtype))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


# -- (a) the parameter tree, by name --------------------------------------------


@pytest.mark.parametrize("config, traffic", [
    ("xglm-564m", "train"), ("starcoder2-3b", "serve.closed"), ("zaya1-8b", "train.moe4k"),
    ("sdar-30b-a3b", "train.bd4k"), ("kanana-2-30b-a3b", "train.mla8k")])
def test_the_model_initialises_to_the_tree_the_benchmark_makes_by_name(config, traffic):
    """The committed configuration at full size under the options of the job
    file that runs it; ``jax.eval_shape`` on both sides, so nothing is made."""
    m, job = _json("configs", config + ".json")["model"], _json("traffic", traffic + ".json")
    options = {k: job[k] for k in ("attn_impl", "fused_ce", "ce_chunk", "remat") if k in job}
    routed = {"zaya": (train_moe, weights_zaya), "sdar": (train_bd, weights_sdar),
              "mla": (train_kanana, weights_kanana)}.get(m.get("block"))
    spec = (routed[0].program_lm if routed else harness.program_lm)(m, **options)
    got = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    key = jax.eval_shape(lambda: weights.seed_key(2 ** 31 + 29))
    if routed:
        made, by = jax.eval_shape(lambda k: (routed[1].program_tree(m, k),
                                             routed[1].counters_tree(m, k)),
                                  key), f"benchmark/{routed[1].__name__.split('.')[-1]}.py"
    else:
        made, by = jax.eval_shape(lambda k: (weights.program_tree(m, k, "float32"), {}),
                                  key), "benchmark/weights.py"
    got, made = _leaves(got), _leaves(made)
    differ = sorted(set(got) ^ set(made)) + [p for p in sorted(set(got) & set(made))
                                              if got[p] != made[p]]
    assert not differ, (
        f"{by} makes {config}'s parameters and state by name, and the program's "
        f"transformer_lm no longer initialises to the same paths, shapes and dtypes; "
        f"first differences: {[(p, got.get(p), made.get(p)) for p in differ[:4]]}")
    if "served_dtype" in job:       # the serve driver's weights: the same tree in bfloat16
        served = _leaves(jax.eval_shape(
            lambda k: weights.program_tree(m, k, job["served_dtype"]), key))
        assert {p: shape for p, (shape, _) in served.items()} == {
            p[len("[0]"):]: shape for p, (shape, _) in got.items() if p.startswith("[0]")}, (
            "benchmark/drivers/serve.py hands GenerationEngine weights.program_tree's leaves")


# -- (b) the engine the train drivers wrap ---------------------------------------


def test_build_engine_and_run_step_keep_the_shape_the_probes_assume():
    """``ProbedMeshTrainer`` (``benchmark/drivers/train.py``) overrides
    ``_build_engine``, wraps ``engine.run_step(params, nt, opt_state, batch)``
    and reads the new parameters at ``out[0]`` and Adam's state at ``out[2]``."""
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.trainers import MeshTrainer

    why = "benchmark/drivers/train.py's ProbedMeshTrainer and StateProbes assume it"
    spec = transformer_lm(vocab=64, maxlen=16, dim=32, heads=2, depth=1, dtype=jnp.float32)
    trainer = MeshTrainer(spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
                          learning_rate=1e-2, mesh_shape={"dp": 1}, batch_size=2,
                          input_mode="stream", num_epoch=1, seed=3)
    built = trainer._build_engine()
    assert isinstance(built, tuple) and len(built) == 3, f"_build_engine() -> 3 parts: {why}"
    engine, to_engine, from_engine = built
    assert callable(to_engine) and callable(from_engine), why
    p0, nt0 = spec.init_np(3)
    params, nt, opt_state = engine.init_state(to_engine(p0), nt0)
    rows = np.random.default_rng(3).integers(0, 64, (2, 17)).astype(np.int32)
    out = engine.run_step(params, nt, opt_state, (rows[:, :-1], rows[:, 1:]))
    assert len(out) >= 3, f"run_step returns (params, nt, opt_state, ...): {why}"
    assert jax.tree.structure(out[0]) == jax.tree.structure(p0), f"out[0] is the parameters: {why}"
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), out[0], p0)
    assert max(jax.tree.leaves(moved)) > 0, f"out[0] is the NEW parameters: {why}"
    first_moments = [s.mu for s in jax.tree.leaves(out[2], is_leaf=lambda s: hasattr(s, "mu"))
                     if hasattr(s, "mu")]
    assert first_moments, f"out[2] is the optimiser's state and holds Adam's mu: {why}"
    assert jax.tree.structure(first_moments[0]) == jax.tree.structure(p0), why
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(first_moments[0])), \
        f"out[2] is the NEW optimiser state: {why}"


# -- (c) the drivers at the benchmark's own tiny data, (d) the names they leave ----


def _program(directory, name) -> str:
    found = sorted(glob.glob(os.path.join(str(directory), f"*_{name}_compile.mlir")))
    if not found:
        return ""
    with open(found[-1]) as f:
        return f.read()


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """One window of each tiny cell, made when first asked for; every program
    JAX lowers meanwhile goes to the run's directory as text, locations and all."""
    cells = {"tiny-train": ("tiny-sincos.tiny-train", "BENCHMARK.json", 1.0),
             "tiny-train-moe": ("tiny-zaya.tiny-train-moe", "BENCHMARK.zaya.json", 1.0),
             "tiny-train-bd": ("tiny-sdar.tiny-train-bd", "BENCHMARK.sdar.json", 1.0),
             "tiny-train-mla": ("tiny-kanana.tiny-train-mla", "BENCHMARK.kanana.json", 1.0),
             "tiny-serve": ("tiny-rope-gqa.tiny-serve", "BENCHMARK.json", 2.0)}
    made = {}

    def run(traffic):
        if traffic not in made:
            cell, bench_file, seconds = cells[traffic]
            loaded = loader.load_cell(cell, os.path.join(DATA, bench_file))
            directory = tmp_path_factory.mktemp(traffic)
            jax.config.update("jax_dump_ir_to", str(directory))
            try:
                facts = DRIVERS[loaded["traffic"]["driver"]].drive(
                    loaded, 2 ** 31 + 29, seconds, False, jax.devices(), t0=time.perf_counter())
            finally:
                jax.config.update("jax_dump_ir_to", None)
            made[traffic] = loaded, facts, directory
        return made[traffic]

    return run


@pytest.mark.parametrize("traffic", ["tiny-train", "tiny-train-moe", "tiny-train-bd",
                                     "tiny-train-mla"])
def test_the_train_drivers_run_their_window_and_report_correct(runs, traffic):
    loaded, facts, _ = runs(traffic)
    job = loaded["traffic"]
    where = f"benchmark/drivers/{job['driver']}.py"
    assert checks.holds(facts["checks"]), f"{where} is not correct: {facts['checks']}"
    assert facts["failed"] == 0 and facts["compiles_in_window"] == 0, where
    assert facts["window"]["steps"] > 0 and facts["window"]["tokens"] == (
        facts["window"]["steps"] * job["batch_size"] * job["seq_len"]), where
    assert facts["attempted"] == job["warmup_steps"] + facts["window"]["steps"], where
    assert facts["end_to_end"]["train_tokens_per_s"] > 0 < facts["end_to_end"]["setup_s"], where
    if job["driver"] in ("train_moe", "train_bd", "train_kanana"):
        m = loaded["config"]["model"]
        routes = "route_gap" if job["driver"] == "train_moe" else "route_count_gap"
        assert routes in facts["checks"], where
        # every routed (token, layer) pair of the window is in the fetched
        # counters; under block diffusion a clean token is two positions of
        # its stream and a position sends experts_per_token pairs; a model
        # with leading dense layers counts in its expert layers only
        pairs = {"train_moe": 1, "train_bd": 2 * m.get("experts_per_token", 1),
                 "train_kanana": m.get("experts_per_token", 1)}[job["driver"]]
        layers = m["depth"] - m.get("dense_layers", 0)
        assert np.sum(facts["moe"]["window_tokens"]) == (
            facts["window"]["tokens"] * layers * pairs), (
            f"{where} reads MeshTrainer's history 'counters' through models.lm.moe_tokens")
    if job["driver"] == "train_kanana":
        assert {"bias_gap", "expert_grad_gap"} <= set(facts["checks"]), where
        assert np.asarray(facts["moe"]["window_tokens"]).shape == (layers, m["experts"]), (
            f"{where} reads the EXPERT layers' counters: layer 0 is dense and has none")
    if job["driver"] == "train_bd":
        assert 0 < facts["bd"]["window_masked"] < facts["window"]["tokens"], (
            f"{where} reads the history's 'bd_masked_tokens' counter")


def test_the_serve_driver_runs_its_window_and_reports_correct(runs):
    loaded, facts, _ = runs("tiny-serve")
    where = "benchmark/drivers/serve.py"
    assert checks.holds(facts["checks"]), f"{where} is not correct: {facts['checks']}"
    assert facts["failed"] == 0 and facts["compiles_in_window"] == 0, where
    assert facts["window"]["requests_done"] > 0 < facts["window"]["output_tokens"], where
    assert facts["end_to_end"]["serve_tokens_per_s"] > 0 < facts["end_to_end"]["request_p95_ms"]
    assert set(facts["checks"]) == {"served_token_gap", "served_gap_mean", "short_replies"}


# the names somebody reading a trace of a cell searches for, with the program
# whose lowered text has to hold them (jit_train_step, flash_fwd/dq/dkv and
# fused_ce_bwd are pinned by tests/test_tpu_compile.py, test_chip_smoke.py and
# test_fused_ce.py)
NAMES = [("jit_serve_decode_greedy", "tiny-serve", "serve_decode_greedy"),
         ("fused_ce_fwd", "tiny-train", "train_step"),
         ("moe_experts", "tiny-train-moe", "train_step"),
         ("moe_route", "tiny-train-moe", "train_step"),
         ("moe_balance", "tiny-train-moe", "train_step"),
         ("cca_conv", "tiny-train-moe", "train_step"),
         ("bd_noise", "tiny-train-bd", "train_step"),
         ("moe_route", "tiny-train-bd", "train_step"),
         ("mla_latent", "tiny-train-mla", "train_step"),
         ("moe_shared", "tiny-train-mla", "train_step"),
         ("moe_bias", "tiny-train-mla", "train_step"),
         ("moe_experts", "tiny-train-mla", "train_step")]
# what benchmark/parts.py's readers ask the program's table of its step for
# (observability.programs.op_scopes), by block type: the loss's two scopes, the
# scope round the optimizer, the attention sublayer's component under a block
# (a method of the dense block, a module of the others), remat's marker
TRAIN_CELLS = {"tiny-train": "blocks_0._attn_full/", "tiny-train-moe": "blocks_0.attend/cca/",
               "tiny-train-bd": "blocks_0.attend/attn/", "tiny-train-mla": "blocks_0.attend/attn/"}
PARTS = {"fused_ce_fwd": "loss_ms.train", "fused_ce_bwd": "loss_ms.train",
         "/optimizer/": "step_scoped_pct.train", "rematted_computation": "remat_forward_ms.train",
         "moe_route": "moe_route_ms.train"}
NAMES += [(name, traffic, "train_step") for traffic, attention in TRAIN_CELLS.items()
          for name in (*PARTS, attention)
          if (name, traffic, "train_step") not in NAMES
          and not (name == "moe_route" and traffic == "tiny-train")]
READ_BY = {"jit_serve_decode_greedy": "benchmark/metrics/decode_roofline.py finds the decode "
                                      "program by name"}
READ_BY.update({name: f"benchmark/metrics/{metric}.py finds the step's operations by this "
                      f"component of their op_name (benchmark/parts.py)"
                for name, metric in PARTS.items()})
READ_BY.update({attention: "benchmark/metrics/attn_outside_flash_ms.train.py finds the attention "
                           "sublayer by this component (parts.ATTENTION)"
                for attention in TRAIN_CELLS.values()})
BY_STEM = ("benchmark/spans.py sums a trace's kernels by name stem, and PERF.md section 5 maps "
           "them to this scope through the step's text")


@pytest.mark.parametrize("name, traffic, program", NAMES,
                         ids=[f"{n[0]}-{n[1]}" for n in NAMES])
def test_a_name_the_readers_search_for_is_in_the_lowered_program(runs, name, traffic, program):
    _, _, directory = runs(traffic)
    text = _program(directory, "jit_" + program)
    assert text, (f"the {traffic} run lowered no program named jit_{program}: "
                  f"benchmark/spans.py and benchmark/xplane.py find programs by that name")
    assert name in text, (f"{name!r} is not in jit_{program}'s lowered text; "
                          f"{READ_BY.get(name, BY_STEM)}")


# -- (e) the engine's counters the serving readers read ---------------------------


@pytest.fixture(scope="module")
def engine():
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.serving import GenerationEngine

    spec = transformer_lm(vocab=64, maxlen=64, dim=32, heads=4, depth=1, dtype=jnp.float32,
                          pos_embedding="rope", kv_heads=2)
    params, _ = spec.init_np(0)
    eng = GenerationEngine(spec, params, max_batch=2, block_size=8, prefill_chunk=8)
    reqs = [eng.submit(np.arange(5 + 7 * i, dtype=np.int32) % 64, max_new_tokens=3)
            for i in range(3)]
    eng.run_until_idle()
    assert all(r.result(timeout=1.0).shape == (3,) for r in reqs)
    return eng


def test_stats_has_every_key_the_serving_readers_read(engine):
    s = engine.stats()
    read_by = {
        "steps": "benchmark/metrics/batch_occupancy.py, decode_roofline.py",
        "occupancy_sum": "benchmark/metrics/batch_occupancy.py, decode_roofline.py",
        "chunk_rows": "benchmark/metrics/prefill_padding_pct.py",
        "chunk_rows_padded": "benchmark/metrics/prefill_padding_pct.py",
        "programs_built": "PERF.md section 3's serve counters (benchmark/drivers/serve.py "
                          "keeps stats() at the window's two ends)",
        "prefills": "benchmark/drivers/serve.py starts its clients on it",
        "active": "benchmark/drivers/serve.py opens its window on it",
    }
    missing = {k: v for k, v in read_by.items() if k not in s}
    assert not missing, f"GenerationEngine.stats() lost keys that are read: {missing}"
    assert s["steps"] > 0 and s["prefills"] == 3 and s["active"] == 0
    assert 0 < s["occupancy_sum"] <= s["steps"] * engine.max_batch
    assert 0 < s["chunk_rows"] <= s["chunk_rows_padded"] and s["programs_built"] >= 2


def test_latency_stats_has_what_queue_mean_ms_reads(engine):
    latency = engine.latency_stats(window_s=60.0)
    assert latency, "benchmark/drivers/serve.py:193 reads latency_stats(window_s=...)"
    for name, c in latency.items():
        assert {"count", "queue_ms"} <= set(c), (
            f"class {name!r}: benchmark/metrics/queue_mean_ms.py reads count and queue_ms")
        assert c["p99_ms"] >= c["p50_ms"] >= c["queue_ms"] >= 0
    assert sum(c["count"] for c in latency.values()) == 3
    run = {"counters": {"latency": latency}, "end_to_end": {"request_p95_ms": 1.0}}
    metrics = os.path.join(loader.ROOT, "benchmark", "metrics")
    assert loader.load_reader(os.path.join(metrics, "queue_mean_ms.py"))(run) >= 0
    assert loader.load_reader(os.path.join(metrics, "request_p95_ms.py"))(run) == 1.0
