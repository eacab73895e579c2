"""The compiled step's operations by the program's own scopes (ISSUE 35):
``observability.programs`` keeps a handle on the step ``SPMDEngine.run_step``
ran, makes the table ``{HLO instruction: (scope path, pass)}`` from it on
demand, and reads an ``op_name`` one way. All on the CPU."""

import gc
import glob
import json
import os
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import loader, parts
from benchmark.drivers import train_bd, train_kanana, train_moe
from benchmark.harness import program_lm
from distkeras_tpu.observability import programs, trace

DATA = os.path.join(loader.ROOT, "benchmark", "tests", "data", "configs")
BLOCKS = {"dense": ("tiny-sincos", program_lm), "zaya": ("tiny-zaya", train_moe.program_lm),
          "sdar": ("tiny-sdar", train_bd.program_lm),
          "mla": ("tiny-kanana", train_kanana.program_lm)}
#: the attention sublayer's component under ``blocks_*``, by block type: what
#: ``benchmark/metrics/attn_outside_flash_ms.train.py`` asks for by name
ATTENTION = {"dense": "blocks_*._attn_full", "zaya": "cca", "sdar": "attn", "mla": "attn"}


def _spec(block, ce_chunk=64, attn_impl="reference"):
    """A two-layer ``transformer_lm`` of one block type at the benchmark's
    tiny test widths: remat, the fused loss, XLA attention unless asked."""
    config, build = BLOCKS[block]
    with open(os.path.join(DATA, config + ".json")) as f:
        m = dict(json.load(f)["model"], depth=2)
    return m, build(m, attn_impl=attn_impl, fused_ce=True, ce_chunk=ce_chunk, remat=True)


def _engine(spec):
    from distkeras_tpu.trainers import MeshTrainer

    trainer = MeshTrainer(spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
                          learning_rate=1e-3, mesh_shape={"dp": 1}, batch_size=2,
                          input_mode="stream", num_epoch=1, seed=3)
    return trainer._build_engine()[0]


def _rows(m, n=2, length=32):
    toks = np.random.default_rng(5).integers(0, m["vocab"] - 1, (n, length + 1)).astype(np.int32)
    return toks[:, :-1], (toks[:, :-1] if m.get("block") == "sdar" else toks[:, 1:])


def _step_once(block, committed=True, length=32, **options):
    """One ``run_step`` of a fresh engine; returns the engine and its state."""
    m, spec = _spec(block, **options)
    engine = _engine(spec)
    p0, nt0 = spec.init_np(3)
    if committed:
        state = engine.init_state(p0, nt0)
        batch = _rows(m, length=length)    # host rows: run_step places them
    else:
        # nothing placed: the arrays go wherever the jit sends them
        engine._resolve_specs(p0)
        engine._build_step()
        p0, nt0 = jax.tree.map(jnp.asarray, (p0, nt0))
        state = (p0, nt0, engine.optimizer.init(p0))
        batch = tuple(jnp.asarray(a) for a in _rows(m, length=length))
        assert not any(a.committed for a in jax.tree.leaves((state, batch)))
    out = engine.run_step(*state, batch)
    jax.block_until_ready(out[3])
    return engine, out


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent cache in a directory of this test's own, every
    program kept; the suite's setting (off) comes back after."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (True, str(tmp_path / "cache"), 0, 0)):
        jax.config.update(n, v)
    compilation_cache.reset_cache()
    yield str(tmp_path / "cache")
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _mark():
    t = time.perf_counter_ns()
    return lambda name: [e for e in trace.run_log() if e["t0_ns"] >= t and e["name"] == name]


# -- the handle and the table -------------------------------------------------


@pytest.mark.parametrize("block, committed", [
    ("dense", True), ("zaya", True), ("sdar", True), ("mla", True), ("dense", False)],
    ids=["dense", "zaya", "sdar", "mla", "dense-uncommitted"])
def test_the_table_is_of_the_step_that_ran(compile_cache, block, committed):
    """The second compile finds the first one's entry in the persistent cache
    and writes no second file: the signature repeats each argument's
    commitment. The table then holds the names the readers ask for."""
    since = _mark()
    engine, _ = _step_once(block, committed)
    files = glob.glob(os.path.join(compile_cache, "jit_train_step-*"))
    assert len(files) == 1, files
    assert not since("program.op_scopes"), "nothing makes the table unasked"
    table = programs.op_scopes("train_step")
    (entry,) = since("program.op_scopes")
    assert entry["args"]["cache"] == "hit", entry
    assert entry["args"]["fun"] == "train_step"
    assert entry["args"]["instructions"] == len(table) > 100
    assert glob.glob(os.path.join(compile_cache, "jit_train_step-*")) == files
    assert programs.op_scopes("train_step") is table and len(since("program.op_scopes")) == 1
    seen = set(table.values())
    paths = {p for p, _ in seen}
    for scope in ("fused_ce_fwd", "fused_ce_bwd", "optimizer", "embed", ATTENTION[block]):
        assert any(scope in p for p in paths), (scope, sorted(paths))
    assert {w for _, w in seen} >= {"forward", "remat", "backward"}
    assert any(p[:1] == ("blocks_*",) and ATTENTION[block] in p for p in paths)
    if block != "dense":
        assert any("moe_route" in p for p in paths)
    # remat's forward holds the attention sublayer and no loss or optimizer
    remat = {p for p, w in seen if w == "remat"}
    assert any(ATTENTION[block] in p for p in remat)
    assert not any(s in p for p in remat for s in ("fused_ce_fwd", "optimizer"))
    del engine


@pytest.mark.parametrize("block", ["dense", "mla"])
def test_remats_forward_holds_no_flash_forward(compile_cache, block):
    """A rematted block keeps the flash forward's two results
    (``ops.REMAT_SAVED``): in the table of a flash step the kernel stands
    under pass ``forward`` alone, while remat's forward still holds the
    attention sublayer's projections, which nothing keeps."""
    engine, _ = _step_once(block, length=128, attn_impl="flash")
    seen = set(programs.op_scopes("train_step").values())
    assert {w for p, w in seen if "flash_fwd" in p} == {"forward"}
    assert {w for p, w in seen if "flash_dq" in p} == {"backward"}
    remat = {p for p, w in seen if w == "remat"}
    projection = {"dense": "qkv", "mla": "q"}[block]
    assert any(ATTENTION[block] in p and projection in p for p in remat), sorted(remat)
    del engine


def test_a_signature_mirrored_the_wrong_way_gives_no_table(compile_cache, capsys):
    """Committed arguments described as uncommitted ones lower to another
    module: the compile misses, a second file appears, and no table is
    given out for a program that did not run."""
    engine, out = _step_once("dense")
    files = glob.glob(os.path.join(compile_cache, "jit_train_step-*"))
    placed = out[:3] + (engine.place_batch(_rows(_spec("dense")[0])),)
    assert all(a.committed for a in jax.tree.leaves(placed))
    loose = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), placed)
    since = _mark()
    programs.note("train_step", engine._step, loose)
    assert programs.op_scopes("train_step") is None
    (entry,) = since("program.op_scopes")
    assert entry["args"]["cache"] == "miss"
    assert "MISSED the persistent cache" in capsys.readouterr().err
    assert len(glob.glob(os.path.join(compile_cache, "jit_train_step-*"))) == len(files) + 1
    # and the right way round again, on the same engine
    programs.note("train_step", engine._step, placed)
    assert programs.op_scopes("train_step") is not None
    assert since("program.op_scopes")[-1]["args"]["cache"] == "hit"


def _live_steps():
    """Fingerprints of the loaded executables of programs named train_step."""
    return {e.fingerprint for e in jax.devices()[0].client.live_executables()
            if "train_step" in e.hlo_modules()[0].name}


def test_the_handle_keeps_no_parameter_and_no_executable_alive():
    gc.collect()
    before = _live_steps()
    engine, out = _step_once("dense", ce_chunk=16)     # a step no other test compiles
    leaf = weakref.ref(jax.tree.leaves(out[0])[0])
    moment = weakref.ref(jax.tree.leaves(out[2])[-1])
    assert len(_live_steps() - before) == 1
    assert engine._step_handle is programs._handles["train_step"]
    del engine, out
    gc.collect()
    assert leaf() is None and moment() is None
    assert not _live_steps() - before, "the handle holds the step's executable on the device"
    assert programs.op_scopes("train_step")      # and still gives the table
    gc.collect()
    assert not _live_steps() - before, "op_scopes let its executable go"


def test_run_step_notes_the_step_once_and_a_new_step_again():
    engine, out = _step_once("dense")
    handle = engine._step_handle
    assert handle is programs._handles["train_step"]
    m = _spec("dense")[0]
    out = engine.run_step(*out[:3], _rows(m))
    assert engine._step_handle is handle
    engine._build_step()                    # a rebuilt step is another program
    assert engine._step_handle is None


def test_the_scopes_are_metadata_and_nothing_else(monkeypatch):
    """``optimizer`` (and every other ``jax.named_scope``) names operations and
    changes none: without its locations the lowered step is the same text."""
    import contextlib

    def lowered():
        m, spec = _spec("dense")
        engine = _engine(spec)
        p0, nt0 = spec.init_np(3)
        state = engine.init_state(p0, nt0)
        return engine._step.lower(*state, engine.place_batch(_rows(m)))

    named = lowered()
    assert "/optimizer/" in named.as_text(debug_info=True)
    assert "optimizer" not in named.as_text()
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = lowered()
    assert "/optimizer/" not in bare.as_text(debug_info=True)
    assert bare.as_text() == named.as_text()


def test_train_lowers_and_compiles_only_the_programs_it_runs(rng):
    """With tracing off the handle costs one cached trace: ``train()`` leaves
    one ``jax.lower`` and one ``jax.compile`` of the step, no table."""
    from distkeras_tpu.models.lm import next_token_dataset, transformer_lm
    from distkeras_tpu.trainers import MeshTrainer

    spec = transformer_lm(vocab=8, maxlen=16, dim=32, heads=4, depth=1, dtype=jnp.float32)
    rows = np.stack([(np.arange(13) + s) % 8 for s in rng.integers(0, 8, 64)]).astype(np.int32)
    trainer = MeshTrainer(spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
                          learning_rate=5e-3, mesh_shape={"dp": 1}, batch_size=16,
                          input_mode="stream", num_epoch=2)
    t = time.perf_counter_ns()
    trainer.train(next_token_dataset(rows))
    log = [e for e in trace.run_log() if e["t0_ns"] >= t]
    for name in ("jax.lower", "jax.compile"):
        steps = [e for e in log if e["name"] == name and e["args"]["fun"] == "jit(train_step)"]
        assert len(steps) == 1, (name, steps)
    assert len([e for e in log if e["name"] == "jax.trace"
                and e["args"]["fun"] == "train_step"]) <= 1
    assert not [e for e in log if e["name"] == "program.op_scopes"]


def test_profile_dir_leaves_the_table_beside_the_trace(rng, tmp_path):
    """An operator's use: ``MeshTrainer(profile_dir=)`` saves the step's table
    into the profile directory, in the form ``benchmark/parts.py`` reads."""
    from distkeras_tpu.models.lm import next_token_dataset, transformer_lm
    from distkeras_tpu.trainers import MeshTrainer

    spec = transformer_lm(vocab=8, maxlen=16, dim=32, heads=4, depth=2, dtype=jnp.float32,
                          remat=True)
    rows = np.stack([(np.arange(13) + s) % 8 for s in rng.integers(0, 8, 32)]).astype(np.int32)
    since = _mark()
    MeshTrainer(spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
                learning_rate=5e-3, mesh_shape={"dp": 1}, batch_size=16, input_mode="stream",
                num_epoch=1, profile_dir=str(tmp_path)).train(next_token_dataset(rows))
    path = tmp_path / "op_scopes.train_step.json"
    assert path.exists() and glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    (finish,) = since("train.finish")
    (made,) = since("program.op_scopes")
    assert finish["t0_ns"] <= made["t0_ns"] and (
        made["t0_ns"] + made["dur_ns"] <= finish["t0_ns"] + finish["dur_ns"])
    table = parts.load_table(str(path))
    assert table == programs.op_scopes("train_step")
    assert (("optimizer",), "forward") in set(table.values())
    assert {w for p, w in table.values() if p[:1] == ("blocks_*",)} == {
        "forward", "remat", "backward"}


# -- one reading of an op_name ------------------------------------------------

HID = "TransformerLM.hidden"
RH = "TransformerLM._routed_hidden"
BWD = f"jit(train_step)/transpose(jvp({HID}))/{RH}/jvp({HID})/{RH}/checkpoint"
OP_NAMES = [
    # the three passes of one module, as this JAX spells them
    (f"jit(train_step)/jvp({HID})/{RH}/blocks_3/blocks_3.attend/attn/mla_latent/mul",
     ("blocks_*", "blocks_*.attend", "attn", "mla_latent"), "forward"),
    (f"{BWD}/rematted_computation/blocks_3/blocks_3.attend/attn/mla_latent/mul",
     ("blocks_*", "blocks_*.attend", "attn", "mla_latent"), "remat"),
    (f"{BWD}/blocks_3/blocks_3.attend/attn/mla_latent/kv_b/transpose",
     ("blocks_*", "blocks_*.attend", "attn", "mla_latent", "kv_b"), "backward"),
    # ISSUE 35's own example
    ("jit(train_step)/jit(main)/transpose(jvp(TransformerLM))/checkpoint/"
     "rematted_computation/blocks_3/attn/mla_latent/mul",
     ("blocks_*", "attn", "mla_latent"), "remat"),
    # a scope INSIDE a wrapper's brackets is path
    ("jit(train_step)/jvp(attn)/dot_general", ("attn",), "forward"),
    (f"{BWD}/blocks_1/moe/transpose(jvp(moe_route))/jit(_where)/select_n",
     ("blocks_*", "moe", "moe_route"), "backward"),
    (f"{BWD}/rematted_computation/blocks_1/moe/while/body/jvp(moe_experts)/jit(silu)/logistic",
     ("blocks_*", "moe", "moe_experts"), "remat"),
    # the dense block: its attention is a method of the block
    (f"jit(train_step)/jvp({HID})/blocks_0/blocks_0._attn_full/blocks_0._project_qkv/qkv/"
     "dot_general", ("blocks_*", "blocks_*._attn_full", "blocks_*._project_qkv", "qkv"),
     "forward"),
    # a kernel: the launcher's jit goes, the kernel's name stays
    (f"{BWD}/blocks_11/blocks_11.attend/cca/jit(_bwd_call)/flash_dkv/pallas_call",
     ("blocks_*", "blocks_*.attend", "cca", "flash_dkv"), "backward"),
    # the loss's loops, under an empty wrapper
    ("jit(train_step)/jvp()/while/body/closed_call/fused_ce_fwd/jit(take_along_axis)/gather",
     ("fused_ce_fwd",), "forward"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/fused_ce_bwd/dot_general",
     ("fused_ce_bwd",), "backward"),
    (f"jit(train_step)/jvp({HID})/TransformerLM._embed_at/embed/jit(_take)/gather",
     ("embed",), "forward"),
    ("jit(train_step)/optimizer/add", ("optimizer",), "forward"),
    # under no scope at all
    ("jit(train_step)/mul", (), "forward"),
    ("jit(train_step)/transpose(jvp())/reshape;jit(train_step)/transpose(jvp())/transpose",
     (), "backward"),
    # the compiler's own names, and an argument's: no path of the program's, no pass
    ("ragged-dot-none", ("ragged-dot-none",), ""),
    ("params['blocks_7']['moe']['router']['kernel']",
     ("params['blocks_*']['moe']['router']['kernel']",), ""),
]


@pytest.mark.parametrize("op_name, path, which", OP_NAMES,
                         ids=[f"{i}-{w or 'none'}" for i, (_, _, w) in enumerate(OP_NAMES)])
def test_part_of_reads_an_op_name_one_way(op_name, path, which):
    assert programs.part_of(op_name) == (path, which)


# -- the rule for a fusion, on a small fixed text -----------------------------

HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[8,8], param_1.1: bf16[8,8]) -> f32[8,8] {
  %param_0.1 = bf16[8,8]{1,0} parameter(0)
  %param_1.1 = bf16[8,8]{1,0} parameter(1)
  %convert.1 = f32[8,8]{1,0} convert(%param_0.1), metadata={op_name="jit(train_step)/jvp(M.hidden)/blocks_0/moe/moe_route/convert_element_type"}
  %convolution.1 = f32[8,8]{1,0} convolution(%convert.1, %param_1.1), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/jvp(M.hidden)/blocks_0/attn/q/dot_general"}
  %multiply.1 = f32[8,8]{1,0} multiply(%convolution.1, %convolution.1), metadata={op_name="jit(train_step)/jvp(M.hidden)/blocks_0/moe/moe_route/mul"}
  ROOT %add.1 = f32[8,8]{1,0} add(%multiply.1, %convert.1), metadata={op_name="jit(train_step)/jvp(M.hidden)/blocks_0/moe/moe_route/add"}
}

%fused_computation.2 (param_0.2: f32[8,8]) -> f32[8,8] {
  %param_0.2 = f32[8,8]{1,0} parameter(0)
  %exp.2 = f32[8,8]{1,0} exponential(%param_0.2), metadata={op_name="jit(train_step)/transpose(jvp(M.hidden))/jvp(M.hidden)/checkpoint/rematted_computation/blocks_1/moe/moe_route/exp"}
  %neg.2 = f32[8,8]{1,0} negate(%exp.2), metadata={op_name="jit(train_step)/transpose(jvp(M.hidden))/jvp(M.hidden)/checkpoint/rematted_computation/blocks_1/moe/moe_route/neg"}
  ROOT %tanh.2 = f32[8,8]{1,0} tanh(%neg.2), metadata={op_name="jit(train_step)/transpose(jvp(M.hidden))/jvp(M.hidden)/checkpoint/rematted_computation/blocks_1/moe/ln/tanh"}
}

%bitcast_fusion (param_0.3: f32[8,8]) -> f32[64] {
  %param_0.3 = f32[8,8]{1,0} parameter(0)
  ROOT %bitcast.3 = f32[64]{0} bitcast(%param_0.3)
}

%region_body.4 (arg.4: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg.4 = (s32[], f32[8,8]{1,0}) parameter(0)
  %get-tuple-element.4 = f32[8,8]{1,0} get-tuple-element(%arg.4), index=1
  %fusion.4 = f32[8,8]{1,0} fusion(%get-tuple-element.4), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/fused_ce_fwd/mul"}
  %logistic.4 = f32[8,8]{1,0} logistic(%fusion.4), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/fused_ce_fwd/logistic"}
  ROOT %tuple.4 = (s32[], f32[8,8]{1,0}) tuple(%get-tuple-element.4, %logistic.4)
}

ENTRY %main.9 (Arg_0.1: bf16[8,8], Arg_1.2: bf16[8,8]) -> f32[64] {
  %Arg_0.1 = bf16[8,8]{1,0} parameter(0), metadata={op_name="params['blocks_0']['attn']['q']['kernel']"}
  %Arg_1.2 = bf16[8,8]{1,0} parameter(1), metadata={op_name="batch[0]"}
  %convolution_add_fusion.7 = f32[8,8]{1,0} fusion(%Arg_0.1, %Arg_1.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(M.hidden)/blocks_0/moe/moe_route/add"}
  %fusion.8 = f32[8,8]{1,0} fusion(%convolution_add_fusion.7), kind=kLoop, calls=%fused_computation.2
  %while.5 = (s32[], f32[8,8]{1,0}) while(%tuple.0), condition=%region_cond.4, body=%region_body.4, metadata={op_name="jit(train_step)/jvp()/while"}
  %ragged-dot-none.15 = f32[8,8]{1,0} custom-call(%fusion.8), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %copy-start.2 = (f32[8,8]{1,0}, f32[8,8]{1,0}, u32[]) copy-start(%fusion.8)
  ROOT %fusion.9 = f32[64]{0} fusion(%fusion.8), kind=kLoop, calls=%bitcast_fusion
}
"""
RULES = [
    # a product with its prologue and epilogue is the product's, whatever the rest says
    ("convolution_add_fusion.7", ("blocks_*", "attn", "q"), "forward"),
    # no product: the part most of its instructions name (2 of moe_route, 1 of ln)
    ("fusion.8", ("blocks_*", "moe", "moe_route"), "remat"),
    # the same computation called from a while body: its own name votes too, and loses
    ("fusion.4", ("blocks_*", "moe", "moe_route"), "remat"),
    # a while body's plain instruction is in the table under its own name
    ("logistic.4", ("fused_ce_fwd",), "forward"),
    ("while.5", (), "forward"),
    # an instruction inside a fused computation
    ("multiply.1", ("blocks_*", "moe", "moe_route"), "forward"),
    # what the compiler named itself, and what it gave no name at all
    ("ragged-dot-none.15", ("ragged-dot-none",), ""),
    ("copy-start.2", (), ""),
    ("fusion.9", (), ""),
    ("Arg_1.2", ("batch[0]",), ""),
]


@pytest.mark.parametrize("name, path, which", RULES, ids=[r[0] for r in RULES])
def test_parse_hlo_gives_every_instruction_its_part(name, path, which):
    table = programs.parse_hlo(HLO)
    assert table[name] == (path, which)
    assert len(table) == 25 and programs.NO_NAME == ((), "")
