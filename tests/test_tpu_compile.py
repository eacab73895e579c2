"""Compiles for a DESCRIBED TPU v5e — the only file that describes the chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached; it refuses what the chip would refuse (a kernel over
the scoped-VMEM limit, a block that breaks the tiling rule, a program that
does not fit 16 GB) where interpret mode accepts everything. These are the
kernels of the main path at the shapes their callers use, a second or two
each, and the whole config-9 LM train step — so a later PR that breaks one
finds out here, at no chip time. Nothing runs: results are chip_smoke.py's
business.

The topology is described inside a module-scoped fixture and nowhere else:
only one process may load the TPU's library, every xdist worker imports every
test file, and a file that touched the library at import would leave the
workers with different tests to collect. The persistent compile cache is off
for the whole suite (conftest.py): these executables cannot be read back
without the chip.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# -- the kernels, one compile each -------------------------------------------


def _flash(shape, *, kv_heads=None, dtype=BF16, causal=True, window=None,
           masked=False, backward=True, block_diffusion=None, v_dim=None):
    """(fn, argument shapes) for flash attention at q ``shape``; ``v_dim``:
    the values' width where it is not q's and k's."""
    from distkeras_tpu.ops.flash_attention import flash_attention

    B, L, H, D = shape
    kv = (B, L, kv_heads or H, D)
    args = [(shape, dtype), (kv, dtype), (kv[:3] + (v_dim or D,), dtype)]
    if masked:
        args.append(((B, L), F32))

    def fwd(q, k, v, mask=None):
        return flash_attention(q, k, v, causal=causal, key_mask=mask,
                               window=window, interpret=False,
                               block_diffusion=block_diffusion)

    if not backward:
        return fwd, args

    def fwd_bwd(q, k, v, mask=None):
        loss = lambda q, k, v: jnp.sum(fwd(q, k, v, mask).astype(F32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return fwd_bwd, args


def _qk_prep(B, S, heads, D=128):
    """(fn, argument shapes): ``qk_prep`` and its gradients at a projection's
    result ``[B, S, heads·D]``, rows at ``0 .. S/2 - 1`` twice."""
    from distkeras_tpu.models.lm import rope_angles_at
    from distkeras_tpu.ops.qk_prep import qk_prep

    row = np.arange(S // 2)
    angles = rope_angles_at(np.concatenate([row, row]), D, 1e6)

    def fwd_bwd(x, w, g):
        out, vjp = jax.vjp(lambda x, w: qk_prep(
            x, w, angles, heads=heads, eps=1e-6, interpret=False), x, w)
        return (out,) + vjp(g)

    return fwd_bwd, [((B, S, heads * D), BF16), ((D,), F32),
                     ((B * heads, S, D), BF16)]


def _mla_prep(B, S, heads, nope=128, rope=64, v=128):
    """(fn, argument shapes): ``mla_prep`` and its gradients at a latent
    sublayer's projections' results."""
    from distkeras_tpu.models.lm import rope_angles
    from distkeras_tpu.ops.mla_prep import mla_prep

    angles = rope_angles(S, rope, 1e6)

    def fwd_bwd(q, kv, k_rope, *g):
        out, vjp = jax.vjp(lambda *x: mla_prep(
            *x, angles, heads=heads, nope=nope, interpret=False),
            q, kv, k_rope)
        return out + vjp(g)

    return fwd_bwd, [((B, S, heads * (nope + rope)), BF16),
                     ((B, S, heads * (nope + v)), BF16), ((B, S, rope), BF16),
                     ((B * heads, S, nope + rope), BF16),
                     ((B * heads, S, nope + rope), BF16),
                     ((B * heads, S, v), BF16)]


def _gated_delta(B, T, Hk, Hv, d=128):
    """(fn, argument shapes): the gated delta rule with its forward in the
    ``gated_delta_fwd`` kernel and its five gradients (the backward XLA's)."""
    from distkeras_tpu.ops.gated_delta import gated_delta_kernel

    def fwd_bwd(q, k, v, g, beta, do):
        out, vjp = jax.vjp(lambda *x: gated_delta_kernel(*x, interpret=False),
                           q, k, v, g, beta)
        return (out,) + vjp(do)

    return fwd_bwd, [((B, T, Hk, d), BF16), ((B, T, Hk, d), BF16),
                     ((B, T, Hv, d), BF16), ((B, T, Hv), F32), ((B, T, Hv), F32),
                     ((B, T, Hv, d), BF16)]


def _lstm(B, T, H, workers=None):
    from distkeras_tpu.ops.recurrent import lstm_scan

    def fwd_bwd(gx, wh):
        loss = lambda gx, wh: jnp.sum(lstm_scan(
            gx, wh, impl="pallas", interpret=False).astype(F32))
        return jax.grad(loss, argnums=(0, 1))(gx, wh)

    args = [((B, T, 4 * H), BF16), ((H, 4 * H), F32)]
    if workers is None:
        return fwd_bwd, args
    return jax.vmap(fwd_bwd), [((workers,) + s, d) for s, d in args]


def _adam(shape):
    from distkeras_tpu.ops.pallas_kernels import fused_adam

    tx = fused_adam(1e-3, interpret=False)

    def update(g, p):
        return tx.update({"w": g}, tx.init({"w": p}))

    return update, [(shape, F32), (shape, F32)]


def _q_matmul(m, k, n, dtype=BF16):
    from distkeras_tpu.ops.quant import QTensor, q_matmul

    def fn(x, q, s):
        return q_matmul(x, QTensor(q, s), impl="pallas", interpret=False)

    return fn, [((m, k), dtype), ((k, n), I8), ((n,), F32)]


KERNELS = {
    # flash attention: the config-9 training shape, then each variant a
    # model in the repo calls it with
    "flash-fwd-causal-bf16-8x2048x8x128":
        lambda: _flash((8, 2048, 8, 128), backward=False),
    "flash-fwdbwd-causal-bf16-8x2048x8x128":
        lambda: _flash((8, 2048, 8, 128)),
    # the two benchmark cells' own calls: xglm-564m.train (16 heads of 64)
    # and zaya1-8b.train (8 query heads over 2 key-value heads of 128)
    "flash-fwd-causal-bf16-8x2048x16x64":
        lambda: _flash((8, 2048, 16, 64), backward=False),
    "flash-fwdbwd-causal-bf16-8x2048x16x64":
        lambda: _flash((8, 2048, 16, 64)),
    "flash-fwd-causal-gqa-kv2-8x4096x8x128":
        lambda: _flash((8, 4096, 8, 128), kv_heads=2, backward=False),
    "flash-fwdbwd-causal-gqa-kv2-8x4096x8x128":
        lambda: _flash((8, 4096, 8, 128), kv_heads=2),
    # sdar-30b-a3b.train's call: a noised and a clean copy of 4 rows of 4096
    # under the block-diffusion mask, 32 query heads over 4 key-value heads
    "flash-fwdbwd-blockdiffusion4-gqa-kv4-4x8192x32x128":
        lambda: _flash((4, 8192, 32, 128), kv_heads=4, causal=False,
                       block_diffusion=4),
    # kanana-2-30b-a3b.train's call: latent attention's q and k 192 wide
    # (128 with no position + 64 rotary), values 128, 32 heads, causal
    "flash-fwdbwd-causal-qk192-v128-4x8192x32":
        lambda: _flash((4, 8192, 32, 192), v_dim=128),
    # qwen3-next-80b-a3b.train's call: 16 query heads of 256 over 2
    # key-value heads, causal: the widest head any cell runs
    "flash-fwdbwd-causal-gqa-kv2-8x4096x16x256":
        lambda: _flash((8, 4096, 16, 256), kv_heads=2),
    # and with a key mask, at tiles of 128 x 384 (rows of 384)
    "flash-fwdbwd-blockdiffusion8-keymask-2x768x4x128":
        lambda: _flash((2, 768, 4, 128), causal=False, masked=True,
                       block_diffusion=8),
    # the heaviest body of the 2048-key ladder: the forward's 512 x 2048
    # float32 tile under the band AND a key mask, at head 128
    "flash-fwdbwd-causal-keymask-8x2048x8x128":
        lambda: _flash((8, 2048, 8, 128), masked=True),
    "flash-fwdbwd-mqa-kv1": lambda: _flash((8, 2048, 8, 128), kv_heads=1),
    "flash-fwdbwd-window512": lambda: _flash((8, 2048, 8, 128), window=512),
    "flash-fwdbwd-keymask-noncausal-d64":
        lambda: _flash((8, 2048, 8, 64), causal=False, masked=True),
    "flash-prefill-mqa-8x128x16x128":
        lambda: _flash((8, 128, 16, 128), kv_heads=1, backward=False),
    "flash-fwdbwd-f32-L16384":
        lambda: _flash((1, 16384, 8, 64), dtype=F32),
    # sdar-30b-a3b.train's q and k on their way to that call: [4, 8192, 4096]
    # to [128, 8192, 128] and [4, 8192, 512] to [16, 8192, 128], and back
    "qk_prep-fwdbwd-q-4x8192x32x128": lambda: _qk_prep(4, 8192, 32),
    "qk_prep-fwdbwd-k-4x8192x4x128": lambda: _qk_prep(4, 8192, 4),
    # kanana-2-30b-a3b.train's q, k and v on their way to its call: [4, 8192,
    # 6144], [4, 8192, 8192] and [4, 8192, 64] to [128, 8192, 192] twice and
    # [128, 8192, 128], and back; and one group of 6 heads at rows of 384
    "mla_prep-fwdbwd-4x8192x32x128+64x128": lambda: _mla_prep(4, 8192, 32),
    "mla_prep-fwdbwd-2x384x6x256+64x128":
        lambda: _mla_prep(2, 384, 6, nope=256),
    # qwen3-next-80b-a3b.train's gated delta rule: 8 rows of 4096 tokens,
    # 16 key heads over 32 value heads of 128, forward and backward
    "gated_delta-fwdbwd-8x4096x16x32x128": lambda: _gated_delta(8, 4096, 16, 32),
    # fused LSTM scan: the IMDB config's batches, the stacked-worker vmap,
    # and chip_smoke's shape
    **{f"lstm-fwdbwd-T200-H128-B{b}": (lambda b=b: _lstm(b, 200, 128))
       for b in (32, 64, 128, 256)},
    "lstm-fwdbwd-vmap4-T200-H128-B64": lambda: _lstm(64, 200, 128, workers=4),
    "lstm-fwdbwd-T200-H512-B64": lambda: _lstm(64, 200, 512),
    "fused-adam-16384x1024": lambda: _adam((16384, 1024)),
    # q_matmul: every projection of the 400M decoder (dim 2048, 16 heads,
    # MQA, mlp_ratio 4, vocab 16384) at the decode batch m = 8 …
    **{f"q_matmul-8x{k}x{n}": (lambda k=k, n=n: _q_matmul(8, k, n))
       for k, n in ((2048, 2304), (2048, 2048), (2048, 8192), (8192, 2048),
                    (2048, 16384))},
    # … and its MLP down-projection under a 1024-token prefill: the fixed
    # 256 x 512 tiles came to 16.5 MiB against the 16 MiB scoped-VMEM limit
    # and the compiler refused it (any prefill of more than 240 rows)
    "q_matmul-1024x8192x2048": lambda: _q_matmul(1024, 8192, 2048),
    # the deepest, widest f32 call the K <= 8192 guard admits
    "q_matmul-f32-4096x8192x8192":
        lambda: _q_matmul(4096, 8192, 8192, dtype=F32),
}


@pytest.mark.parametrize("case", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = KERNELS[case]()
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"


NAMED = {
    "flash-fwdbwd-keymask-noncausal-d64": ("flash_fwd", "flash_dq",
                                           "flash_dkv"),
    "flash-fwdbwd-causal-bf16-8x2048x16x64": ("flash_fwd", "flash_dq",
                                              "flash_dkv"),
    "flash-fwdbwd-causal-gqa-kv2-8x4096x8x128": ("flash_fwd", "flash_dq",
                                                 "flash_dkv"),
    "flash-fwdbwd-causal-gqa-kv2-8x4096x16x256": ("flash_fwd", "flash_dq",
                                                  "flash_dkv"),
    "flash-fwdbwd-blockdiffusion4-gqa-kv4-4x8192x32x128": (
        "flash_fwd", "flash_dq", "flash_dkv"),
    "qk_prep-fwdbwd-q-4x8192x32x128": ("qk_prep_fwd", "qk_prep_bwd"),
    "qk_prep-fwdbwd-k-4x8192x4x128": ("qk_prep_fwd", "qk_prep_bwd"),
    "mla_prep-fwdbwd-4x8192x32x128+64x128": ("mla_prep_fwd", "mla_prep_bwd"),
    "gated_delta-fwdbwd-8x4096x16x32x128": ("gated_delta_fwd",),
    "lstm-fwdbwd-T200-H128-B32": ("lstm_scan_fwd", "lstm_scan_bwd"),
    "fused-adam-16384x1024": ("fused_adam",),
    "q_matmul-8x2048x2048": ("q_matmul",),
}


@pytest.mark.parametrize("case", sorted(NAMED))
def test_kernel_carries_its_name_for_v5e(one_chip, case):
    """``pallas_call(name=...)`` survives into the compiled program: it is
    what a profiler trace of the chip shows for the kernel's operation."""
    fn, args = KERNELS[case]()
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    for name in NAMED[case]:
        assert name in text, f"{name} is not in the compiled program"


# -- the whole LM train step ---------------------------------------------------


@pytest.mark.parametrize("dp,sharding", [(1, "megatron"), (4, "fsdp")])
def test_lm_train_step_compiles_with_its_kernels(topo, monkeypatch, dp,
                                                 sharding):
    """The config-9 step (vocab 16384, L 2048, dim 1024 x 8 layers, bf16,
    flash + RoPE + fused CE, adam) as ``MeshTrainer`` builds it, for one
    described chip and — ZeRO-3 sharded, global batch 8 — for the 2x2 host:
    24 kernel calls (flash forward, dq, dk/dv in each of 8 layers) and
    temporaries that leave room in 16 GB. On four chips each kernel must sit
    in a shard_map on its device's 2 rows (the compiler refuses to partition
    a Mosaic kernel itself) between the parameter all-gathers.
    ``attn_impl="flash"`` means the kernel on any backend; HOW it lowers
    still follows the backend the process runs on, so the native lowering is
    steered on here — in the test, not through an option of the program."""
    import chip_smoke
    from distkeras_tpu import ops
    from distkeras_tpu.trainers import MeshTrainer

    monkeypatch.setattr(ops, "native_kernels", lambda: True)
    B, L = 8, 2048
    spec = chip_smoke._lm_spec(vocab=16384, maxlen=L, dim=1024, heads=8,
                               depth=8, ce_chunk=512)
    mesh = Mesh(np.asarray(topo.devices[:dp]), ("dp",))
    trainer = MeshTrainer(spec, worker_optimizer="adam", learning_rate=1e-4,
                          mesh=mesh, parameter_sharding=sharding,
                          batch_size=B)
    engine, _, _ = trainer._build_engine()

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, shardings)

    rep = NamedSharding(mesh, P())
    params, nt = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    engine._resolve_specs(params)
    engine._build_step()
    tokens = jax.ShapeDtypeStruct((B, L), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp")))
    compiled = engine._step.lower(
        placed(params, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                    engine.param_specs)),
        placed(nt, jax.tree.map(lambda _: rep, nt)),
        placed(jax.eval_shape(engine.optimizer.init, params),
               engine._opt_shardings(params)),
        (tokens, tokens),
    ).compile()

    text = compiled.as_text()
    assert text.count(chip_smoke.KERNEL_CALL) == 24
    # what a trace of the chip names: the program, and each kernel 8 times
    assert "HloModule jit_train_step" in text
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert text.count(name) >= 8, name
    assert "qk_prep" not in text      # the block-diffusion block's alone
    assert (" all-gather(" in text) == (dp > 1)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * 2 ** 30, mem
    assert mem.argument_size_in_bytes < 2 * 2 ** 30 / dp, mem


def test_zaya_train_step_compiles_with_its_kernels(topo, monkeypatch):
    """``chip_smoke.py``'s ``moe`` step (ZAYA1 blocks: 4 query / 2 key-value
    heads of 128 under CCA, 8 experts of which 4 are held, remat, fused CE)
    for one described chip: in each of 2 layers the three flash kernels (the
    forward once: remat keeps its output and log-sum-exp), and the dropless
    expert layer's grouped products as the TPU compiler's own
    ``ragged-dot`` kernels, 8 a layer
    (gate-and-up and down: forward twice, the gradient to the rows, the
    gradient to the weights) beside the kernels that lay out their groups.
    No ``[tokens, experts, capacity]`` array is in the program."""
    import inspect

    import chip_smoke
    from distkeras_tpu import ops
    from distkeras_tpu.models import ZayaDims
    from distkeras_tpu.trainers import MeshTrainer

    monkeypatch.setattr(ops, "native_kernels", lambda: True)
    size = {k: v.default for k, v in
            inspect.signature(chip_smoke.moe).parameters.items()}
    dims = ZayaDims(head_dim=size["head_dim"], router_dim=size["router_dim"],
                    experts=size["experts"],
                    experts_held=size["experts_held"],
                    expert_dim=size["expert_dim"])
    spec = chip_smoke._zaya_spec(
        size["vocab"], size["maxlen"], size["dim"], size["heads"],
        size["kv_heads"], size["depth"], dims, size["ce_chunk"])
    B, L = size["batch"], size["maxlen"]
    mesh = Mesh(np.asarray(topo.devices[:1]), ("dp",))
    trainer = MeshTrainer(spec, loss="sparse_softmax_cross_entropy",
                          worker_optimizer="adam", learning_rate=1e-4,
                          mesh=mesh, batch_size=B)
    engine, _, _ = trainer._build_engine()
    rep = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            tree)

    params, nt = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    engine._resolve_specs(params)
    engine._build_step()
    tokens = jax.ShapeDtypeStruct((B, L), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp")))
    text = engine._step.lower(
        placed(params), placed(nt),
        placed(jax.eval_shape(engine.optimizer.init, params)),
        (tokens, tokens)).compile().as_text()
    depth = size["depth"]
    assert text.count("ragged-dot-none") >= 8 * depth
    # ONE forward a layer: remat keeps its two results (ops.REMAT_SAVED)
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert len(re.findall(rf"%{name}[.\d]* = ", text)) == depth, name
    assert text.count(chip_smoke.KERNEL_CALL) == size["kernel_calls"]
    assert "qk_prep" not in text      # CCA keeps apply_rope and its program
    T, E = B * L, size["experts"]
    assert f"[{T},{E},{T}]" not in text and f"[{T},{E}," not in text.replace(
        f"[{T},{E}]", "")


def _instructions(text):
    """``{name: (opcode, operand names, the line)}`` of a compiled program."""
    import re

    out = {}
    for line in text.splitlines():
        m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\((.*)", line)
        if m:
            operands = re.findall(r"%([\w.\-]+)", m.group(3).split(")")[0])
            out[m.group(1)] = (m.group(2), operands, line)
    return out


#: what may stand between two kernels without being a pass over the array: a
#: view, a tuple's element, and the compiler's own moves between its memory
#: spaces (``S(1)`` in a layout), which run beside other work
_NO_PASS = ("bitcast", "get-tuple-element", "copy-start", "copy-done")


def _source(instructions, name):
    """The instruction ``name`` is a view of, and the opcodes on the way."""
    seen = []
    while instructions[name][0] in _NO_PASS:
        seen.append(instructions[name][0])
        name = instructions[name][1][0]
    return name, seen


def test_block_diffusion_train_step_compiles_with_its_kernels(topo, monkeypatch):
    """A block-diffusion expert model's step (2 layers; 4 query / 2 key-value
    heads of 128; 16 experts of which 4 are held, 4 a token; rows of 1024 as
    streams of 2048; remat, fused CE) for one described chip: in each layer
    the three flash kernels (the forward ONCE: remat keeps its output and
    log-sum-exp) and the grouped products as ``ragged-dot`` kernels; the
    noise is drawn inside the step (``bd_noise``), and neither a ``[2 L, 2 L]`` mask nor a ``[pairs, dim]``
    array of every (token, expert) pair is anywhere in the program. q and k
    go from their projections to ``flash_fwd`` through ``qk_prep_fwd`` (4
    calls a layer) and their gradients back through ``qk_prep_bwd`` (2) with
    no copy, transpose or fusion between the kernels, and nothing under
    ``/attn/`` is a gather or a scatter."""
    from distkeras_tpu import ops
    from distkeras_tpu.models import SdarDims, transformer_lm
    from distkeras_tpu.models.lm import held_rows
    from distkeras_tpu.trainers import MeshTrainer

    monkeypatch.setattr(ops, "native_kernels", lambda: True)
    B, L, dim, depth = 4, 1024, 512, 2
    dims = SdarDims(head_dim=128, experts=16, experts_per_token=4,
                    experts_held=(0, 4), expert_dim=256, block_length=4)
    spec = transformer_lm(vocab=8192, maxlen=L, dim=dim, heads=4, kv_heads=2,
                          depth=depth, pos_embedding="rope", dtype=BF16,
                          attn_impl="flash", fused_ce=True, ce_chunk=256,
                          remat=True, sdar=dims)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("dp",))
    trainer = MeshTrainer(spec, loss="sparse_softmax_cross_entropy",
                          worker_optimizer="adam", learning_rate=1e-5,
                          mesh=mesh, batch_size=B)
    engine, _, _ = trainer._build_engine()
    rep = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            tree)

    params, nt = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    engine._resolve_specs(params)
    engine._build_step()
    tokens = jax.ShapeDtypeStruct((B, L), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp")))
    lowered = engine._step.lower(
        placed(params), placed(nt),
        placed(jax.eval_shape(engine.optimizer.init, params)),
        (tokens, tokens))
    assert "bd_noise" in lowered.as_text(debug_info=True)
    text = lowered.compile().as_text()
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert text.count(f"%{name}") >= depth, name
    assert "ragged-dot" in text
    for name, n in (("qk_prep_fwd", 4), ("qk_prep_bwd", 2)):
        assert text.count(f"%{name}") >= n * depth, name
    ins = _instructions(text)
    kernel = lambda stem: [n for n in ins if n.split(".")[0] == stem
                           and ins[n][0] == "custom-call"]
    # ONE forward a layer: remat keeps its two results (ops.REMAT_SAVED)
    assert len(kernel("flash_fwd")) == depth
    for name in kernel("flash_fwd"):            # q and k: operands 0 and 1
        for operand in ins[name][1][:2]:
            source, _ = _source(ins, operand)
            assert source.split(".")[0] == "qk_prep_fwd", ins[source][2][:300]
    assert len(kernel("qk_prep_bwd")) == 2 * depth
    for name in kernel("qk_prep_bwd"):          # the cotangent: operand 1
        source, _ = _source(ins, ins[name][1][1])
        assert source.split(".")[0] in ("flash_dq", "flash_dkv"), \
            ins[source][2][:300]
    for name, (opcode, _, line) in ins.items():
        if "/attn/" in line and "op_name=" in line:
            assert "gather" not in opcode and "scatter" not in opcode \
                and not name.startswith(("gather", "scatter")), line[:300]
    pairs = B * 2 * L * dims.experts_per_token
    assert held_rows(B * 2 * L, dims) == (11264, 4096) and 11264 < pairs
    for shape in (f"[{2 * L},{2 * L}]", f",{2 * L},{2 * L}]", f"[{pairs},{dim}]"):
        assert shape not in text, shape


def test_latent_attention_train_step_compiles_with_its_kernels(topo, monkeypatch):
    """A latent-attention expert model's step (3 layers, the first dense; 4
    heads of 128 + 64 / 128 over a latent of 512; 16 experts of which 4 are
    held, 2 a token, 2 shared; rows of 1024; remat, fused CE) for one
    described chip: in EVERY layer the three flash kernels at q and k 192
    wide and values 128 (the forward once: remat keeps its results), with no
    operand padded to 256 or values carried 192 wide; the grouped products as
    ``ragged-dot`` kernels in the two expert layers only; the scopes
    ``mla_latent``, ``moe_shared`` and ``moe_bias`` in the lowered step. q, k
    and v go from their projections to ``flash_fwd`` through ``mla_prep_fwd``
    (a q call and a k / v call, twice a layer under remat) and their gradients
    back through ``mla_prep_bwd`` with nothing between, and no gather, scatter
    or array of the shared key broadcast to every head is left under
    ``/attn/``."""
    from distkeras_tpu import ops
    from distkeras_tpu.models import MlaDims, transformer_lm
    from distkeras_tpu.trainers import MeshTrainer

    monkeypatch.setattr(ops, "native_kernels", lambda: True)
    B, L, dim, heads, depth = 4, 1024, 512, 4, 3
    dims = MlaDims(experts=16, experts_per_token=2, experts_held=(0, 4),
                   expert_dim=256, dense_dim=1024)
    spec = transformer_lm(vocab=8192, maxlen=L, dim=dim, heads=heads,
                          depth=depth, pos_embedding="rope", dtype=BF16,
                          attn_impl="flash", fused_ce=True, ce_chunk=256,
                          remat=True, mla=dims)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("dp",))
    trainer = MeshTrainer(spec, loss="sparse_softmax_cross_entropy",
                          worker_optimizer="adam", learning_rate=1e-4,
                          mesh=mesh, batch_size=B)
    engine, _, _ = trainer._build_engine()
    rep = NamedSharding(mesh, P())

    def placed(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rep),
            tree)

    params, nt = jax.eval_shape(spec.init, jax.random.PRNGKey(0))
    engine._resolve_specs(params)
    engine._build_step()
    tokens = jax.ShapeDtypeStruct((B, L), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp")))
    lowered = engine._step.lower(
        placed(params), placed(nt),
        placed(jax.eval_shape(engine.optimizer.init, params)),
        (tokens, tokens))
    named = lowered.as_text(debug_info=True)
    for scope in ("mla_latent", "moe_shared", "moe_bias", "moe_experts"):
        assert scope in named, scope
    text = lowered.compile().as_text()
    ins = _instructions(text)
    kernel = lambda stem: [n for n in ins if n.split(".")[0] == stem
                           and ins[n][0] == "custom-call"]
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert len(kernel(name)) == depth, name
    wide, narrow = f"bf16[{B * heads},{L},192]", f"bf16[{B * heads},{L},128]"
    for name in kernel("flash_fwd"):
        line = ins[name][2]
        assert line.count(wide) >= 2 and narrow in line, line[:400]
        assert f"{L},256]" not in line, line[:400]
        for operand in ins[name][1][:3]:        # q, k and v
            source, _ = _source(ins, operand)
            assert source.split(".")[0] == "mla_prep_fwd", ins[source][2][:300]
    assert len(kernel("mla_prep_fwd")) == 4 * depth
    assert len(kernel("mla_prep_bwd")) == 2 * depth
    for name in kernel("mla_prep_bwd"):         # every cotangent, tables last
        for operand in ins[name][1][:-2]:
            source, _ = _source(ins, operand)
            assert source.split(".")[0] in ("flash_dq", "flash_dkv"), \
                ins[source][2][:300]
    for name, (opcode, _, line) in ins.items():
        if "/attn/" in line and "op_name=" in line:
            assert "gather" not in opcode and "scatter" not in opcode \
                and not name.startswith(("gather", "scatter")), line[:300]
            assert f"[{B},{L},{heads},64]" not in line, line[:300]
    assert "ragged-dot" in text
    assert "blocks_0/moe" not in named and "blocks_1/moe" in named


# -- the serving steps -----------------------------------------------------------


@pytest.mark.parametrize("step", ["prefill-128", "decode"])
def test_serving_step_compiles_for_v5e(one_chip, monkeypatch, step):
    """The 400M MQA decoder's engine programs at full width (vocab 16384,
    dim 2048, 16 heads / 1 KV head, 8 layers, bf16, 8 rows of 1024): the
    batched prefill of a 128-token prompt — flash attention inside, one call
    per layer — and the greedy paged decode step over an 18-block table."""
    import chip_smoke
    from distkeras_tpu import ops
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.serving import GenerationEngine

    monkeypatch.setattr(ops, "native_kernels", lambda: True)
    spec = transformer_lm(vocab=16384, maxlen=1024, dim=2048, heads=16,
                          depth=8, kv_heads=1, dtype=BF16, attn_impl="flash",
                          pos_embedding="rope")
    shaped = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)
    arr = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    params = shaped(jax.eval_shape(spec.init, jax.random.PRNGKey(0))[0])
    engine = GenerationEngine(spec, None, max_batch=8)
    k, v = shaped(engine.cache.k_pools), shaped(engine.cache.v_pools)
    i32, f32 = jnp.int32, F32
    if step == "decode":
        lowered = engine._decode_fn_greedy.lower(
            params, k, v, arr(i32, 8), arr(i32, 8, 18), arr(i32, 8),
            arr(i32, 8))
        kernels = 0          # the paged gather + attention are plain XLA
    else:
        lowered = engine._make_prefill().lower(
            params, None, k, v, (), (), arr(i32, 1, 128), arr(i32, 1, 128),
            arr(i32, 1), arr(f32, 1), arr(i32, 1), arr(f32, 1),
            arr(jnp.bool_, 1), arr(i32, 1))
        kernels = 8
    compiled = lowered.compile()
    assert compiled.as_text().count(chip_smoke.KERNEL_CALL) == kernels
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30
