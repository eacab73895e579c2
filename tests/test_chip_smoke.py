"""chip_smoke.py rehearsed on the CPU mesh: every phase function at a tiny
size (Pallas kernels in interpret mode), so a wrong path, argument or check
in the script costs no chip time — and the device gate itself: on a platform
that is not a TPU, ``main`` exits non-zero before any phase and prints no
``"ok": true`` line.
"""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke

LM_TINY = dict(vocab=256, maxlen=128, dim=64, heads=2, depth=1, ce_chunk=64,
               batch=4, steps=2)


def test_kernels_phase_interpret_mode():
    line = chip_smoke.kernels(
        attn=(1, 128, 2, 64), qmm=((8, 128, 256), (40, 256, 128)),
        adam=(40, 33), lstm=(8, 5, 128), interpret=True,
        timed=((1, 256, 2, 64, 1), (1, 512, 2, 64, 1, 4),
               (1, 256, 2, (48, 32), 2)),
        prep=((2, 256, 2, 128), (1, 128, 1, 128)), wide=(1, 256, 2, 48, 32),
        latent=((1, 256, 2, 128, 64, 128),),
    )
    assert line["phase"] == "kernels" and line["interpret"] is True
    assert set(line["norm_err"]) >= {"flash.out", "flash.dq", "lstm.dwh",
                                     "fused_adam.update2"}
    # a stream of 128 is rows of 64: too short for the kernel's tiles, so
    # the block-diffusion comparison waits for a longer call (below)
    assert "flash_bd.out" not in line["norm_err"]
    # the timed leg: no device time off the chip, the static census beside it
    causal, full, blocks, latent = line["flash"]
    # latent attention's call: q and k of one width, v of another, causal
    assert latent["shape"] == [1, 256, 2, (48, 32), 2] and latent["causal"]
    assert latent["flash_dq"]["computed_over_band"] > 1.0
    assert {"flash_48_32.out", "flash_48_32.dq", "flash_48_32.dk",
            "flash_48_32.dv"} <= set(line["norm_err"])
    assert blocks["block_diffusion"] == 4 and "causal" not in blocks
    assert blocks["flash_dkv"]["ms"] is None
    assert blocks["flash_fwd"]["steps"] == 4 * 2     # 4 q tiles x (own + 1 clean)
    assert blocks["flash_dq"]["computed_over_band"] > 1.0
    assert causal["causal"] and causal["flash_dkv"]["ms"] is None
    assert causal["flash_dq"]["computed_over_band"] > 1.0
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert full[name]["computed_over_band"] == 1.0
        assert full[name]["unmasked_share"] == 1.0
        assert full[name]["steps_idle"] == 0
    # off the chip "auto" keeps the references for attention and the LSTM —
    # the phase reports it, and only a native run insists on the kernels
    assert line["auto"] == {"attention": "reference", "lstm_scan": "xla",
                            "q_matmul": "pallas", "qk_prep": "xla",
                            "mla_prep": "xla"}
    # qk_prep at a q and a k projection's shape: its error against the jnp
    # chain beside (off the chip) no device time
    q, k = line["qk_prep"]
    assert q["shape"] == [2, 256, 2, 128] and k["shape"] == [1, 128, 1, 128]
    assert q["qk_prep_fwd"] == {"ms": None, "floor_bytes": 2 * 2 * 256 * 256 * 2,
                                "gb_per_s": None}
    assert q["qk_prep_bwd"]["floor_bytes"] == 3 * 2 * 256 * 256 * 2
    assert q["chain_ms"] is None and max(q["norm_err"].values()) < 2e-2
    assert {"qk_prep2.out", "qk_prep2.dx", "qk_prep1.dw"} <= set(line["norm_err"])
    # mla_prep at a latent sublayer's projections, q's kernels and k / v's
    # apart: each operand read and each result written once
    lq, lkv = line["mla_prep"]
    assert (lq["part"], lkv["part"]) == ("q", "kv")
    assert lq["shape"] == lkv["shape"] == [1, 256, 2, 128, 64, 128]
    assert lq["mla_prep_fwd"] == lq["mla_prep_bwd"] == {
        "ms": None, "floor_bytes": 2 * 256 * 2 * 192 * 2, "gb_per_s": None}
    assert lkv["mla_prep_bwd"]["floor_bytes"] == 256 * 2 * (
        2 * 256 + 64 + 2 * 192 + 2 * 128)
    assert lkv["chain_ms"] is None
    assert {"mla_prep2.q", "mla_prep2.dq", "mla_prep2.k", "mla_prep2.v",
            "mla_prep2.dkv", "mla_prep2.dk_rope"} <= set(line["norm_err"])


def test_kernels_phase_compares_the_block_diffusion_mask():
    line = chip_smoke.kernels(attn=(1, 256, 2, 64), qmm=((8, 128, 128),), adam=(8, 16),
                              lstm=(8, 2, 128), interpret=True, timed=(), prep=(), wide=(),
                              latent=())
    assert {"flash_bd.out", "flash_bd.dq", "flash_bd.dk",
            "flash_bd.dv"} <= set(line["norm_err"])


def test_kernels_phase_fails_on_a_wrong_kernel(monkeypatch):
    """The comparison has teeth: a kernel that is off its reference fails
    the phase (after the line with every measured error is printed)."""
    from distkeras_tpu.ops import quant

    monkeypatch.setattr(
        chip_smoke, "TOL", {"bfloat16": 0.0, "float32": 0.0}
    )
    monkeypatch.setattr(quant, "_q_matmul_xla",
                        lambda x, qt, dt: (x @ qt.q.astype(x.dtype)) * 1.5)
    with pytest.raises(chip_smoke.SmokeFailure, match="off its reference"):
        chip_smoke.kernels(attn=(1, 128, 1, 64), qmm=((8, 128, 128),),
                           adam=(8, 16), lstm=(8, 2, 128), interpret=True,
                           timed=(), prep=(), wide=(), latent=())


def test_loss_phase_tiny():
    """The fused loss alone: the static count of its backward's tiles beside
    (off the chip) no device time, and d_hidden held to the plain formula."""
    line = chip_smoke.loss(shapes=((96, 8, 9000, 16), (40, 8, 300, 64)),
                           timed=False, check_rows=32)
    assert line["phase"] == "loss" and line["timed"] is False
    several, one = line["shapes"]
    assert set(several) == {"shape", "tiles", "vb", "padded_columns",
                            "carry_bytes_moved", "loss", "dh_norm_err", "ms",
                            "ops"}
    assert (several["tiles"], several["vb"]) == (3, 3072)
    assert several["padded_columns"] == 216
    assert several["carry_bytes_moved"] == 3 * 8 * 96 * 8
    assert (one["tiles"], one["vb"], one["padded_columns"]) == (1, 300, 0)
    assert several["ms"] is None and several["ops"] is None
    assert several["dh_norm_err"] < 2e-2 and one["dh_norm_err"] < 2e-2


def test_adag_phase_tiny():
    line = chip_smoke.adag(n_train=512, n_test=128, batch_size=16, window=1,
                           epochs=3, num_workers=2, min_accuracy=0.5)
    assert line["windows"] == 48 and len(line["state_devices"]) == 2
    assert line["loss_last"] < 0.5 * line["loss_first"]


def test_lm_phase_tiny():
    # interpret-mode kernels leave no custom call in the compiled step
    line = chip_smoke.lm(**LM_TINY, epochs=2, kernel_calls=0)
    assert line["steps"] == 4
    assert abs(line["losses"][0] - line["plain_f32_first_loss"]) < 0.06


def test_moe_phase_tiny():
    line = chip_smoke.moe(vocab=256, maxlen=128, dim=64, heads=4, kv_heads=2,
                          depth=2, head_dim=16, router_dim=16, experts=4,
                          experts_held=(0, 2), expert_dim=64, ce_chunk=64,
                          batch=2, steps=2, epochs=2, kernel_calls=0,
                          top_k=(64, 32, 16, 16, 4, 8))
    assert line["steps"] == 4
    assert abs(line["losses"][0] - line["plain_f32_first_loss"]) < 0.06
    assert 0.0 < line["held_share"] < 1.0
    top = line["top_k"]
    assert top["k"] == 8 and top["pairs"] == 64 * 8
    assert 0 < top["pairs_held"] < top["pairs"] and top["norm_err"] < 2e-2


def test_serve_phase_tiny():
    line = chip_smoke.serve(vocab=64, maxlen=64, dim=32, heads=4, depth=2,
                            kv_heads=1, prompt_lens=(5, 8, 13, 16),
                            new_tokens=8, max_batch=4)
    assert line["completed"] == 4 and line["server_stopped"]
    assert line["first_token_differing_from_dense_generate"] == [None] * 4


def test_adag4_phase_tiny():
    line = chip_smoke.adag4(n_train=256, n_test=64, batch_size=16,
                            window=1, epochs=1, optimizers=("sgd",),
                            min_accuracy=0.0)
    devices = {dev for dev, _ in line["worker_state_shards"]}
    assert len(devices) == 4
    # on the CPU the two programs share their arithmetic to the bit
    assert line["center_rel_l2_vs_stacked"] == {"sgd": 0.0}


def test_lm4_phase_tiny():
    line = chip_smoke.lm4(**LM_TINY)
    assert line["params_sharded"] > 0
    assert line["collectives_in_step"]["all-gather"] > 0


def test_main_refuses_a_platform_that_is_not_tpu(capsys):
    """JAX is held to the CPU here: exit non-zero before any phase, say
    what was found, and print nothing a driver could read as a result."""
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    out, err = capsys.readouterr()
    assert "cpu" in err and '"ok"' not in out and '"phase"' not in out
    for line in out.splitlines():
        assert not json.loads(line).get("ok")
