"""Driver benchmark: all five BASELINE configs, samples/sec + MFU.

Prints ONE JSON line on stdout (the north-star config — ADAG/MNIST-CNN):

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "mfu": N}

Run order is budget-safe (VERDICT r3 #1): BASELINE configs → time-to-
accuracy → CPU proxy → **headline JSON on stdout**, and only then the
beyond-reference legs (transformer/LM training, decode, speculative,
composed serving), each emitting its stderr record as it completes and
each gated on an elapsed-time budget (``DISTKERAS_BENCH_BUDGET`` seconds,
default 1500; ``--full`` disables the gate). A harness timeout can then
only truncate extras — never the headline record.

Everything except the headline goes to stderr: one JSON line per config
and, with ``--scaling``, a stacked-worker scaling sweep W ∈ {1,2,4,8} on
one chip (real multi-chip is unavailable here; see SCALING.md).

``vs_baseline`` is the speedup over the reference-proxy denominator. The
reference's own number (16-executor Spark/CPU cluster) is unrecoverable
(BASELINE.md), so per SURVEY.md §6 the documented proxy is a single-process
CPU run of the same model with the SAME batch_size/communication_window
(fewer rows; ≥3 timed epochs post-warmup), measured in this run. The
north-star "≥12× a 16-executor cluster" corresponds to ``vs_baseline ≥ 192``
under ideal linear Spark scaling (16 executors × 12).

MFU = samples/sec × analytic training FLOPs/sample ÷ chip peak. Training
FLOPs are counted as 3× forward (fwd + ~2× bwd), conv/dense/LSTM matmul terms
only — elementwise ops excluded, so MFU is slightly underestimated. Peak
defaults to 197 bf16 TFLOP/s (TPU v5e); override with
``DISTKERAS_PEAK_TFLOPS``.
"""

import argparse
import json
import math
import os
import sys
import threading
import time

import jax
import numpy as np


def log(msg):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Analytic training-FLOP models (3× forward; matmul terms only)
# ---------------------------------------------------------------------------


def mlp_flops(dims):
    return 3 * 2 * sum(a * b for a, b in zip(dims, dims[1:]))


def lenet_flops():
    fwd = (
        2 * 25 * 1 * 32 * 28 * 28      # conv1 5×5×1→32 @ 28×28
        + 2 * 25 * 32 * 64 * 14 * 14   # conv2 5×5×32→64 @ 14×14
        + 2 * 3136 * 256               # dense1
        + 2 * 256 * 10                 # head
    )
    return 3 * fwd


def vgg_small_flops():
    fwd = 0
    res, cin = 32 * 32, 3
    for w in (64, 128, 256):
        fwd += 2 * 9 * cin * w * res + 2 * 9 * w * w * res
        cin, res = w, res // 4
    fwd += 2 * 4096 * 512 + 2 * 512 * 10
    return 3 * fwd


def lstm_flops(maxlen=200, embed=128, hidden=128):
    fwd = maxlen * 8 * hidden * (embed + hidden) + 2 * hidden * 2
    return 3 * fwd


#: bf16 peak FLOP/s by device-kind substring (first match wins; order puts
#: the more specific names first). Override with DISTKERAS_PEAK_TFLOPS.
_PEAK_BF16 = (
    ("v6e", 918e12),      # Trillium
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),  # v5e reports device_kind "TPU v5 lite"
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def peak_flops(device) -> float | None:
    if device.platform != "tpu":
        return None
    env = os.environ.get("DISTKERAS_PEAK_TFLOPS")
    if env:
        return float(env) * 1e12
    kind = getattr(device, "device_kind", "").lower()
    for key, val in _PEAK_BF16:
        if key in kind:
            return val
    raise ValueError(
        f"no bf16 peak known for TPU device_kind {device.device_kind!r}: "
        f"add it to _PEAK_BF16 (or set DISTKERAS_PEAK_TFLOPS) — an MFU "
        f"against a guessed peak is not a measurement"
    )


# ---------------------------------------------------------------------------
# Measurement core: steady-state samples/sec of one (model, rule) config on
# one device via the HBM-resident epoch path (what the trainer's auto mode
# uses) — one upload, one dispatch per epoch, timed after a warm-up epoch.
# ---------------------------------------------------------------------------


def measure(device, spec, rule, optimizer, train, cols, batch_size, window,
            num_workers=1, epochs_timed=3, reduce="median"):
    from distkeras_tpu.ops.losses import sparse_softmax_cross_entropy
    from distkeras_tpu.parallel.local_sgd import LocalSGDEngine
    from distkeras_tpu.parallel.mesh import get_mesh

    n_feat = len(cols) - 1

    def loss_step(params, nt, batch):
        feats, y = batch[:n_feat], batch[n_feat]
        x = feats[0] if n_feat == 1 else tuple(feats)
        out, new_nt = spec.apply(params, nt, x, training=True)
        return sparse_softmax_cross_entropy(y, out), new_nt

    # one physical device; num_workers > 1 stacks replicas on it
    mesh = get_mesh(1, devices=[device])
    engine = LocalSGDEngine(
        spec, loss_step, optimizer, rule, mesh,
        num_workers=num_workers, window=window, batch_size=batch_size,
    )
    params, nt = spec.init_np(0)
    state = engine.init_state(params, nt)
    staged = engine.stage_dataset(
        train.worker_shards(num_workers, batch_size, window, cols)
    )
    rows_pw = staged[0].shape[1]
    n_windows = rows_pw // (batch_size * window)
    epoch_rows = num_workers * n_windows * batch_size * window

    t0 = time.perf_counter()
    state, losses = engine.run_epoch_resident(state, staged, 0)  # compile+warm
    # HOST FETCH as well as block_until_ready: fetching a compute-dependent
    # scalar is the sync point the timed epochs below use, so the warm-up
    # ends on the same one.
    float(np.asarray(losses[-1]))
    jax.block_until_ready(state.center)
    log(f"  compile+warm epoch: {time.perf_counter() - t0:.1f}s")

    # per-epoch timing; the reported number is the MEDIAN epoch (VERDICT r2:
    # aggregates hid noisy sub-second epochs), spread logged alongside
    per_epoch, epoch_losses = [], []
    for e in range(epochs_timed):
        t0 = time.perf_counter()
        state, losses = engine.run_epoch_resident(state, staged, e + 1)
        jax.block_until_ready(state)
        epoch_losses.append(float(np.asarray(losses[-1])))  # forces drain
        per_epoch.append(epoch_rows / (time.perf_counter() - t0))
    # reduce="max" (CPU-proxy denominator only): the fastest epoch is the
    # least CPU-contended one, i.e. the closest to the uncontended truth —
    # and a FASTER denominator makes vs_baseline a conservative lower
    # bound, so contention can only understate the ratio, never inflate it
    sps = float(max(per_epoch) if reduce == "max" else np.median(per_epoch))
    med = float(np.median(per_epoch))
    spread = ((max(per_epoch) - min(per_epoch)) / med if med else 0.0)
    # chained state ⇒ every epoch's final loss must differ; a bit-identical
    # pair means a dispatch was dropped/memoized and the timing is garbage
    distinct = len(set(epoch_losses)) == len(epoch_losses)
    stat = "max" if reduce == "max" else "median"
    log(f"  {sps:,.0f} samples/sec {stat} of {epochs_timed} epochs "
        f"(spread {100 * spread:.0f}%, {n_windows} windows × {num_workers}w, "
        f"final loss {epoch_losses[-1]:.4f})")
    if not distinct:
        log(f"  WARNING: identical epoch losses {epoch_losses} — a timed "
            f"dispatch did not run; record marked invalid")
    return sps, spread, distinct


#: spread above this marks a record invalid (r4's bogus config-5 record
#: carried 58% spread; legitimate records here measure ≤10%)
MAX_SPREAD = 0.30


def emit(name, sps, flops_per_sample, peak, extra=None, spread=None,
         distinct=True, reduce="median"):
    """Emit one stderr JSON record, with validity gating (VERDICT r4 #1):
    an MFU above 1.0 is physically impossible and a spread above
    ``MAX_SPREAD`` (or non-distinct chained-epoch losses) means the timing
    loop was fooled — such records ship with ``"invalid": true`` so no
    downstream reader can mistake them for measurements. ``reduce="max"``
    legs (CPU-measured while the concurrent proxy subprocess contends for
    the host — see run_proxy_only) are exempt from the spread gate:
    contention only SLOWS epochs, the fastest epoch is the least-contended
    estimate, so a wild spread there reflects the contention this treatment
    exists to ride out, not a fooled timing loop. ``distinct`` still
    gates them."""
    rec = {
        "config": name,
        "samples_per_sec": round(sps, 1),
        "flops_per_sample": int(flops_per_sample),
    }
    if spread is not None:
        rec["spread"] = round(spread, 3)
    if reduce == "max":
        rec["reduce"] = "max"
    if peak:
        rec["tflops_delivered"] = round(sps * flops_per_sample / 1e12, 2)
        rec["mfu"] = round(sps * flops_per_sample / peak, 4)
        if rec["mfu"] > 1.0:
            rec["invalid"] = True
            log(f"  INVALID: mfu {rec['mfu']} > 1 is physically impossible "
                f"(chip peak {peak / 1e12:.0f} TFLOP/s)")
    spread_gated = reduce != "max"
    if (spread_gated and spread is not None and spread > MAX_SPREAD) \
            or not distinct:
        rec["invalid"] = True
        log(f"  INVALID: spread {spread} > {MAX_SPREAD} or non-distinct "
            f"epoch losses — timing not trustworthy")
    if extra:
        rec.update(extra)
    log(json.dumps(rec))
    return rec


def measure_checked(name, device, spec, rule, optimizer, train, cols,
                    batch_size, window, flops_per_sample, peak,
                    num_workers=1, epochs_timed=3, extra=None,
                    reduce="median"):
    """measure() + emit() with one retry: if the record comes back invalid
    (impossible MFU / wild spread / memoized epoch), re-measure once with
    more timed epochs before shipping it, still gated."""
    sps, spread, distinct = measure(
        device, spec, rule, optimizer, train, cols, batch_size, window,
        num_workers=num_workers, epochs_timed=epochs_timed, reduce=reduce)
    bad = (not distinct
           or (reduce != "max" and spread > MAX_SPREAD)
           or (peak and sps * flops_per_sample / peak > 1.0))
    if bad:
        log(f"  re-measuring {name} (first attempt invalid)")
        sps, spread, distinct = measure(
            device, spec, rule, optimizer, train, cols, batch_size, window,
            num_workers=num_workers, epochs_timed=epochs_timed + 2,
            reduce=reduce)
    return emit(name, sps, flops_per_sample, peak, extra=extra,
                spread=spread, distinct=distinct, reduce=reduce)


def run_all_configs(accel):
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.datasets import cifar10, higgs, imdb, mnist
    from distkeras_tpu.models import lenet, lstm_classifier, mlp, vgg_small
    from distkeras_tpu.parallel.merge_rules import (
        ADAGMerge,
        DownpourMerge,
        DynSGDMerge,
        ElasticAverageMerge,
    )

    peak = peak_flops(accel)
    on_tpu = accel.platform == "tpu"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    results = {}

    def cfg(tpu_val, cpu_val):
        # accelerator-sized vs CPU-only-host-sized run parameters (single-core
        # XLA:CPU convs are ~4 orders of magnitude slower — see SCALING.md)
        return tpu_val if on_tpu else cpu_val

    # -- config 1: MNIST 3-layer MLP, SingleTrainer (single-process CPU) ----
    # reduce="max": this leg runs on the host CPU while the CPU-proxy
    # subprocess (spawned before run_all_configs) burns its ~550 s XLA:CPU
    # compile on the same cores — the same conservative treatment as the
    # proxy itself (see run_proxy_only), so proxy contention can't inflate
    # this leg's median or spuriously trip the spread gate
    log("[config 1] MNIST-MLP / SingleTrainer (single-process CPU)")
    cpu = jax.devices("cpu")[0]
    train, _ = mnist(n_train=8192, n_test=64)
    results["mnist_mlp_single_cpu"] = measure_checked(
        "mnist_mlp_single_cpu", cpu, mlp(dtype=jnp.float32), ADAGMerge(),
        optax.sgd(0.01), train, ["features", "label"], batch_size=64,
        window=1, flops_per_sample=mlp_flops((784, 500, 300, 10)), peak=None,
        reduce="max")

    # -- config 2: MNIST LeNet CNN, ADAG (the north-star) -------------------
    # Two legs: batch 256 (matched to the CPU proxy for the vs_baseline
    # ratio) and batch 1024 (the throughput-optimal config — a batch-1024
    # CPU proxy is impractical: its warm epoch alone takes ~45 min on this
    # single-process host, measured once for SCALING.md).
    log(f"[config 2] MNIST-CNN / ADAG on {accel.platform} (ratio leg, b256)")
    train, _ = mnist(n_train=cfg(524288, 768), n_test=64)
    results["adag_mnist_cnn"] = measure_checked(
        "adag_mnist_cnn", accel, lenet(dtype=dt), ADAGMerge(),
        optax.adam(1e-3), train, ["features", "label"],
        batch_size=cfg(256, 64), window=cfg(8, 3),
        flops_per_sample=lenet_flops(), peak=peak,
        epochs_timed=cfg(3, 1), extra={"batch_size": cfg(256, 64)})
    if on_tpu:
        log("[config 2] MNIST-CNN / ADAG peak leg (b1024)")
        results["adag_mnist_cnn_peak"] = measure_checked(
            "adag_mnist_cnn_peak", accel, lenet(dtype=dt), ADAGMerge(),
            optax.adam(1e-3), train, ["features", "label"], batch_size=1024,
            window=8, flops_per_sample=lenet_flops(), peak=peak,
            extra={"batch_size": 1024})

    # -- config 3: CIFAR-10 VGG-small, DOWNPOUR -----------------------------
    log(f"[config 3] CIFAR10-VGG / DOWNPOUR on {accel.platform}")
    # batch 512 beats 256 by ~10-15% on the chip (batch sweep in SCALING.md)
    train, _ = cifar10(n_train=cfg(65536, 64), n_test=64)
    results["downpour_cifar_vgg"] = measure_checked(
        "downpour_cifar_vgg", accel, vgg_small(dtype=dt), DownpourMerge(),
        optax.adam(5e-4), train, ["features", "label"],
        batch_size=cfg(512, 16), window=cfg(4, 2),
        flops_per_sample=vgg_small_flops(), peak=peak,
        epochs_timed=cfg(3, 1))

    # -- config 4: Higgs tabular MLP, AEASGD + EAMSGD -----------------------
    # rows sized so each timed epoch is ~1 s (all TPU configs follow this
    # rule): a 26 ms epoch is too short to time against the fixed
    # per-epoch dispatch + sync cost, so short epochs understate
    # throughput; with per-epoch medians the two legs' numbers reproduce
    # within their stated spread
    log(f"[config 4] Higgs-MLP / AEASGD+EAMSGD on {accel.platform}")
    train, _ = higgs(n_train=cfg(4194304, 4096), n_test=64)
    hdims = (28, 256, 128, 2)
    hspec = mlp(input_shape=(28,), hidden=hdims[1:-1], num_classes=2, dtype=dt)
    for nm, opt in (("aeasgd", optax.sgd(0.05)),
                    ("eamsgd", optax.sgd(0.05, momentum=0.9, nesterov=True))):
        results[f"{nm}_higgs_mlp"] = measure_checked(
            f"{nm}_higgs_mlp", accel, hspec, ElasticAverageMerge(alpha=0.05),
            opt, train, ["features", "label"], batch_size=cfg(512, 128),
            window=cfg(8, 4), flops_per_sample=mlp_flops(hdims), peak=peak,
            epochs_timed=cfg(3, 1))

    # -- config 5: IMDB LSTM, DynSGD ----------------------------------------
    # W=8 stacked workers on the chip: the worker vmap axis batches the thin
    # [B×128]·[128×512] recurrent matmuls into the MXU (the repo's own
    # scaling sweep shows >2× at W=8; VERDICT r2 flagged benchmarking the
    # distributed config with no distribution)
    log(f"[config 5] IMDB-LSTM / DynSGD on {accel.platform} (W=8 stacked)")
    train, _ = imdb(n_train=cfg(65536, 128), n_test=64)
    results["dynsgd_imdb_lstm"] = measure_checked(
        "dynsgd_imdb_lstm", accel, lstm_classifier(dtype=dt), DynSGDMerge(),
        optax.adam(1e-3), train, ["features", "mask", "label"],
        batch_size=cfg(64, 16), window=cfg(4, 2),
        flops_per_sample=lstm_flops(), peak=peak,
        num_workers=cfg(8, 1), epochs_timed=cfg(3, 1),
        extra={"num_workers": cfg(8, 1)})

    return results


def transformer_flops_per_token(dim, depth, L):
    # matmul terms only: qkv/attn_out/mlp (24·d²/layer) + QKᵀ and AV (4·L·d);
    # 3× forward. The flash backward recomputes the forward, so true FLOPs
    # are ~4×fwd — reported MFU underestimates accordingly.
    return 3 * depth * (24 * dim * dim + 4 * L * dim)


_TRANSFORMER_DIMS = dict(dim=512, heads=8, depth=8)
_TRANSFORMER_L, _TRANSFORMER_B = 2048, 8


def _transformer_spec(attn_impl: str, heads: int | None = None):
    import jax.numpy as jnp

    from distkeras_tpu.models import transformer_classifier

    dims = dict(_TRANSFORMER_DIMS)
    if heads is not None:
        dims["heads"] = heads
    return transformer_classifier(
        vocab=8192, maxlen=_TRANSFORMER_L, num_classes=2,
        attn_impl=attn_impl, dtype=jnp.bfloat16, **dims,
    )


def run_transformer_handrolled(accel, attn_impl="flash", n_steps=20):
    """The hand-jitted reference step (kept as the sanity bound for the
    trainer-level leg below). attn_impl='flash': the Pallas fwd+bwd kernels
    are 1.7× XLA at this length since the round-3 backward (SCALING.md).
    Chained-state timing: every step consumes the previous step's
    state, so no dispatch repeats an earlier one."""
    import optax

    from distkeras_tpu.ops.losses import sparse_softmax_cross_entropy

    L, B = _TRANSFORMER_L, _TRANSFORMER_B
    spec = _transformer_spec(attn_impl)
    params, nt = spec.init_np(0)
    tx = optax.sgd(1e-3)
    opt = tx.init(params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 8192, size=(B, L)).astype(np.int32)
    mask = np.ones((B, L), np.float32)
    y = rng.integers(0, 2, size=(B,)).astype(np.int32)

    def step(params, opt, nt):
        def loss_fn(p):
            out, new_nt = spec.apply(p, nt, (toks, mask), training=True)
            return sparse_softmax_cross_entropy(y, out), new_nt

        (loss, nt), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, nt, loss

    step = jax.jit(step, donate_argnums=(0, 1))
    t0 = time.perf_counter()
    params, opt, nt, loss = step(params, opt, nt)
    float(np.asarray(loss))  # host fetch: full drain (see measure())
    log(f"  [handrolled/{attn_impl}] compile+first step: "
        f"{time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt, nt, loss = step(params, opt, nt)
    float(np.asarray(loss))
    dt = time.perf_counter() - t0
    tok_s = n_steps * B * L / dt
    log(f"  [handrolled/{attn_impl}] {tok_s:,.0f} tokens/sec "
        f"({1e3 * dt / n_steps:.2f} ms/step)")
    return tok_s


def run_transformer_config(accel):
    """Beyond-reference leg: transformer encoder, bf16, flash attention,
    full fwd+bwd training at L=2048 — measured THROUGH the trainer API
    (MeshTrainer, resident input path: the epoch is one jitted scan), per
    VERDICT r2 #4. The hand-rolled step is measured alongside as the sanity
    bound; the trainer number is the record."""
    import contextlib

    from distkeras_tpu.data import Dataset
    from distkeras_tpu.trainers import MeshTrainer

    L, B = _TRANSFORMER_L, _TRANSFORMER_B
    DIMS = _TRANSFORMER_DIMS
    log(f"[config 6] transformer bf16 on {accel.platform} "
        f"(L={L}, B={B}, {DIMS}, flash attention, MeshTrainer)")
    hand_tok_s = run_transformer_handrolled(accel)

    # 48 steps/epoch amortizes per-epoch dispatch + metrics drain (same
    # finding as config 9 - see run_lm_train_config)
    steps_per_epoch = 48
    rng = np.random.default_rng(0)
    n = B * steps_per_epoch
    ds = Dataset({
        "features": rng.integers(0, 8192, size=(n, L)).astype(np.int32),
        "mask": np.ones((n, L), np.float32),
        "label": rng.integers(0, 2, size=(n,)).astype(np.int32),
    })
    def trainer_leg(heads, name, extra):
        trainer = MeshTrainer(
            _transformer_spec("flash", heads=heads), worker_optimizer="sgd",
            learning_rate=1e-3, mesh_shape={"dp": 1}, batch_size=B,
            num_epoch=4, features_col=["features", "mask"],
            label_col="label", input_mode="resident", log_metrics=True,
        )
        # log_metrics streams per-epoch JSON to stdout; bench's stdout
        # contract is ONE line, so route the trainer's stream to stderr
        with contextlib.redirect_stdout(sys.stderr):
            trainer.train(ds)
        # epoch 0 includes compile; median of the rest is the steady state
        sps = sorted(m["samples_per_sec"] for m in trainer.metrics_[1:])
        if not sps:
            raise RuntimeError("transformer leg needs >=2 epochs")
        spread = (sps[-1] - sps[0]) / sps[len(sps) // 2]
        sps_med = sps[len(sps) // 2]
        tok_s = sps_med * L
        peak = peak_flops(accel)
        rec = {
            "config": name,
            "tokens_per_sec": round(tok_s, 1),
            "ms_per_step": round(1e3 * B / sps_med, 2),
            "seq_len": L, "batch": B, "heads": heads,
            "via": "MeshTrainer(resident)",
            "spread": round(spread, 3),
            **extra,
        }
        fpt = transformer_flops_per_token(DIMS["dim"], DIMS["depth"], L)
        if peak:
            rec["mfu"] = round(tok_s * fpt / peak, 4)
            if rec["mfu"] > 1.0 or spread > MAX_SPREAD:
                rec["invalid"] = True
                log("  INVALID: impossible mfu or wild spread")
        log(json.dumps(rec))
        return rec

    rec = trainer_leg(8, "transformer_bf16_L2048", {})
    rec["vs_handrolled"] = round(rec["tokens_per_sec"] / hand_tok_s, 3)
    # the MXU-shaped variant: same dim/depth/FLOPs, D=128 heads — the thin
    # D=64 score/AV tiles are this config's roofline (SCALING.md); wide
    # heads lift MFU ~1.6x at identical arithmetic
    rec_wide = trainer_leg(4, "transformer_bf16_L2048_wide_heads", {})
    log(json.dumps({"config": "transformer_bf16_L2048", "vs_handrolled":
                    rec["vs_handrolled"]}))
    return rec, rec_wide


def lm_train_flops_per_token(dim, depth, L, vocab):
    # matmul terms, 3× forward: per-layer qkv/attn_out/mlp (24·d²) + QKᵀ/AV
    # (4·L·d), plus the lm_head projection (2·d·V — at vocab 16k and
    # dim 1024 that's ~18% of the total, so it is counted, unlike the
    # classifier head above which is noise). Flash backward recompute and
    # elementwise ops are excluded, so MFU is slightly underestimated.
    return 3 * (depth * (24 * dim * dim + 4 * L * dim) + 2 * dim * vocab)


def run_lm_train_config(accel):
    """Config 9 (VERDICT r3 #3): the flagship TRAINING composition — a
    causal LM with flash attention + fused (chunked) cross-entropy + RoPE +
    bf16, trained THROUGH the trainer API (MeshTrainer, resident input
    path). dim 1024 / heads 8 gives D=128 head tiles (full MXU lanes); the
    fused-CE path never materializes the [B, L, 16384] logits tensor."""
    import contextlib

    import jax.numpy as jnp

    from distkeras_tpu.data import Dataset
    from distkeras_tpu.models import transformer_lm
    from distkeras_tpu.trainers import MeshTrainer

    V, L, B = 16384, 2048, 8
    DIM, HEADS, DEPTH = 1024, 8, 8
    # remat=False: at this size activations fit HBM, and the block
    # recompute would cost a measured ~27% of throughput (85.4k → 62.5k
    # tok/s); remat is the memory lever for configs that NEED it, not a
    # default tax. B=8 edges out B=16 (85.4k vs 80.9k) — the fused-CE
    # chunk loop dominates at larger B.
    spec = transformer_lm(vocab=V, maxlen=L, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.bfloat16, attn_impl="flash",
                          pos_embedding="rope", fused_ce=True, ce_chunk=512,
                          remat=False)
    # 48 steps/epoch: the per-epoch dispatch + metrics drain is a fixed
    # cost, and short epochs under-amortize it — the trainer adds no
    # per-step cost over the hand-rolled step
    steps_per_epoch = 48
    rng = np.random.default_rng(0)
    n = B * steps_per_epoch
    toks = rng.integers(0, V, size=(n, L + 1)).astype(np.int32)
    ds = Dataset({"features": toks[:, :-1], "label": toks[:, 1:]})
    trainer = MeshTrainer(
        spec, loss="sparse_softmax_cross_entropy", worker_optimizer="adam",
        learning_rate=1e-4, mesh_shape={"dp": 1}, batch_size=B,
        num_epoch=4, input_mode="resident", log_metrics=True,
    )
    with contextlib.redirect_stdout(sys.stderr):
        trainer.train(ds)
    # epoch 0 includes compile; median of the rest is the steady state
    sps = sorted(m["samples_per_sec"] for m in trainer.metrics_[1:])
    if not sps:  # num_epoch lowered to 1 would leave no steady-state epochs
        raise RuntimeError("lm_train needs >=2 epochs for a steady-state "
                           "median (epoch 0 is compile)")
    spread = (sps[-1] - sps[0]) / sps[len(sps) // 2]
    sps_med = sps[len(sps) // 2]
    tok_s = sps_med * L
    peak = peak_flops(accel)
    rec = {
        "config": "lm_train_bf16_L2048",
        "tokens_per_sec": round(tok_s, 1),
        "ms_per_step": round(1e3 * B / sps_med, 2),
        "seq_len": L, "batch": B, "dim": DIM, "heads": HEADS,
        "depth": DEPTH, "vocab": V,
        "fused_ce": True, "remat": False,
        "via": "MeshTrainer(resident)",
        "spread": round(spread, 3),
    }
    fpt = lm_train_flops_per_token(DIM, DEPTH, L, V)
    if peak:
        rec["mfu"] = round(tok_s * fpt / peak, 4)
        if rec["mfu"] > 1.0 or spread > MAX_SPREAD:
            rec["invalid"] = True
            log("  INVALID: impossible mfu or wild spread")
    log(json.dumps(rec))
    return {"lm_train_bf16_L2048": rec}


def run_lm_decode_config(accel):
    """Beyond-reference leg: KV-cached autoregressive decode throughput on
    the causal-LM family (dim 512 / 8 heads / depth 8, bf16, RoPE, flash
    prefill), one jitted prefill+scan program per config. Decode is
    KV-cache-bandwidth-bound — the cache is read end to end every step — so
    the GQA/MQA legs (kv_heads=2/1: 4x/8x smaller caches) are the
    performance configurations."""
    from distkeras_tpu.models import generate, transformer_lm

    B, PROMPT, NEW = 8, 128, 256
    out = {}
    for name, kvh, window in (
        ("lm_decode_mha", None, None),
        ("lm_decode_gqa2", 2, None),
        ("lm_decode_mqa", 1, None),
        # the other cache lever: a sliding window shrinks the cache LENGTH
        # (ring buffer of `window` slots instead of maxlen)
        ("lm_decode_win256", None, 256),
    ):
        spec = transformer_lm(vocab=8192, maxlen=2048, dim=512, heads=8,
                              depth=8, dtype=jax.numpy.bfloat16,
                              attn_impl="flash", pos_embedding="rope",
                              kv_heads=kvh, attn_window=window)
        params, _ = spec.init_np(0)
        params = jax.device_put(params, accel)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, 8192, size=(B, PROMPT)).astype(np.int32)
        # generate() materializes host tokens, i.e. a full drain; its jitted
        # prefill+scan program is lru-cached across calls, so only the first
        # call compiles
        t0 = time.perf_counter()
        generate(spec, params, prompt, NEW)
        log(f"  [{name}] compile+first decode: {time.perf_counter()-t0:.1f}s")
        ts = []
        for r in range(3):
            t0 = time.perf_counter()
            generate(spec, params, prompt, NEW, seed=r + 1)
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts))
        rec = {
            "config": name,
            "decode_tokens_per_sec": round(B * NEW / t, 1),
            "ms_per_step": round(1e3 * t / NEW, 3),
            "batch": B, "new_tokens": NEW, "kv_heads": kvh or 8,
            "window": window,
            "spread": round((max(ts) - min(ts)) / t, 3),
        }
        log(json.dumps(rec))
        out[name] = rec
    log(json.dumps({
        "config": "lm_decode_summary",
        "gqa2_vs_mha": round(out["lm_decode_gqa2"]["decode_tokens_per_sec"]
                             / out["lm_decode_mha"]["decode_tokens_per_sec"],
                             2),
        "mqa_vs_mha": round(out["lm_decode_mqa"]["decode_tokens_per_sec"]
                            / out["lm_decode_mha"]["decode_tokens_per_sec"],
                            2),
    }))
    return out


def run_lm_decode_int8(accel):
    """Int8 weight-only serving (ops/quant.py), measured where it applies:
    a 400M-param MQA decoder whose per-step bytes are WEIGHT-dominated
    (~810 MB bf16 weights vs a ~17 MB MQA cache), i.e. decode is on the
    HBM-bandwidth roofline. The dim-512 config above is per-step
    overhead-bound (~0.5 ms against an ~80 µs byte roofline), where
    halving weight bytes cannot show — measured and rejected, 0.84×; the
    quantization win needs bandwidth-bound decode, and at 400M params it
    gets one."""
    from distkeras_tpu.models import generate, quantize_lm, transformer_lm

    B, PROMPT, NEW = 8, 128, 128
    out = {}
    spec = transformer_lm(vocab=16384, maxlen=1024, dim=2048, heads=16,
                          depth=8, dtype=jax.numpy.bfloat16,
                          attn_impl="flash", pos_embedding="rope",
                          kv_heads=1)
    params, _ = spec.init_np(0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 16384, size=(B, PROMPT)).astype(np.int32)
    for name, s, p in (
        ("lm_decode_400m_bf16", spec, params),
        ("lm_decode_400m_int8", *quantize_lm(spec, params)),
    ):
        p = jax.device_put(p, accel)
        t0 = time.perf_counter()
        generate(s, p, prompt, NEW)
        log(f"  [{name}] compile+first decode: {time.perf_counter()-t0:.1f}s")
        ts = []
        for r in range(5):  # ~0.2 s each; medians ride out host hiccups
            t0 = time.perf_counter()
            generate(s, p, prompt, NEW, seed=r + 1)
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts))
        rec = {
            "config": name,
            "decode_tokens_per_sec": round(B * NEW / t, 1),
            "ms_per_step": round(1e3 * t / NEW, 3),
            "batch": B, "new_tokens": NEW,
            "spread": round((max(ts) - min(ts)) / t, 3),
        }
        log(json.dumps(rec))
        out[name] = rec
        del p
    log(json.dumps({
        "config": "lm_decode_int8_summary",
        "int8_vs_bf16_400m": round(
            out["lm_decode_400m_int8"]["decode_tokens_per_sec"]
            / out["lm_decode_400m_bf16"]["decode_tokens_per_sec"], 2),
    }))
    return out


def _greedy_consistent(spec, params, toks, prompt_len):
    """Tie-aware greedy check: is every emitted token argmax-of-its-context
    within one bf16 ulp? Saturated bf16 models produce EXACT logit ties
    (measured: a 4-way tie at 22.375 on the trained 400M cycle-language
    model), and the multi-token verify pass (`extend`) can resolve a tie
    one ulp differently than the single-token decode path — both streams
    are then legitimate greedy decodes that differ bitwise. One full
    forward over the emitted stream settles it: the emitted token's logit
    must be within a bf16 ulp of the row max at every position."""
    import jax.numpy as jnp

    logits = spec.module.apply(
        {"params": params}, jnp.asarray(toks[:, :-1])
    )
    lg = np.asarray(logits[:, prompt_len - 1:], np.float32)
    emitted = toks[:, prompt_len:]
    mx = lg.max(-1)
    got = np.take_along_axis(lg, emitted[..., None], -1)[..., 0]
    # ulp(x) for |x| in [2^e, 2^(e+1)) is 2^(e-7), so |mx|·2^-7 lies in
    # [1, 2) true ulps at every magnitude. Measured calibration on the
    # trained 400M model: the PLAIN GREEDY stream itself shows gaps up to
    # exactly one true ulp (0.125 at logit ~22) against this full-forward
    # oracle — the decode program's logits legitimately round differently
    # — and the spec stream's gap distribution matches it (56 vs 58
    # positions beyond 2^-8, max 0.125 both). A real emission bug on the
    # cycle language would gap by whole units.
    tol = np.maximum(np.abs(mx) * 2.0 ** -7, 2.0 ** -7)
    ok = got >= mx - tol
    return bool(np.all(ok)), int(np.sum(~ok))


def _check_greedy_stream(name, spec, params, toks, greedy, prompt_len):
    """Assert a speculative stream equals the plain greedy stream, falling
    back to the tie-aware check when they differ bitwise (bf16 ties)."""
    if np.array_equal(toks, greedy):
        return
    n_diff = int(np.sum(toks != greedy))
    ok, bad = _greedy_consistent(spec, params, toks, prompt_len)
    if not ok:
        raise AssertionError(
            f"{name}: {bad} emitted tokens are not argmax-within-ulp of "
            f"their context — a real divergence, not a bf16 tie"
        )
    log(f"  [{name}] stream differs from plain greedy at {n_diff} "
        f"positions but every token is argmax-within-a-bf16-ulp (logit "
        f"ties resolve differently across the decode/verify programs; "
        f"both streams are valid greedy decodes)")


def run_lm_speculative_config(accel):
    """Beyond-reference leg: greedy speculative decoding (SCALING.md
    "Speculative decoding"). Target (dim 512 / depth 8) and draft
    (dim 128 / depth 2) are TRAINED for 3 epochs on a deterministic cycle
    language so the reported acceptance is measured draft/target
    agreement, not an assumption; exact equality with the plain greedy
    stream is asserted in-run before timing."""
    import jax.numpy as jnp

    from distkeras_tpu.models import (generate, next_token_dataset,
                                      speculative_generate, transformer_lm)
    from distkeras_tpu.trainers import SingleTrainer

    # 2048 rows x 2 epochs: the cycle language saturates fast, so the
    # TARGET trains in 2 epochs (the training exec is this leg's budget
    # cost), but the tiny DRAFT gets 4 - its sampled-q quality gates the
    # sampled-spec acceptance (1024x2 measured greedy 0.947 but sampled
    # 0.43; the round-5 sweep at fuller training measured 0.62 at T=1.0)
    period, L, rows = 256, 128, 2048
    rng = np.random.default_rng(0)
    starts = rng.integers(0, period, size=(rows, 1))
    grid = (starts + np.arange(L + 1)[None]) % period
    ds = next_token_dataset(grid)

    def trained(dim, heads, depth, epochs):
        spec = transformer_lm(vocab=period, maxlen=2048, dim=dim,
                              heads=heads, depth=depth,
                              pos_embedding="rope", attn_impl="flash",
                              dtype=jnp.bfloat16)
        tr = SingleTrainer(spec, loss="sparse_softmax_cross_entropy",
                           worker_optimizer="adam", learning_rate=3e-3,
                           batch_size=64, num_epoch=epochs)
        tr.train(ds, shuffle=True)
        return spec, jax.device_put(tr.trained_params_, accel)

    t0 = time.perf_counter()
    target, tparams = trained(512, 8, 8, 2)
    draft, dparams = trained(128, 4, 2, 4)
    log(f"  [lm_spec] trained target+draft in {time.perf_counter()-t0:.0f}s")

    B, LP, NEW = 8, 64, 1024
    prompt = ((np.arange(LP)[None] + rng.integers(0, period, (B, 1)))
              % period).astype(np.int32)
    greedy = generate(target, tparams, prompt, max_new_tokens=NEW)

    def med3(fn):
        # callers pre-warm: the greedy-reference / equality-check call of
        # each program has already compiled and executed it
        ts = []
        for _ in range(3):
            t1 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t1)
        return float(np.median(ts)), ts

    t_plain, ts = med3(
        lambda: generate(target, tparams, prompt, max_new_tokens=NEW)
    )
    out = {"lm_spec_plain": {
        "config": "lm_spec_plain",
        "decode_tokens_per_sec": round(B * NEW / t_plain, 1),
        "batch": B, "new_tokens": NEW,
        "spread": round((max(ts) - min(ts)) / t_plain, 3),
    }}
    log(json.dumps(out["lm_spec_plain"]))
    for K in (4, 8):
        toks, stats = speculative_generate(
            target, tparams, draft, dparams, prompt, NEW, spec_tokens=K
        )
        _check_greedy_stream(f"lm_spec_k{K}", target, tparams, toks,
                             greedy, LP)
        t_spec, ts = med3(lambda: speculative_generate(
            target, tparams, draft, dparams, prompt, NEW, spec_tokens=K
        )[0])
        rec = {
            "config": f"lm_spec_k{K}",
            "decode_tokens_per_sec": round(B * NEW / t_spec, 1),
            "acceptance": round(stats["acceptance"], 3),
            "verify_rounds": stats["rounds"],
            "speedup_vs_plain": round(t_plain / t_spec, 2),
            "batch": B, "new_tokens": NEW,
            "spread": round((max(ts) - min(ts)) / t_spec, 3),
        }
        log(json.dumps(rec))
        out[f"lm_spec_k{K}"] = rec

    # SAMPLED speculative (VERDICT r4 #3: round 4 shipped the Leviathan §3
    # rejection-sampling scheme with no perf leg anywhere): temperature
    # 1.0 + top-k 64, K=8, against plain sampled generate at identical
    # warp settings. The emitted distribution is exactly p (pinned by the
    # TV-distance test gate in tests/test_generation.py); acceptance is the
    # measured per-row draft/target agreement under sampling.
    TEMP, TOPK, K = 1.0, 64, 8
    t0 = time.perf_counter()
    generate(target, tparams, prompt, NEW, temperature=TEMP, top_k=TOPK)
    log(f"  [lm_spec_sampled] plain-sampled compile: "
        f"{time.perf_counter()-t0:.1f}s")
    t_plain_s, ts = med3(lambda: generate(
        target, tparams, prompt, NEW, temperature=TEMP, top_k=TOPK))
    out["lm_spec_sampled_plain"] = {
        "config": "lm_spec_sampled_plain",
        "decode_tokens_per_sec": round(B * NEW / t_plain_s, 1),
        "temperature": TEMP, "top_k": TOPK,
        "batch": B, "new_tokens": NEW,
        "spread": round((max(ts) - min(ts)) / t_plain_s, 3),
    }
    log(json.dumps(out["lm_spec_sampled_plain"]))
    t0 = time.perf_counter()
    _, stats = speculative_generate(
        target, tparams, draft, dparams, prompt, NEW, spec_tokens=K,
        temperature=TEMP, top_k=TOPK)
    log(f"  [lm_spec_sampled] spec compile: {time.perf_counter()-t0:.1f}s")
    t_spec_s, ts = med3(lambda: speculative_generate(
        target, tparams, draft, dparams, prompt, NEW, spec_tokens=K,
        temperature=TEMP, top_k=TOPK)[0])
    rec = {
        "config": f"lm_spec_sampled_k{K}",
        "decode_tokens_per_sec": round(B * NEW / t_spec_s, 1),
        "acceptance": round(stats["acceptance"], 3),
        "verify_rounds": stats["rounds"],
        "speedup_vs_plain_sampled": round(t_plain_s / t_spec_s, 2),
        "temperature": TEMP, "top_k": TOPK,
        "batch": B, "new_tokens": NEW,
        "spread": round((max(ts) - min(ts)) / t_spec_s, 3),
    }
    log(json.dumps(rec))
    out[f"lm_spec_sampled_k{K}"] = rec
    return out


def run_composed_decode_config(accel):
    """Config 10 (VERDICT r3 #7): the decode levers COMPOSED on one model —
    a 400M-param MQA target (the weight-bandwidth-bound regime where int8
    showed 1.36-1.62×) with int8 quantization and speculative decoding
    stacked, against the same model's plain bf16 greedy decode. Answers
    whether the separately-benchmarked wins multiply or saturate: spec
    multiplies target passes down, int8 cheapens each pass, and both legs'
    outputs are pinned to their own greedy stream before timing. The target
    and draft are TRAINED on the deterministic cycle language so acceptance
    is measured agreement, not an assumption."""
    import jax.numpy as jnp

    from distkeras_tpu.models import (generate, next_token_dataset,
                                      quantize_lm, speculative_generate,
                                      transformer_lm)
    from distkeras_tpu.trainers import SingleTrainer

    period, L, rows = 256, 128, 1024
    rng = np.random.default_rng(0)
    starts = rng.integers(0, period, size=(rows, 1))
    grid = (starts + np.arange(L + 1)[None]) % period
    ds = next_token_dataset(grid)

    def trained(name, lr, **kw):
        # reference (XLA) attention for the short-L training pass: at
        # L=128 the flash kernels buy nothing and their fwd+bwd compiles
        # dominated this leg's wall time; decode throughput below is
        # cache-step-bound and attn_impl-independent
        spec = transformer_lm(vocab=16384, maxlen=1024,
                              pos_embedding="rope", dtype=jnp.bfloat16,
                              **kw)
        tr = SingleTrainer(spec, loss="sparse_softmax_cross_entropy",
                           worker_optimizer="adam", learning_rate=lr,
                           batch_size=64, num_epoch=2)
        t0 = time.perf_counter()
        tr.train(ds, shuffle=True)
        log(f"  [composed] trained {name} in {time.perf_counter()-t0:.0f}s")
        return spec, jax.device_put(tr.trained_params_, accel)

    # ~400M params: the config 7b model, MQA cache. lr 3e-4: the dim-512
    # models train fine at 3e-3, but the 400M target COLLAPSES there
    # (greedy stream oscillated instead of following the cycle, measured
    # acceptance 0.001); at 3e-4 it follows the cycle 100% and the pair
    # measures acceptance 0.98.
    target, tparams = trained("400M target", 3e-4, dim=2048, heads=16,
                              depth=8, kv_heads=1)
    draft, dparams = trained("draft", 3e-3, dim=128, heads=4, depth=2)
    target_q, tparams_q = quantize_lm(target, tparams)
    draft_q, dparams_q = quantize_lm(draft, dparams)

    B, LP, NEW, K = 8, 64, 256, 8
    prompt = ((np.arange(LP)[None] + rng.integers(0, period, (B, 1)))
              % period).astype(np.int32)

    def med3(fn):
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), ts

    out = {}

    def time_leg(name, fn, oracle=None, oracle_model=None, stats=None):
        t0 = time.perf_counter()
        toks = fn()
        log(f"  [{name}] compile+first decode: {time.perf_counter()-t0:.1f}s")
        if oracle is not None:
            _check_greedy_stream(name, *oracle_model, toks, oracle, LP)
        t, ts = med3(fn)
        rec = {
            "config": name,
            "decode_tokens_per_sec": round(B * NEW / t, 1),
            "ms_per_step": round(1e3 * t / NEW, 3),
            "batch": B, "new_tokens": NEW,
            "spread": round((max(ts) - min(ts)) / t, 3),
        }
        if stats is not None:
            rec["acceptance"] = round(stats["acceptance"], 3)
        log(json.dumps(rec))
        out[name] = rec
        return toks, rec

    greedy_bf16, base = time_leg(
        "composed_400m_bf16",
        lambda: generate(target, tparams, prompt, NEW))
    # int8's greedy stream is its own oracle (quantization legitimately
    # changes logits; spec decode must preserve whichever model it serves)
    greedy_int8, rec_i = time_leg(
        "composed_400m_int8",
        lambda: generate(target_q, tparams_q, prompt, NEW))
    _, stats_s = speculative_generate(target, tparams, draft, dparams,
                                      prompt, NEW, spec_tokens=K)
    _, rec_s = time_leg(
        "composed_400m_spec_k8",
        lambda: speculative_generate(target, tparams, draft, dparams,
                                     prompt, NEW, spec_tokens=K)[0],
        oracle=greedy_bf16, oracle_model=(target, tparams), stats=stats_s)
    _, stats_si = speculative_generate(target_q, tparams_q, draft_q,
                                       dparams_q, prompt, NEW, spec_tokens=K)
    _, rec_si = time_leg(
        "composed_400m_int8_spec_k8",
        lambda: speculative_generate(target_q, tparams_q, draft_q, dparams_q,
                                     prompt, NEW, spec_tokens=K)[0],
        oracle=greedy_int8, oracle_model=(target_q, tparams_q),
        stats=stats_si)

    base_tps = base["decode_tokens_per_sec"]
    summary = {
        "config": "composed_serving_summary",
        "int8_vs_bf16": round(rec_i["decode_tokens_per_sec"] / base_tps, 2),
        "spec_vs_bf16": round(rec_s["decode_tokens_per_sec"] / base_tps, 2),
        "int8_spec_vs_bf16": round(
            rec_si["decode_tokens_per_sec"] / base_tps, 2),
        "product_of_parts": round(
            rec_i["decode_tokens_per_sec"] * rec_s["decode_tokens_per_sec"]
            / (base_tps * base_tps), 2),
    }
    log(json.dumps(summary))
    out["composed_serving_summary"] = summary
    return out


def run_time_to_accuracy(accel, target=0.99, max_epochs=20):
    """BASELINE primary metric: wall-clock to `target` test accuracy on the
    north-star config (ADAG/LeNet), training time only (eval excluded),
    compile/warm excluded (steady-state TPU time — compile is a one-off)."""
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.datasets import mnist
    from distkeras_tpu.models import lenet
    from distkeras_tpu.ops.losses import sparse_softmax_cross_entropy
    from distkeras_tpu.parallel.local_sgd import LocalSGDEngine
    from distkeras_tpu.parallel.merge_rules import ADAGMerge
    from distkeras_tpu.parallel.mesh import get_mesh

    on_tpu = accel.platform == "tpu"
    rows, batch, window = (16384, 256, 8) if on_tpu else (768, 64, 3)
    train, test = mnist(n_train=rows, n_test=2048)
    spec = lenet(dtype=jnp.bfloat16 if on_tpu else jnp.float32)

    def loss_step(params, nt, b):
        x, y = b
        out, new_nt = spec.apply(params, nt, x, training=True)
        return sparse_softmax_cross_entropy(y, out), new_nt

    mesh = get_mesh(1, devices=[accel])
    engine = LocalSGDEngine(spec, loss_step, optax.adam(1e-3), ADAGMerge(),
                            mesh, num_workers=1, window=window,
                            batch_size=batch)
    params, nt = spec.init_np(0)
    state = engine.init_state(params, nt)
    staged = engine.stage_dataset(
        train.worker_shards(1, batch, window, ["features", "label"])
    )
    xt = jax.device_put(test["features"], accel)
    nt0 = lambda s: jax.tree.map(lambda x: x[0], s.nt)
    fwd = jax.jit(lambda p, n, x: spec.apply(p, n, x, False)[0])

    # compile both programs outside the clock, then restart from fresh weights
    state, _ = engine.run_epoch_resident(state, staged, 0)
    jax.block_until_ready(fwd(state.center, nt0(state), xt))
    state = engine.init_state(*spec.init_np(0))

    train_time, acc = 0.0, 0.0
    for epoch in range(max_epochs):
        t0 = time.perf_counter()
        state, losses = engine.run_epoch_resident(state, staged, epoch + 1)
        jax.block_until_ready(state.center)
        # host fetch is the sync point (see measure()): without it the
        # epoch's compute could be timed into the eval below and
        # train_time understated
        float(np.asarray(losses[-1]))
        train_time += time.perf_counter() - t0
        out = fwd(state.center, nt0(state), xt)
        acc = float(np.mean(np.argmax(np.asarray(out), -1) == test["label"]))
        log(f"  epoch {epoch}: test acc {acc:.4f} "
            f"(cumulative train {train_time:.3f}s)")
        if acc >= target:
            break
    rec = {
        "metric": "time_to_accuracy",
        "target": target,
        "reached": round(acc, 4),
        "reached_target": bool(acc >= target),  # unrounded comparison
        "epochs": epoch + 1,
        "train_seconds": round(train_time, 3),
    }
    log(json.dumps(rec))
    return rec


def run_scaling(accel):
    """Stacked-worker scaling on ONE chip: W replicas time-share the device.

    This is the honest single-chip substitute for a chip-scaling curve (no
    multi-chip hardware here): it shows the engine keeps the MXU busy as the
    worker dimension grows — per-worker batch is held constant, so total work
    scales with W.
    """
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.datasets import mnist
    from distkeras_tpu.models import lenet
    from distkeras_tpu.parallel.merge_rules import ADAGMerge

    on_tpu = accel.platform == "tpu"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    rows_pw, batch = (32768, 128) if on_tpu else (512, 32)
    out = {}
    for W in (1, 2, 4, 8):
        # big enough shards (32 windows/worker/epoch) that the epoch is
        # compute-bound, not dispatch-bound
        train, _ = mnist(n_train=rows_pw * W, n_test=64)
        log(f"[scaling] ADAG/LeNet W={W} (stacked on one {accel.platform})")
        sps, spread, distinct = measure(
            accel, lenet(dtype=dt), ADAGMerge(), optax.adam(1e-3), train,
            ["features", "label"], batch_size=batch, window=4,
            num_workers=W, epochs_timed=3 if on_tpu else 1)
        out[W] = sps
        rec = {"scaling_w": W, "samples_per_sec": round(sps, 1),
               "spread": round(spread, 3)}
        if spread > MAX_SPREAD or not distinct:
            rec["invalid"] = True  # same gate as every other leg
        log(json.dumps(rec))
    base = out[1]
    for W, sps in out.items():
        log(f"[scaling] W={W}: {sps:,.0f} samples/sec "
            f"({sps / base:.2f}× W=1)")
    return out


# ---------------------------------------------------------------------------
# Parameter-server hot-path microbenchmark (--ps-bench): N worker threads
# hammering pull/commit against an in-process and a socket PS, compressed
# and raw. This is the measurement behind the PS decontending work: the
# center lock's critical sections must stay O(fold), and compressed pulls
# must scale past the old serialize-everything-behind-one-lock number.
# ---------------------------------------------------------------------------


def _ps_bench_tree(n_params):
    """A ~n_params float32 tree shaped like a real model: one embedding-
    sized leaf plus smaller dense leaves."""
    rng = np.random.default_rng(0)
    big = n_params - n_params // 8 - n_params // 64
    return {
        "emb": rng.normal(size=(big,)).astype(np.float32),
        "dense": {
            "w": rng.normal(size=(n_params // 8,)).astype(np.float32),
            "b": rng.normal(size=(n_params // 64,)).astype(np.float32),
        },
    }


def _ps_bench_phase(clients, op, seconds):
    """Run `op(client, i)` in one thread per client for ~`seconds`;
    returns (total_ops, elapsed). A worker error propagates."""
    import threading

    counts = [0] * len(clients)
    errors = []
    stop = threading.Event()

    def worker(i):
        try:
            while not stop.is_set():
                op(clients[i], i)
                counts[i] += 1
        except BaseException as e:  # pragma: no cover - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(len(clients))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    stop.wait(seconds)
    stop.set()
    for t in threads:
        t.join(timeout=120)
    if errors:
        raise errors[0]
    return sum(counts), time.perf_counter() - t0


def run_ps_microbench(n_params=10_000_000, workers=4, seconds=4.0,
                      transports=("inprocess", "socket")):
    """PS throughput microbenchmark: per (transport, compression) leg,
    three phases — pull-only, commit-only, then a mixed pull+commit hammer
    — each with `workers` threads against one server holding a ~n_params
    float32 tree. Pull rates include the client-side decode (that is what
    a worker pays per pull); per-phase isolation keeps each op's rate
    interpretable on its own. Emits one stderr JSON record per leg with
    the server's ps.stats() contention counters (mean center-lock hold ns
    is the O(fold) criticial-section check) and returns {leg: record}."""
    from distkeras_tpu.parallel.merge_rules import DownpourMerge
    from distkeras_tpu.parameter_servers import (
        ParameterServer,
        ParameterServerClient,
        SocketParameterServer,
    )
    from distkeras_tpu.workers import _BoundPS

    center = _ps_bench_tree(n_params)
    delta = {
        "emb": np.full_like(center["emb"], 1e-6),
        "dense": {"w": np.full_like(center["dense"]["w"], 1e-6),
                  "b": np.full_like(center["dense"]["b"], 1e-6)},
    }
    out = {}
    for transport in transports:
        for comp in (None, "int8"):
            name = f"ps_{transport}_{comp or 'raw'}"
            log(f"[ps-bench] {name}: {workers} workers, "
                f"{n_params / 1e6:.0f}M params")
            if transport == "inprocess":
                ps = ParameterServer(center, DownpourMerge(), workers)
                clients = [_BoundPS(ps, i, pull_compression=comp)
                           for i in range(workers)]
            else:
                ps = SocketParameterServer(center, DownpourMerge(), workers)
                ps.initialize()
                ps.start()
                clients = [
                    ParameterServerClient("127.0.0.1", ps.port, i,
                                          pull_compression=comp)
                    for i in range(workers)
                ]
            try:
                # socket pulls decode in the client; in-process int8 pulls
                # decode inside _BoundPS.pull — raw _BoundPS pulls return
                # the copy directly, nothing extra to do
                pulls, t_pull = _ps_bench_phase(
                    clients, lambda c, i: c.pull(), seconds)
                commits, t_commit = _ps_bench_phase(
                    clients, lambda c, i: c.commit(i, delta), seconds)
                mixed, t_mixed = _ps_bench_phase(
                    clients,
                    lambda c, i: (c.pull(), c.commit(i, delta)), seconds)
                rec = {
                    "config": name,
                    "workers": workers,
                    "params": n_params,
                    "pulls_per_sec": round(pulls / t_pull, 2),
                    "commits_per_sec": round(commits / t_commit, 2),
                    "mixed_rounds_per_sec": round(mixed / t_mixed, 2),
                }
                if hasattr(ps, "stats"):  # absent on pre-refactor servers
                    s = ps.stats()
                    rec["center_lock_mean_hold_ns"] = \
                        s["center_lock_mean_hold_ns"]
                    rec["center_lock_wait_ns"] = s["center_lock_wait_ns"]
                    rec["bytes_out"] = s["bytes_out"]
                    rec["bytes_in"] = s["bytes_in"]
                log(json.dumps(rec))
                out[name] = rec
            finally:
                for c in clients:
                    c.close()
                ps.stop()
    return out


def run_ps_shard_bench(n_params=10_000_000, workers=4, seconds=4.0,
                       shard_counts=(1, 2, 4),
                       transports=("socket", "native")):
    """Sharded-center scaling legs (ISSUE 8): the pull/commit hammer
    against an N-shard consistent-hash group (``distkeras_tpu/sharding``)
    for N in ``shard_counts``, socket and native transports. Each leg
    reports AGGREGATE pull and commit throughput (rounds crossing the
    whole group; every op touches every shard) plus the per-shard byte
    balance — the scaling claim is commit throughput growing with N,
    because each shard folds 1/N of the bytes behind its own lock/GIL-
    free mutex.

    Host-ceiling accounting (the PR 6/7 treatment): on a 1-core CI host
    the N shard folds serialize on the one core, so the curve flattens —
    ``host_cores`` rides every record and the structural claim lives in
    ``bytes_per_commit_per_shard`` shrinking with N. Multi-core hosts
    (and the real DCN topology, one shard per host) are the scaling
    regime."""
    import os as _os

    import jax as _jax

    from distkeras_tpu.parallel.merge_rules import DownpourMerge
    from distkeras_tpu.sharding import ShardedPSGroup

    # a transformer-shaped tree — many similar-sized block leaves — not
    # the embedding-dominated microbench tree: one leaf holding 6/7 of
    # the bytes caps sharded speedup at ~7/6 no matter how many shards
    # (that leaf's shard is the critical path), which would measure the
    # tree's skew, not the architecture. Real sharded-PS workloads are
    # the many-blocks regime; the ring's bounded-load balance test covers
    # the skewed case.
    rng = np.random.default_rng(0)
    n_layers = 16
    per = max(1, n_params // n_layers)
    center = {
        f"layer_{i:02d}": rng.normal(size=(per,)).astype(np.float32)
        for i in range(n_layers)
    }
    delta = _jax.tree.map(lambda l: np.full_like(l, 1e-6), center)
    host_cores = _os.cpu_count() or 1
    out = {}
    for transport in transports:
        if transport == "native":
            from distkeras_tpu.native import load_dkps

            if load_dkps(required=False) is None:
                log("[ps-shard] native transport unavailable (no g++); "
                    "leg skipped")
                continue
        for n_shards in shard_counts:
            name = f"ps_shard_{transport}_n{n_shards}"
            log(f"[ps-shard] {name}: {workers} workers, "
                f"{n_params / 1e6:.0f}M params, {n_shards} shards")
            group = ShardedPSGroup(center, DownpourMerge(), workers,
                                   num_shards=n_shards, transport=transport)
            group.initialize()
            group.start()
            clients = [group.make_client(i) for i in range(workers)]
            try:
                pulls, t_pull = _ps_bench_phase(
                    clients, lambda c, i: c.pull(), seconds)
                commits, t_commit = _ps_bench_phase(
                    clients, lambda c, i: c.commit(i, delta), seconds)
                s = group.stats()
                rec = {
                    "config": name,
                    "workers": workers,
                    "params": n_params,
                    "num_shards": n_shards,
                    "pulls_per_sec": round(pulls / t_pull, 2),
                    "commits_per_sec": round(commits / t_commit, 2),
                    # per-shard fold cost: the quantity sharding divides
                    "bytes_per_commit_per_shard": int(
                        max(group.plan.shard_nbytes)
                    ),
                    "shard_nbytes": list(group.plan.shard_nbytes),
                    "center_lock_mean_hold_ns":
                        s["center_lock_mean_hold_ns"],
                    "ring": group.plan.digest[:12],
                    # host-ceiling accounting: N folds serialize on a
                    # 1-core host — the scaling regime needs >= N cores
                    "host_cores": host_cores,
                }
                log(json.dumps(rec))
                out[name] = rec
            finally:
                for c in clients:
                    try:
                        c.close()
                    except OSError:
                        pass
                group.stop()
    return out


def run_ps_exchange_bench(n_params=1_000_000, workers=(2, 4), seconds=2.0,
                          transports=("socket", "native", "shm"),
                          compute_ms=3.0, per_round_extra_s=0.0):
    """Exchange-leg microbenchmark (ISSUE 10 + 12): serial (``commit();
    pull()`` — 2 RTTs) vs fused (one EXCHANGE RTT) vs fused+pipelined
    (the exchange overlapped with the NEXT window's simulated device
    compute) rounds/s, per transport and worker count. ISSUE 12 grows
    the grid a third transport — ``shm``, the zero-syscall mmap ring
    lane for the colocated regime — and the batched-fold columns: every
    leg reports ``batched_folds`` and the measured center-lock
    acquisitions per round (< 1.0 during the fused phase means folds
    rode shared lock sections; the native lane's C++ fold path is
    per-commit, so it honestly reports 0 / 1.0).

    Each "round" is one training window's exchange plus ``compute_ms``
    of simulated device time — ``time.sleep``, which is faithful to a
    real accelerator window: the device computes without consuming host
    CPU, exactly the gap the pipelined loop hides host work inside. The
    pipelined leg runs the sleep on a per-worker single-thread "device"
    executor and exchanges concurrently, so its round costs
    ~max(compute, exchange) instead of their sum.

    Counter oracle per leg (asserted by the test contract, recorded
    here): during the serial phase the server's ``exchange_rtts`` grows
    by 2 per round; during the fused phases by exactly 1 per round
    (``fused_exchanges`` == rounds) — the 2→1 wire-cost claim read
    straight off ``ps.stats()``. ``host_cores`` rides the record
    (PR 6/7/8 honesty treatment): the fold itself still serializes on a
    1-core host, but the overlap claim targets wire+encode latency, not
    fold CPU.

    ``per_round_extra_s`` injects a REAL sleep into every exchange op —
    the perf-regression guard's self-test seam (ISSUE 13): ``bench.py
    --regress --regress-slowdown X`` measures a genuinely slowed leg
    and must flag it against the clean baseline (the same role
    ``FaultPlan`` plays for the chaos tests: measured, not mocked)."""
    import os as _os
    from concurrent.futures import ThreadPoolExecutor

    from distkeras_tpu.parallel.merge_rules import DownpourMerge
    from distkeras_tpu.parameter_servers import (
        ParameterServerClient,
        SocketParameterServer,
    )

    center = _ps_bench_tree(n_params)
    delta = {
        "emb": np.full_like(center["emb"], 1e-6),
        "dense": {"w": np.full_like(center["dense"]["w"], 1e-6),
                  "b": np.full_like(center["dense"]["b"], 1e-6)},
    }
    host_cores = _os.cpu_count() or 1
    compute_s = compute_ms / 1e3
    out = {}
    for transport in transports:
        if transport == "native":
            from distkeras_tpu.native import load_dkps

            if load_dkps(required=False) is None:
                log("[ps-exchange] native transport unavailable "
                    "(no g++); leg skipped")
                continue
        for W in workers:
            name = f"ps_exchange_{transport}_w{W}"
            log(f"[ps-exchange] {name}: {W} workers, "
                f"{n_params / 1e6:.1f}M params, compute {compute_ms}ms")
            if transport == "native":
                from distkeras_tpu.native_ps import (
                    NativePSClient,
                    NativeSocketParameterServer,
                )

                ps = NativeSocketParameterServer(center, DownpourMerge(), W)
                ps.initialize()
                ps.start()
                clients = [NativePSClient("127.0.0.1", ps.port, i, ps.spec)
                           for i in range(W)]
            elif transport == "shm":
                from distkeras_tpu.shm import (
                    ShmParameterServer,
                    ShmPSClient,
                )

                ps = ShmParameterServer(center, DownpourMerge(), W)
                ps.initialize()
                ps.start()
                clients = [ShmPSClient(ps, i) for i in range(W)]
            else:
                ps = SocketParameterServer(center, DownpourMerge(), W)
                ps.initialize()
                ps.start()
                clients = [
                    ParameterServerClient("127.0.0.1", ps.port, i)
                    for i in range(W)
                ]
            devices = [ThreadPoolExecutor(1) for _ in range(W)]
            try:
                for c in clients:
                    c.pull()  # prime the staleness bookkeeping

                extra_s = float(per_round_extra_s)

                def serial_op(c, i):
                    time.sleep(compute_s)      # the "device" window
                    if extra_s:
                        time.sleep(extra_s)    # --regress slowdown seam
                    c.commit(i, delta)         # RTT 1
                    c.pull()                   # RTT 2

                def fused_op(c, i):
                    time.sleep(compute_s)
                    if extra_s:
                        time.sleep(extra_s)
                    c.exchange(i, delta)       # ONE RTT

                def pipelined_op(c, i):
                    # launch the next window on the "device", exchange
                    # the previous one while it runs — the depth-1 loop
                    fut = devices[i].submit(time.sleep, compute_s)
                    if extra_s:
                        time.sleep(extra_s)
                    c.exchange(i, delta, lag=True)
                    fut.result()

                s0 = ps.stats()
                serial, t_serial = _ps_bench_phase(
                    clients, serial_op, seconds)
                s1 = ps.stats()
                fused, t_fused = _ps_bench_phase(clients, fused_op, seconds)
                s2 = ps.stats()
                piped, t_piped = _ps_bench_phase(
                    clients, pipelined_op, seconds)
                s3 = ps.stats()
                serial_rps = serial / t_serial
                fused_rps = fused / t_fused
                piped_rps = piped / t_piped
                rec = {
                    "config": name,
                    "workers": W,
                    "params": n_params,
                    "compute_ms": compute_ms,
                    "serial_rounds_per_sec": round(serial_rps, 2),
                    "fused_rounds_per_sec": round(fused_rps, 2),
                    "pipelined_rounds_per_sec": round(piped_rps, 2),
                    "speedup_fused_vs_serial": round(
                        fused_rps / serial_rps, 3),
                    "speedup_pipelined_vs_serial": round(
                        piped_rps / serial_rps, 3),
                    # the RTT oracle, measured not asserted: 2 wire round
                    # trips per serial round, 1 per fused round
                    "serial_rtts_per_round": round(
                        (s1["exchange_rtts"] - s0["exchange_rtts"])
                        / max(serial, 1), 3),
                    "fused_rtts_per_round": round(
                        (s2["exchange_rtts"] - s1["exchange_rtts"])
                        / max(fused, 1), 3),
                    "fused_exchanges": (s3["fused_exchanges"]
                                        - s1["fused_exchanges"]),
                    # batched local exchange (ISSUE 12): folds that rode
                    # a shared center-lock acquisition during the fused
                    # phase, and the measured acquisitions per round —
                    # < 1.0 is the lock-amortization claim (one round ==
                    # one worker exchange; without batching every fold
                    # acquires once). Native reports 0 / ~1.0: its C++
                    # fold path is per-commit by design.
                    "batched_folds": (s3["batched_folds"]
                                      - s1["batched_folds"]),
                    "fused_lock_acquires_per_round": round(
                        (s2["center_lock_acquires"]
                         - s1["center_lock_acquires"]) / max(fused, 1),
                        3),
                    "host_cores": host_cores,
                }
                log(json.dumps(rec))
                out[name] = rec
            finally:
                for c in clients:
                    try:
                        c.close()
                    except OSError:
                        pass
                for d in devices:
                    d.shutdown(wait=False)
                ps.stop()
    # the ISSUE 12 acceptance ratio, recorded honestly per worker count:
    # the shm lane's rounds/s over the socket lane's, serial AND fused
    # (>= 1.5x is the colocated-regime target on this host)
    for W in workers:
        shm_rec = out.get(f"ps_exchange_shm_w{W}")
        sock_rec = out.get(f"ps_exchange_socket_w{W}")
        if shm_rec and sock_rec:
            for leg in ("serial", "fused", "pipelined"):
                base = sock_rec[f"{leg}_rounds_per_sec"]
                shm_rec[f"shm_vs_socket_{leg}"] = (
                    round(shm_rec[f"{leg}_rounds_per_sec"] / base, 3)
                    if base else 0.0
                )
            log(json.dumps({
                "config": f"ps_exchange_shm_vs_socket_w{W}",
                **{k: shm_rec[k] for k in shm_rec
                   if k.startswith("shm_vs_socket_")},
            }))
    return out


# ---------------------------------------------------------------------------
# --regress: the perf-regression guard (ISSUE 13) — turn the write-only
# BENCH_*.json trajectory into an enforced contract
# ---------------------------------------------------------------------------

#: record keys that are identity/shape, never performance
_REGRESS_SKIP_KEYS = frozenset({
    "config", "metric", "unit", "workers", "params", "batch",
    "batch_size", "host_cores", "seq_len", "dim", "heads", "depth",
    "vocab", "new_tokens", "kv_heads", "window", "compute_ms", "epochs",
    "num_workers", "trace_path", "invalid", "via", "fused_ce", "remat",
    "n", "epoch", "target", "reached_target",
})


def metric_direction(key, record=None):
    """Which way is better for this metric key: ``"higher"``,
    ``"lower"``, or ``None`` (not a performance metric — skipped). The
    trajectory's ``value`` headline counts as a rate only when its
    record says so (``unit`` contains ``/sec``)."""
    k = str(key).lower()
    if k in _REGRESS_SKIP_KEYS:
        return None
    if k == "value":
        unit = str((record or {}).get("unit", ""))
        return "higher" if "/sec" in unit else None
    if ("per_sec" in k or k.endswith("_rps") or k.startswith("speedup")
            or k in ("mfu", "spread", "acceptance", "spec_acceptance",
                     "bound_fraction", "host_ceiling_x")):
        # spread/acceptance-style ratios: bigger is better or neutral —
        # judged higher-better so a collapse is visible
        return "higher"
    if (k.endswith(("_ms", "_seconds", "_s")) or k.startswith("ms_")
            or k in ("ms_per_step", "wall_time", "tta_99_seconds")):
        return "lower"
    return None


def load_trajectory(glob_pat="BENCH_*.json", root="."):
    """Parse the checked-in BENCH_*.json trajectory into a flat record
    list. Each trajectory file is a driver capture ``{"parsed": <last
    stdout JSON>, "tail": <stdout/stderr tail>, ...}`` — every JSON
    object line in the tail is a per-config record too, so one capture
    contributes the whole visible history, not just the headline.
    Records flagged ``invalid`` are dropped (they flagged themselves)."""
    import glob as _glob

    records = []
    files = sorted(_glob.glob(os.path.join(root, glob_pat)))
    for path in files:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        seen = set()
        cands = []
        if isinstance(doc.get("parsed"), dict):
            cands.append(doc["parsed"])
        for line in str(doc.get("tail", "")).splitlines():
            line = line.strip()
            if line.startswith("{") and line.endswith("}"):
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict):
                    cands.append(rec)
        for rec in cands:
            ident = json.dumps(rec, sort_keys=True)
            if ident in seen:
                continue  # parsed usually repeats the last tail line
            seen.add(ident)
            if rec.get("invalid"):
                continue
            rec = dict(rec)
            rec["_file"] = os.path.basename(path)
            records.append(rec)
    return files, records


def _record_config(rec):
    return rec.get("config") or rec.get("metric")


def compare_to_trajectory(current_records, baseline_records,
                          rel_slack=0.12, spread_mult=3.0,
                          min_samples=2, host_cores=None):
    """Noise-aware comparison of freshly measured records against a
    trajectory. For every performance metric on every current record,
    the baseline pool is the trajectory records with the SAME config
    and a compatible ``host_cores`` (a number measured on a different
    core count is not a baseline — the PR 6-12 honesty rule); the
    verdict is against ``median(pool)`` with a tolerance of
    ``max(rel_slack × |median|, spread_mult × MAD)`` — the measured
    spread decides how much regression is noise. Metrics without
    ``min_samples`` baselines report ``no_baseline`` (the trajectory
    starts HERE — the next run has a contract), never a failure."""
    checks = []
    for cur in current_records:
        cfg = _record_config(cur)
        if cfg is None:
            continue
        pool = [r for r in baseline_records if _record_config(r) == cfg]
        for key in sorted(cur):
            direction = metric_direction(key, cur)
            if direction is None:
                continue
            val = cur.get(key)
            if not isinstance(val, (int, float)):
                continue
            samples, host_skipped = [], 0
            for r in pool:
                s = r.get(key)
                if not isinstance(s, (int, float)):
                    continue
                hc = r.get("host_cores")
                if (host_cores is not None and hc is not None
                        and int(hc) != int(host_cores)):
                    host_skipped += 1
                    continue
                samples.append(float(s))
            check = {"config": cfg, "key": key, "direction": direction,
                     "current": float(val), "n_baseline": len(samples),
                     "host_skipped": host_skipped}
            if len(samples) < min_samples:
                check["status"] = "no_baseline"
                checks.append(check)
                continue
            med = float(np.median(samples))
            mad = float(np.median(np.abs(np.asarray(samples) - med)))
            tol = max(rel_slack * abs(med), spread_mult * mad)
            delta = (float(val) - med if direction == "higher"
                     else med - float(val))   # negative == worse
            check.update({
                "baseline_median": med, "baseline_mad": mad,
                "tolerance": tol,
                "delta_frac": (float(val) - med) / med if med else 0.0,
            })
            check["status"] = ("regression" if delta < -tol else "ok")
            checks.append(check)
    n_reg = sum(1 for c in checks if c["status"] == "regression")
    return {
        "checks": checks,
        "regressions": n_reg,
        "verdict": "regression" if n_reg else "ok",
    }


def run_regress_bench(repeats=2, seconds=1.0, n_params=200_000,
                      compute_ms=3.0, slowdown=0.0,
                      glob_pat="BENCH_*.json", root=".",
                      rel_slack=0.12, spread_mult=3.0):
    """``--regress``: measure the exchange leg now, compare against the
    BENCH_*.json trajectory + this invocation's own clean repeats, and
    return a verdict record (the stdout blob; CI fails the build on
    ``verdict != "ok"``).

    The baseline pool is trajectory history PLUS ``repeats`` fresh clean
    runs (their run-to-run spread measured, not assumed). An EMPTY
    trajectory is an error, not a pass: with no history the guard would
    only ever compare a run against itself.
    ``slowdown`` (the self-test seam) injects a real per-round sleep of
    that fraction of the clean fused round time into the FINAL measured
    run only: ``--regress-slowdown 0.25`` must come back flagged, and
    an unmodified HEAD must come back ``ok``."""
    import os as _os

    host_cores = _os.cpu_count() or 1
    files, trajectory = load_trajectory(glob_pat, root)
    log(f"[regress] trajectory: {len(trajectory)} records from "
        f"{len(files)} files ({glob_pat})")
    if not trajectory:
        # a guard with nothing to guard against must not report "ok"
        raise FileNotFoundError(
            f"--regress: empty trajectory — no usable record in "
            f"{len(files)} file(s) matching {glob_pat!r} under {root!r}; "
            f"there is no history to compare against"
        )

    def one_exchange_run(extra_s=0.0):
        out = run_ps_exchange_bench(
            n_params=n_params, workers=(2,), seconds=seconds,
            transports=("socket",), compute_ms=compute_ms,
            per_round_extra_s=extra_s,
        )
        return out["ps_exchange_socket_w2"]

    clean = []
    for k in range(max(1, int(repeats))):
        log(f"[regress] clean repeat {k + 1}/{repeats}")
        clean.append(one_exchange_run())
    extra_s = 0.0
    if slowdown:
        fused_med = float(np.median(
            [r["fused_rounds_per_sec"] for r in clean]
        ))
        extra_s = float(slowdown) / max(fused_med, 1e-9)
        log(f"[regress] injecting {extra_s * 1e3:.2f} ms/round synthetic "
            f"slowdown (fraction {slowdown} of the clean fused round)")
    current = one_exchange_run(extra_s)
    report = compare_to_trajectory(
        [current], trajectory + clean,
        rel_slack=rel_slack, spread_mult=spread_mult,
        host_cores=host_cores,
    )
    # coverage honesty: trajectory families this invocation did NOT
    # re-measure are named, not silently skipped
    measured = {_record_config(current)}
    unmeasured = sorted({
        c for r in trajectory
        if (c := _record_config(r)) is not None and c not in measured
    })
    rec = {
        "config": "bench_regress",
        "verdict": report["verdict"],
        "regressions": report["regressions"],
        "checks": report["checks"],
        "repeats": len(clean),
        "slowdown_injected": float(slowdown),
        "seconds_per_phase": seconds,
        "params": n_params,
        "host_cores": host_cores,
        "trajectory_files": len(files),
        "trajectory_records": len(trajectory),
        "trajectory_configs_not_measured": unmeasured,
        "rel_slack": rel_slack,
        "spread_mult": spread_mult,
    }
    for c in report["checks"]:
        log(json.dumps({"regress_check": c}))
    log(f"[regress] verdict: {rec['verdict']} "
        f"({rec['regressions']} regression(s))")
    return rec


def run_ps_chaos_bench(n_params=1_000_000, workers=4, seconds=4.0,
                       drop_recv=0.02, delay=0.05, delay_s=0.002, seed=0):
    """PS throughput under injected chaos (--chaos): the same mixed
    pull+commit hammer as --ps-bench, but over the socket transport with a
    seeded FaultPlan dropping replies and delaying frames, the clients
    wrapped in ResilientPSClient (reconnect + retry + seqno'd commits +
    heartbeats). Reports the surviving round rate plus the resilience
    counters, and asserts the dedup oracle: folds applied == logical
    commits issued, no matter how many retries replayed."""
    from distkeras_tpu.parallel.merge_rules import DownpourMerge
    from distkeras_tpu.parameter_servers import (
        ParameterServerClient,
        SocketParameterServer,
    )
    from distkeras_tpu.resilience import FaultPlan, ResilientPSClient, RetryPolicy

    center = _ps_bench_tree(n_params)
    delta = {
        "emb": np.full_like(center["emb"], 1e-6),
        "dense": {"w": np.full_like(center["dense"]["w"], 1e-6),
                  "b": np.full_like(center["dense"]["b"], 1e-6)},
    }
    log(f"[ps-chaos] socket + faults: {workers} workers, "
        f"{n_params / 1e6:.1f}M params, drop_recv={drop_recv}, "
        f"delay={delay}@{delay_s * 1e3:.0f}ms")
    ps = SocketParameterServer(center, DownpourMerge(), workers,
                               lease_timeout=1.0)
    ps.initialize()
    ps.start()
    policy = RetryPolicy(base_delay=0.01, max_delay=0.2, deadline=60.0,
                         seed=seed)
    clients = [
        ResilientPSClient(
            lambda i=i: ParameterServerClient("127.0.0.1", ps.port, i),
            i, policy=policy, heartbeat_interval=0.2,
        )
        for i in range(workers)
    ]
    plan = FaultPlan(seed=seed, drop_recv=drop_recv, delay=delay,
                     delay_s=delay_s)
    try:
        with plan:
            def op(c, i):
                c.pull()
                c.commit(i, delta)
                c.maybe_heartbeat()

            rounds, t = _ps_bench_phase(clients, op, seconds)
        logical = sum(c.seq for c in clients)
        s = ps.stats()
        rec = {
            "config": "ps_chaos_socket",
            "workers": workers,
            "params": n_params,
            "rounds_per_sec": round(rounds / t, 2),
            "logical_commits": logical,
            "applied_commits": s["commits"],
            "dup_commits": s["dup_commits"],
            "dedup_exact_once": s["commits"] == logical,
            "retries": sum(c.retries for c in clients),
            "evicted_workers": s["evicted_workers"],
            "heartbeats": s["heartbeats"],
            "faults": plan.stats(),
        }
        if not rec["dedup_exact_once"]:
            rec["invalid"] = True  # the oracle failing is a bug, not noise
        log(json.dumps(rec))
        return {"ps_chaos_socket": rec}
    finally:
        for c in clients:
            try:
                c.close()
            except OSError:
                pass
        ps.stop()


def run_ps_elastic_bench(n_params=200_000, workers=3, join_workers=2,
                         seconds=4.5, pace_s=0.01, seed=0):
    """Elastic-membership leg (--chaos, ISSUE 9): a join + preempt sweep
    at FIXED offered load. Each worker runs pull → commit → sleep(pace_s),
    so its offered rate is ~constant and aggregate throughput should
    track pool size; the sweep is three equal phases — base pool, pool +
    live-joined workers (the `join` wire action), pool drained back down
    (drain events + the `drain` wire action). The acceptance line:
    per-phase throughput tracks pool size within ±1 worker's contribution
    (phase-A per-worker rate is the unit). Honesty fields: `host_cores`
    (fewer cores than peak pool serializes the workers — the per-worker
    rate sags and tracking is host-ceiling-capped, flagged rather than
    failed) and the exactly-once dedup oracle, asserted as always."""
    import os as _os

    from distkeras_tpu.parallel.merge_rules import DownpourMerge
    from distkeras_tpu.parameter_servers import (
        ParameterServerClient,
        SocketParameterServer,
    )
    from distkeras_tpu.resilience import ResilientPSClient, RetryPolicy

    center = _ps_bench_tree(n_params)
    delta = {
        "emb": np.full_like(center["emb"], 1e-6),
        "dense": {"w": np.full_like(center["dense"]["w"], 1e-6),
                  "b": np.full_like(center["dense"]["b"], 1e-6)},
    }
    peak = workers + join_workers
    log(f"[ps-elastic] socket join/preempt sweep: {workers}→{peak}→"
        f"{workers} workers, {n_params / 1e6:.1f}M params, "
        f"pace {pace_s * 1e3:.0f}ms")
    ps = SocketParameterServer(center, DownpourMerge(), workers,
                               lease_timeout=30.0)
    ps.initialize()
    ps.start()
    policy = RetryPolicy(base_delay=0.01, max_delay=0.2, deadline=60.0,
                         seed=seed)
    phase = [0]
    counters = [0, 0, 0]
    clock = [0.0, 0.0, 0.0]
    lock = threading.Lock()
    global_stop = threading.Event()
    clients: dict[int, ResilientPSClient] = {}
    drain_events: dict[int, threading.Event] = {}
    threads: dict[int, threading.Thread] = {}
    errors: list = []

    def make(i):
        return ResilientPSClient(
            lambda: ParameterServerClient("127.0.0.1", ps.port, i),
            i, policy=policy,
        )

    def hammer(i):
        c = clients[i]
        evt = drain_events[i]
        try:
            while not global_stop.is_set() and not evt.is_set():
                c.pull()
                c.commit(i, delta)
                with lock:
                    counters[phase[0]] += 1
                time.sleep(pace_s)
        except BaseException as e:  # pragma: no cover - surfaced below
            errors.append(e)

    def launch(i, joiner):
        clients[i] = make(i)
        if joiner:
            clients[i].join()  # the live-join wire action
        drain_events[i] = threading.Event()
        t = threading.Thread(target=hammer, args=(i,), daemon=True)
        threads[i] = t
        t.start()

    def run_phase(k, dur):
        with lock:
            phase[0] = k
        t0 = time.perf_counter()
        time.sleep(dur)
        clock[k] = time.perf_counter() - t0

    dur = seconds / 3.0
    try:
        for i in range(workers):
            launch(i, joiner=False)
        run_phase(0, dur)
        joiner_ids = list(range(workers, peak))
        for i in joiner_ids:
            launch(i, joiner=True)
        run_phase(1, dur)
        # preempt sweep: drain the joiners back out (finish the in-flight
        # round, then the drain wire action retires the dedup seqno)
        for i in joiner_ids:
            drain_events[i].set()
        for i in joiner_ids:
            threads[i].join(timeout=30)
            clients[i].drain(timeout=False)
        run_phase(2, dur)
    finally:
        global_stop.set()
        for t in threads.values():
            t.join(timeout=30)
    assert not errors, errors

    pools = [workers, peak, workers]
    rates = [counters[k] / max(clock[k], 1e-9) for k in range(3)]
    unit = rates[0] / workers  # one worker's contribution, phase-A basis
    tracking = all(
        abs(rates[k] - unit * pools[k]) <= unit for k in range(3)
    )
    host_cores = _os.cpu_count() or 1
    logical = sum(c.seq for c in clients.values())
    s = ps.stats()
    rec = {
        "config": "ps_elastic_socket",
        "params": n_params,
        "workers_base": workers,
        "workers_joined": len(joiner_ids),
        "pace_s": pace_s,
        "phases": [
            {"name": n, "pool": pools[k],
             "rounds_per_sec": round(rates[k], 2),
             "per_worker_rounds_per_sec": round(rates[k] / pools[k], 2)}
            for k, n in enumerate(("base", "joined", "drained"))
        ],
        "unit_rounds_per_sec": round(unit, 2),
        "tracking_within_one_worker": tracking,
        # honesty: with fewer cores than the peak pool the workers
        # serialize and per-worker rate sags — the tracking claim's
        # regime is host_cores >= peak pool (or a real multi-host pool)
        "host_cores": host_cores,
        "host_ceiling_limited": (not tracking) and host_cores < peak,
        "logical_commits": logical,
        "applied_commits": s["commits"],
        "dedup_exact_once": s["commits"] == logical,
        "pool_stats": {k: s[k] for k in (
            "pool_size", "joined_workers", "preempted_workers",
            "drain_timeouts")},
    }
    if not rec["dedup_exact_once"] or (
            not tracking and not rec["host_ceiling_limited"]):
        rec["invalid"] = True
    try:
        for c in clients.values():
            c.close()
    except OSError:
        pass
    ps.stop()
    log(json.dumps(rec))
    return {"ps_elastic_socket": rec}


def run_ps_failover_bench(n_params=1_000_000, workers=4, seconds=4.0,
                          seed=0):
    """PS survivability benchmark (--chaos-ps): the mixed pull+commit
    hammer over the socket transport, with the PRIMARY crash-stopped
    mid-run (SIGKILL semantics: torn connections, no final fsync) and
    recovered two ways — one leg restarts in place from the write-ahead
    log, one promotes a hot standby. Each leg reports rounds/s before vs
    after the failover, the failover latency and WAL-replay time from
    the supervisor, and asserts the cross-failover exactly-once oracle:
    lifetime folds (num_updates, which survives recovery) == logical
    commits issued, no matter what the kill tore mid-ACK."""
    import shutil
    import tempfile
    import warnings

    from distkeras_tpu.parallel.merge_rules import DownpourMerge
    from distkeras_tpu.parameter_servers import (
        ParameterServerClient,
        SocketParameterServer,
        StandbySocketParameterServer,
    )
    from distkeras_tpu.resilience import (
        PSEndpoint,
        PSFailoverSupervisor,
        ResilientPSClient,
        RetryPolicy,
    )

    center = _ps_bench_tree(n_params)
    delta = {
        "emb": np.full_like(center["emb"], 1e-6),
        "dense": {"w": np.full_like(center["dense"]["w"], 1e-6),
                  "b": np.full_like(center["dense"]["b"], 1e-6)},
    }
    out = {}
    for mode in ("restart", "standby"):
        name = f"ps_failover_{mode}"
        log(f"[chaos-ps] {name}: {workers} workers, "
            f"{n_params / 1e6:.1f}M params, kill at t={seconds / 2:.1f}s")
        wal_dir = tempfile.mkdtemp(prefix="dk-walbench-")
        ps = SocketParameterServer(center, DownpourMerge(), workers,
                                   lease_timeout=5.0, wal_dir=wal_dir,
                                   snapshot_every=50)
        ps.initialize()
        ps.start()
        resolver = PSEndpoint("127.0.0.1", ps.port, epoch=ps.fence_epoch)
        standby = None
        if mode == "standby":
            standby = StandbySocketParameterServer(
                center, DownpourMerge(), workers, lease_timeout=5.0,
            )
            standby.initialize()
            standby.start()
            ps.attach_standby("127.0.0.1", standby.port)

        def factory(_wal=wal_dir):
            new = SocketParameterServer(center, DownpourMerge(), workers,
                                        lease_timeout=5.0, wal_dir=_wal,
                                        snapshot_every=50)
            new.initialize()
            new.start()
            return new

        sup = PSFailoverSupervisor(
            resolver, ps, standby=standby, restart_factory=factory,
            failover_timeout=0.5,
        )
        sup.start()

        def mk(i):
            host, port, epoch = resolver.resolve()
            return ParameterServerClient(host, port, i, epoch=epoch,
                                         connect_timeout=5.0)

        policy = RetryPolicy(max_attempts=200, base_delay=0.01,
                             max_delay=0.25, deadline=120.0, seed=seed)
        clients = [
            ResilientPSClient(lambda i=i: mk(i), i, policy=policy,
                              heartbeat_interval=0.2, resolver=resolver)
            for i in range(workers)
        ]

        def op(c, i):
            c.pull()
            c.commit(i, delta)
            c.maybe_heartbeat()

        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                before, t_before = _ps_bench_phase(clients, op,
                                                   seconds / 2)
                ps._crash()  # SIGKILL semantics mid-service
                t_kill = time.perf_counter()
                after, t_after = _ps_bench_phase(clients, op, seconds / 2)
            while sup.failovers == 0 and time.perf_counter() - t_kill < 30:
                time.sleep(0.01)  # phase B can outrun the promotion log
            sup.stop()
            active = sup.active
            logical = sum(c.seq for c in clients)
            s = active.stats()
            rec = {
                "config": name,
                "workers": workers,
                "params": n_params,
                "rounds_per_sec_before": round(before / t_before, 2),
                "rounds_per_sec_after": round(after / t_after, 2),
                "failovers": sup.failovers,
                "failover_latency_ms": round(
                    sup.failover_latency_s * 1e3, 2),
                "wal_replay_ms": round(sup.wal_replay_s * 1e3, 2),
                "logical_commits": logical,
                "applied_commits_lifetime": s["num_updates"],
                "dedup_exact_once": s["num_updates"] == logical,
                "retries": sum(c.retries for c in clients),
                "fenced_commits": s["fenced_commits"],
            }
            if not rec["dedup_exact_once"] or sup.failovers != 1:
                rec["invalid"] = True  # a broken oracle is a bug, not noise
            log(json.dumps(rec))
            out[name] = rec
        finally:
            for c in clients:
                try:
                    c.close()
                except OSError:
                    pass
            try:
                sup.stop()
            except Exception:
                pass
            for server in (sup.active, ps, standby):
                if server is not None:
                    try:
                        server.stop()
                    except Exception:
                        pass
            shutil.rmtree(wal_dir, ignore_errors=True)
    return out


def run_ps_group_commit_sweep(n_params=1_000_000, workers=4, seconds=3.0,
                              transports=("socket", "native", "shm")):
    """Durability-cost sweep (--chaos-ps, ISSUE 7): the mixed pull+commit
    hammer per transport across flush-window settings —

    - ``nowal``: no WAL at all (the raw line the durable legs chase),
    - ``w1``: flush-per-record + periodic fsync, immediate ACK (the PR 5
      behavior on the socket path; per-commit-fsync on native),
    - ``w8`` / ``w32``: group commit — ACKs deferred onto one fsync per
      window (``w8`` is the trainer default),
    - ``time``: window 0 — immediate ACK, fsync every interval (the
      durability window bounded in seconds, weakest/fastest durable mode).

    Every leg commits through per-worker seqnos and asserts the
    exactly-once oracle (``num_updates == logical commits``); durable legs
    report the WAL amortization counters (records/fsyncs/max group). The
    headline number is ``durable_fraction_w8``: group-commit rounds/s as
    a fraction of the no-WAL line (the ISSUE 7 target is >= 0.85).

    WAL placement: full-payload logging moves ~4 MB per commit at 1M
    params, so a slow log device turns every leg into a disk-bandwidth
    measurement (this class of VM's virtio disk writes ~100 MB/s — a
    ~25 commits/s hard ceiling no software can beat; that ceiling, not
    fsync count, was most of PR 5's measured "4x"). The sweep therefore
    measures the SOFTWARE cost of durability the way WAL benchmarks
    conventionally do: the log lives on the fastest local filesystem
    (``/dev/shm`` when present, override with $DISTKERAS_WAL_BENCH_DIR),
    and the record names the placement (``wal_fs``) so the trajectory
    stays honest about what was measured."""
    import shutil
    import tempfile

    from distkeras_tpu.parallel.merge_rules import DownpourMerge
    from distkeras_tpu.parameter_servers import (
        ParameterServerClient,
        SocketParameterServer,
    )

    wal_base = os.environ.get("DISTKERAS_WAL_BENCH_DIR")
    if wal_base is None and os.path.isdir("/dev/shm") \
            and os.access("/dev/shm", os.W_OK):
        wal_base = "/dev/shm"

    center = _ps_bench_tree(n_params)
    delta = {
        "emb": np.full_like(center["emb"], 1e-6),
        "dense": {"w": np.full_like(center["dense"]["w"], 1e-6),
                  "b": np.full_like(center["dense"]["b"], 1e-6)},
    }
    windows = (("nowal", None), ("w1", 1), ("w8", 8), ("w32", 32),
               ("time", 0))
    out = {}
    for transport in transports:
        if transport == "native":
            from distkeras_tpu.native import load_dkps

            if load_dkps() is None:
                log("[group-commit] native transport skipped "
                    "(no C++ toolchain)")
                continue
            from distkeras_tpu.native_ps import (
                NativePSClient,
                NativeSocketParameterServer,
            )
        name = f"ps_group_commit_{transport}"
        rec = {"config": name, "workers": workers, "params": n_params,
               "wal_fs": wal_base or tempfile.gettempdir(), "legs": {}}
        for leg, window in windows:
            wal_dir = (None if window is None
                       else tempfile.mkdtemp(prefix="dk-walsweep-",
                                             dir=wal_base))
            kw = {} if window is None else dict(
                wal_dir=wal_dir, snapshot_every=10 ** 9,
                wal_group_window=window, wal_group_interval=0.25,
            )
            if transport == "native":
                ps = NativeSocketParameterServer(
                    center, DownpourMerge(), workers, **kw)
            elif transport == "shm":
                # ISSUE 12 satellite: the flush-window sweep on the shm
                # lane — durable commits ride the pickle lane so the WAL
                # logs wire frames verbatim, exactly like the socket leg
                from distkeras_tpu.shm import ShmParameterServer

                ps = ShmParameterServer(
                    center, DownpourMerge(), workers, **kw)
            else:
                ps = SocketParameterServer(
                    center, DownpourMerge(), workers, **kw)
            ps.initialize()
            ps.start()
            if transport == "native":
                clients = [NativePSClient("127.0.0.1", ps.port, i, ps.spec)
                           for i in range(workers)]
            elif transport == "shm":
                from distkeras_tpu.shm import ShmPSClient

                clients = [ShmPSClient(ps, i) for i in range(workers)]
            else:
                clients = [ParameterServerClient("127.0.0.1", ps.port, i)
                           for i in range(workers)]
            seqs = [0] * workers
            log(f"[group-commit] {name}/{leg}: {workers} workers, "
                f"{n_params / 1e6:.1f}M params")
            try:
                def op(c, i):
                    c.pull()
                    seqs[i] += 1
                    c.commit(i, delta, seq=seqs[i])

                rounds, t = _ps_bench_phase(clients, op, seconds)
                s = ps.stats()
                logical = sum(seqs)
                leg_rec = {
                    "rounds_per_sec": round(rounds / t, 2),
                    "logical_commits": logical,
                    "applied_commits": s["num_updates"],
                    "dedup_exact_once": s["num_updates"] == logical,
                    "wal_records": s["wal_records"],
                    "wal_fsyncs": s["wal_fsyncs"],
                    "wal_group_max": s["wal_group_max"],
                    # the structural proof group commit is after: the
                    # center lock's critical section must not grow when
                    # durability turns on (the log append under the lock
                    # is an O(1) queue of chunk refs)
                    "center_lock_mean_hold_ns": s["center_lock_mean_hold_ns"],
                }
                if not leg_rec["dedup_exact_once"]:
                    leg_rec["invalid"] = True
                rec["legs"][leg] = leg_rec
            finally:
                for c in clients:
                    try:
                        c.close()
                    except OSError:
                        pass
                ps.stop()
                if wal_dir is not None:
                    shutil.rmtree(wal_dir, ignore_errors=True)
        raw = rec["legs"]["nowal"]["rounds_per_sec"]
        for leg, _ in windows[1:]:
            rps = rec["legs"][leg]["rounds_per_sec"]
            rec["legs"][leg]["durable_fraction"] = (
                round(rps / raw, 3) if raw else 0.0
            )
        rec["durable_fraction_w8"] = rec["legs"]["w8"]["durable_fraction"]
        # Host-ceiling accounting (the PR 6 serve-bench treatment): on a
        # 1-core host EVERY off-lock durable byte — payload checksum, the
        # flusher's log write (tmpfs page alloc+copy ~1.5 ms/4 MB), fsync
        # — executes serially with the fold path, so durable_fraction
        # measures the host's spare cycles, not the lock structure. The
        # per-commit serial overhead below plus an unchanged
        # center_lock_mean_hold_ns IS the claim on this host; with >= 2
        # cores the off-lock work overlaps the serialized fold path and
        # the durable line approaches the no-WAL line (the >= 0.85
        # regime the ISSUE targets).
        rec["host_cores"] = os.cpu_count()
        w8 = rec["legs"]["w8"]["rounds_per_sec"]
        if raw and w8:
            rec["serial_durable_overhead_ms_per_round"] = round(
                (1.0 / w8 - 1.0 / raw) * 1e3, 3
            )
        if rec["host_cores"] == 1 and rec["durable_fraction_w8"] < 0.85:
            rec["host_ceiling_note"] = (
                "1-core host: off-lock durable work (checksum + log "
                "write) cannot overlap the fold path; the lock-hold "
                "parity across legs is the structural result, the "
                "fraction is this host's serial ceiling"
            )
        log(json.dumps(rec))
        out[name] = rec
    return out


# ---------------------------------------------------------------------------
# Serving-tier benchmark (--serve): Poisson open-loop load against the
# continuous-batching generation server (block-paged KV cache) vs the
# sequential one-request-at-a-time GeneratorPredictor baseline. The number
# that matters: completed requests/sec at each offered rate, with p50/p99
# end-to-end latency — continuous batching should hold >=3x the sequential
# throughput at saturation (ISSUE 6 acceptance).
# ---------------------------------------------------------------------------


def _serve_lm(vocab, maxlen, dim, heads, depth, dtype_name):
    import jax.numpy as jnp

    from distkeras_tpu.models import transformer_lm

    dtype = {"bf16": jnp.bfloat16, "f32": jnp.float32}[dtype_name]
    spec = transformer_lm(vocab=vocab, maxlen=maxlen, dim=dim, heads=heads,
                          depth=depth, dtype=dtype)
    params, _ = spec.init_np(0)
    return spec, params


def _serve_open_loop(port, prompts, max_new, rate, seconds, seed):
    """Poisson open-loop load: seeded exponential interarrivals at `rate`
    req/s for `seconds`, one client thread per request (arrivals never
    wait for completions — the open-loop discipline that exposes queueing
    delay). Busy backpressure is ridden out by the reconnecting client,
    so it lands in latency, not in silent drops. Returns (latencies_s,
    wall_s, errors)."""
    import threading

    from distkeras_tpu.resilience import RetryPolicy
    from distkeras_tpu.serving import (
        GenerationClient,
        ResilientGenerationClient,
    )

    rng = np.random.default_rng(seed)
    # cap outstanding work: past saturation the queue does the measuring,
    # thousands of client threads would only measure the host's scheduler
    n = max(1, min(int(rate * seconds), 400))
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n))
    lats, errors = [], []
    lock = threading.Lock()

    def one(i):
        try:
            client = ResilientGenerationClient(
                lambda: GenerationClient("127.0.0.1", port),
                policy=RetryPolicy(max_attempts=200, base_delay=0.02,
                                   max_delay=0.5, deadline=120.0,
                                   seed=seed + i),
            )
            t0 = time.perf_counter()
            client.generate(prompts[i % len(prompts)],
                            max_new_tokens=max_new, seed=i)
            dt = time.perf_counter() - t0
            client.close()
            with lock:
                lats.append(dt)
        except Exception as e:  # surfaced in the record
            with lock:
                errors.append(repr(e))

    threads = []
    t_start = time.perf_counter()
    for i in range(n):
        delay = t_start + arrivals[i] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=one, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=300)
    wall = time.perf_counter() - t_start
    return lats, wall, errors


def run_serve_hotswap_bench(spec, params, prompts, seq_rps, max_new=48,
                            max_batch=16, block_size=16, seconds=6.0,
                            swap_interval=None, seed=0):
    """Hot-swap serving leg (ISSUE 16): the same Poisson open-loop load,
    with a deployer thread flipping the engine between two weight sets
    through the refill version gate mid-window. The number that matters:
    p99 across swap events vs a no-swap window at the SAME offered rate
    — the end-to-end latency price of a live deployment. A refill swap
    re-prefills every in-flight row under the new weights, so the
    penalty is real work (repeated prefill), not queueing artifact;
    ``swap_events``/``refilled`` off ``engine.stats()`` say how many
    requests actually paid it. ``host_cores`` rides the record: on a
    1-core host prefill replay and decode contend for the same core and
    the penalty reads as an upper bound for the TPU regime."""
    from distkeras_tpu.serving import (
        GenerationClient,
        GenerationEngine,
        GenerationServer,
    )

    # a second init of the same spec: identical shapes, so the gate
    # never recompiles — exactly what a streamed training snapshot is
    params_b, _ = spec.init_np(seed + 1)
    engine = GenerationEngine(spec, params, max_batch=max_batch,
                              block_size=block_size, max_queue=256,
                              model_version=1)
    server = GenerationServer(engine)
    server.start()
    try:
        def _warm(i):
            c = GenerationClient("127.0.0.1", server.port)
            c.generate(prompts[i % len(prompts)], max_new_tokens=max_new)
            c.close()

        ws = [threading.Thread(target=_warm, args=(i,))
              for i in range(max_batch)]
        for w in ws:
            w.start()
        for w in ws:
            w.join(timeout=300)

        rate = max(0.5, 2.0 * seq_rps)
        base_lats, base_wall, base_errors = _serve_open_loop(
            server.port, prompts, max_new, rate, seconds, seed)

        interval = (max(0.5, seconds / 4.0) if swap_interval is None
                    else float(swap_interval))
        stop = threading.Event()
        flips = [params, params_b]

        def deployer():
            v = 1
            while not stop.wait(interval):
                v += 1
                engine.swap_params(flips[v % 2], v, policy="refill")

        dep = threading.Thread(target=deployer, daemon=True)
        dep.start()
        lats, wall, errors = _serve_open_loop(
            server.port, prompts, max_new, rate, seconds, seed + 1)
        stop.set()
        dep.join(timeout=10)
        stats = engine.stats()

        def _pcts(xs):
            if not xs:
                return None, None
            ms = np.sort(np.asarray(xs)) * 1e3
            return (round(float(np.percentile(ms, 50)), 1),
                    round(float(np.percentile(ms, 99)), 1))

        b50, b99 = _pcts(base_lats)
        s50, s99 = _pcts(lats)
        rec = {
            "config": "serve_hotswap",
            "offered_rps": round(rate, 2),
            "seconds_per_window": seconds,
            "swap_interval_s": round(interval, 2),
            "no_swap": {"completed": len(base_lats),
                        "errors": len(base_errors),
                        "throughput_rps": round(
                            len(base_lats) / base_wall, 2),
                        "p50_ms": b50, "p99_ms": b99},
            "swap": {"completed": len(lats), "errors": len(errors),
                     "throughput_rps": round(
                         len(lats) / wall, 2) if lats else 0.0,
                     "p50_ms": s50, "p99_ms": s99},
            "swap_events": stats["swaps"],
            "refilled_requests": stats["refilled"],
            "p99_swap_penalty_ms": (round(s99 - b99, 1)
                                    if s99 is not None and b99 is not None
                                    else None),
            "final_model_version": stats["model_version"],
            "blocks_in_use_after": stats["blocks_in_use"],
            "host_cores": os.cpu_count() or 1,
        }
        log(f"[serve] hotswap @ {rate:.2f} req/s: p99 "
            f"{b99} ms no-swap -> {s99} ms across "
            f"{rec['swap_events']} swaps ({rec['refilled_requests']} "
            f"requests re-prefilled)")
        log(json.dumps(rec))
        return rec
    finally:
        server.stop(drain=False, timeout=10)


def run_serve_prefix_bench(spec, params, vocab, max_new=32, max_batch=8,
                           block_size=16, sys_len=96, tail_len=16,
                           n_requests=16, prefill_chunk=16, seed=0):
    """Shared-system-prompt leg (ISSUE 17): every request carries the
    same ``sys_len``-token system prefix plus a unique ``tail_len``-token
    user suffix — the workload automatic prefix caching exists for. One
    ``prefix_cache=True`` engine serves three waves, each under its own
    ``slo_class`` label so the retired-ring summary keeps them apart:
    a warmup wave (unique prefixes; fills the jit buckets, uncounted), a
    COLD wave (unique prefixes again — 0% hit rate, every prompt token
    prefilled), and a WARM wave (the shared system prompt, seeded by one
    uncounted request — only the unique tail prefills). Same engine,
    same chunked-prefill code path, same concurrency: the only variable
    is the hit rate, and the number that matters is mean prefill ms
    dropping with it. ``prefill_chunk`` is pinned so every wave runs the
    same chunk shapes (no compile skew between waves)."""
    from distkeras_tpu.serving import (
        GenerationClient,
        GenerationEngine,
        GenerationServer,
    )

    rng = np.random.default_rng(seed)

    def fresh(n):  # unique (prefix, tail) prompts — never cache-hit
        return [rng.integers(0, vocab, (sys_len + tail_len,)).astype(
            np.int32) for _ in range(n)]

    system = rng.integers(0, vocab, (sys_len,)).astype(np.int32)
    shared = [np.concatenate([
        system, rng.integers(0, vocab, (tail_len,)).astype(np.int32)])
        for _ in range(n_requests + 1)]

    engine = GenerationEngine(spec, params, max_batch=max_batch,
                              block_size=block_size, max_queue=256,
                              prefix_cache=True,
                              prefill_chunk=prefill_chunk)
    server = GenerationServer(engine)
    server.start()
    try:
        def one(prompt, slo_class):
            c = GenerationClient("127.0.0.1", server.port)
            c.generate(prompt, max_new_tokens=max_new,
                       slo_class=slo_class, tenant="prefix-bench")
            c.close()

        def wave(prompts, slo_class):
            before = engine.stats()
            ts = [threading.Thread(target=one, args=(p, slo_class))
                  for p in prompts]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            after = engine.stats()
            lat = engine.latency_stats().get(slo_class, {})
            d_hit = (after["prefix_hit_tokens"]
                     - before["prefix_hit_tokens"])
            d_tot = (after["prefix_prompt_tokens"]
                     - before["prefix_prompt_tokens"])
            return {
                "prefill_ms": round(lat.get("prefill_ms", 0.0), 2),
                "p50_ms": round(lat.get("p50_ms", 0.0), 1),
                "p99_ms": round(lat.get("p99_ms", 0.0), 1),
                "completed": lat.get("count", 0),
                "hit_rate": round(d_hit / d_tot, 4) if d_tot else 0.0,
            }

        wave(fresh(max_batch), "warmup")        # jit buckets, uncounted
        cold = wave(fresh(n_requests), "cold")
        one(shared[0], "seed")                  # make the prefix resident
        warm = wave(shared[1:], "warm")

        stats = engine.stats()
        rec = {
            "config": "serve_prefix",
            "sys_len": sys_len, "tail_len": tail_len,
            "max_new_tokens": max_new, "n_requests": n_requests,
            "prefill_chunk": prefill_chunk,
            "cold_prefill_ms": cold["prefill_ms"],
            "warm_prefill_ms": warm["prefill_ms"],
            "prefill_speedup": (round(cold["prefill_ms"]
                                      / warm["prefill_ms"], 2)
                                if warm["prefill_ms"] else 0.0),
            "cold_hit_rate": cold["hit_rate"],
            "warm_hit_rate": warm["hit_rate"],
            "prefix_cached_blocks": stats["prefix_cached_blocks"],
            "prefix_evictions": stats["prefix_evictions"],
            "cow_copies": stats["cow_copies"],
            "cold": cold, "warm": warm,
            "host_cores": os.cpu_count() or 1,
        }
        log(f"[serve] prefix: mean prefill {cold['prefill_ms']} ms at "
            f"{cold['hit_rate']:.0%} hit rate -> {warm['prefill_ms']} ms "
            f"at {warm['hit_rate']:.0%} ({rec['prefill_speedup']}x)")
        log(json.dumps(rec))
        return rec
    finally:
        server.stop(drain=False, timeout=10)


def run_serve_tenants_bench(spec, params, vocab, max_batch=4,
                            block_size=16, n_batch=10, n_rt=8,
                            rt_gap_s=0.25, seed=0):
    """Mixed-tenant SLO leg (ISSUE 17): a best-effort tenant bursts
    ``n_batch`` LONG requests (64-token prompts, 48 new tokens) into a
    deliberately block-starved engine, then a realtime tenant's SHORT
    requests (16+8 tokens) arrive one every ``rt_gap_s``. Under strict
    FIFO the realtime requests queue behind the burst; under
    ``admission='slo'`` they jump the queue and, when the block pool is
    exhausted, preempt best-effort rows (recompute-on-resume keeps the
    preempted outputs bit-identical). The numbers that matter:
    realtime p99 bounded under 'slo' vs 'fifo' at the same load, with
    ``preemptions`` counting what best-effort absorbed to pay for it."""
    from distkeras_tpu.serving import (
        GenerationClient,
        GenerationEngine,
        GenerationServer,
    )

    rng = np.random.default_rng(seed)
    long_prompts = [rng.integers(0, vocab, (64,)).astype(np.int32)
                    for _ in range(n_batch)]
    short_prompts = [rng.integers(0, vocab, (16,)).astype(np.int32)
                     for _ in range(n_rt)]
    # block-starved on purpose: the pool holds exactly TWO long rows
    # plus one spare block, so a realtime arrival finds rows free but
    # blocks exhausted — under FIFO it queues behind the head-of-line
    # long request; under 'slo' it preempts a best-effort row
    long_blocks = int(math.ceil((64 + 48) / block_size))
    num_blocks = 2 * long_blocks + 1

    def measure(admission):
        engine = GenerationEngine(spec, params, max_batch=max_batch,
                                  block_size=block_size, max_queue=256,
                                  num_blocks=num_blocks,
                                  admission=admission)
        server = GenerationServer(engine)
        server.start()
        try:
            def one(prompt, max_new, slo_class, tenant):
                c = GenerationClient("127.0.0.1", server.port)
                c.generate(prompt, max_new_tokens=max_new,
                           slo_class=slo_class, tenant=tenant)
                c.close()

            one(long_prompts[0], 48, "default", "warm")   # compile
            one(short_prompts[0], 8, "default", "warm")
            ts = [threading.Thread(
                target=one,
                args=(long_prompts[i], 48, "best_effort", "batch"))
                for i in range(n_batch)]
            for t in ts:
                t.start()
            time.sleep(rt_gap_s)  # let the burst occupy the engine
            rs = []
            for i in range(n_rt):
                r = threading.Thread(
                    target=one,
                    args=(short_prompts[i], 8, "realtime", "rt"))
                r.start()
                rs.append(r)
                time.sleep(rt_gap_s)
            for t in ts + rs:
                t.join(timeout=300)
            lat = engine.latency_stats()
            stats = engine.stats()
            return {
                "rt_p50_ms": round(
                    lat.get("realtime", {}).get("p50_ms", 0.0), 1),
                "rt_p99_ms": round(
                    lat.get("realtime", {}).get("p99_ms", 0.0), 1),
                "be_p99_ms": round(
                    lat.get("best_effort", {}).get("p99_ms", 0.0), 1),
                "rt_completed": lat.get("realtime", {}).get("count", 0),
                "be_completed": lat.get("best_effort", {}).get(
                    "count", 0),
                "preemptions": stats.get("preemptions", 0),
                "blocks_in_use_after": stats["blocks_in_use"],
            }
        finally:
            server.stop(drain=False, timeout=10)

    fifo = measure("fifo")
    slo = measure("slo")
    rec = {
        "config": "serve_tenants",
        "max_batch": max_batch, "num_blocks": num_blocks,
        "n_batch_requests": n_batch, "n_rt_requests": n_rt,
        "fifo_rt_p99_ms": fifo["rt_p99_ms"],
        "slo_rt_p99_ms": slo["rt_p99_ms"],
        "fifo_be_p99_ms": fifo["be_p99_ms"],
        "slo_be_p99_ms": slo["be_p99_ms"],
        "rt_p99_gain_x": (round(fifo["rt_p99_ms"] / slo["rt_p99_ms"], 2)
                          if slo["rt_p99_ms"] else 0.0),
        "preemptions": slo["preemptions"],
        "fifo": fifo, "slo": slo,
        "host_cores": os.cpu_count() or 1,
    }
    log(f"[serve] tenants: realtime p99 {fifo['rt_p99_ms']} ms FIFO -> "
        f"{slo['rt_p99_ms']} ms slo admission "
        f"({rec['rt_p99_gain_x']}x; best-effort absorbed "
        f"{slo['preemptions']} preemptions)")
    log(json.dumps(rec))
    return rec


def run_serving_bench(vocab=1024, maxlen=160, dim=512, heads=8, depth=4,
                      dtype_name="f32", prompt_len=16, max_new=48,
                      max_batch=16, block_size=16, n_baseline=6,
                      rates=(1.0, 2.0, 4.0, 6.0), seconds=6.0,
                      legs=("paged", "int8", "spec"), seed=0):
    """Serving-tier benchmark: sequential GeneratorPredictor baseline, then
    the continuous-batching server under Poisson open-loop load at offered
    rates of `rates` x the sequential throughput. One record per leg:
    throughput_rps (completed/sec over the whole open-loop window), p50/p99
    end-to-end latency, speedup_vs_sequential (best sustained rate over the
    sequential baseline), plus the engine's occupancy/block stats. Legs:
    'paged' (the headline), 'int8' (weight-only quantized engine — same
    server, same cache), 'spec' (self-draft speculative serving: the
    acceptance=1.0 upper bound of draft-based serving — a real deployment
    substitutes a trained draft), 'hotswap' (live-deployment leg: p99
    across refill-gate weight swaps vs a no-swap window at the same
    offered rate — ISSUE 16).

    The default model/dtype is sized so a BATCH-1 decode step is WEIGHT-
    STREAMING bound (dim 512 x 4 layers f32: ~50 MB of kernels stream per
    step, far over cache; f32 because this host's vectorized f32 matmul
    is fast enough to be bandwidth-bound at B=1 where its bf16 path is
    compute-bound at any batch) — the regime real serving lives in, where
    a batched step costs less per row than a batch-1 step. A toy model
    instead measures fused-scan dispatch overhead, where the sequential
    baseline's zero-Python decode loop is unbeatable and the comparison
    says nothing about serving (measured: dim=128 flips the ratio to
    0.3x).

    The record also carries the HOST CEILING: ``static_batch_rps`` times
    a dense ``generate`` scan at B=``max_batch`` — the throughput of a
    perfect drain-the-batch static batcher with zero scheduling overhead
    — and ``host_ceiling_x`` (that bound over the sequential baseline).
    On a single-core CPU the ceiling is set by the core's compute/
    bandwidth balance (measured ~2.3x here) and the >=3x acceptance line
    is a TPU-regime claim: ``bound_fraction`` (achieved throughput over
    the static bound) is the number that transfers across hosts —
    continuous batching at ~1.0 means the scheduler adds nothing on top
    of an ideal batcher while ALSO admitting/retiring per iteration."""
    from distkeras_tpu.data import Dataset
    from distkeras_tpu.models import quantize_lm
    from distkeras_tpu.predictors import GeneratorPredictor
    from distkeras_tpu.serving import GenerationEngine, GenerationServer

    spec, params = _serve_lm(vocab, maxlen, dim, heads, depth, dtype_name)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, (prompt_len,)).astype(np.int32)
               for _ in range(32)]

    # sequential baseline: one request at a time through the predictor
    # (the pre-serving-tier deployment story), timed after a warmup pass
    base_ds = Dataset({"features": np.stack(prompts[:n_baseline])})
    pred = GeneratorPredictor(spec, params, max_new_tokens=max_new,
                              batch_size=1)
    pred.predict(Dataset({"features": np.stack(prompts[:1])}))  # warm/compile
    t0 = time.perf_counter()
    pred.predict(base_ds)
    seq_rps = n_baseline / (time.perf_counter() - t0)
    log(f"[serve] sequential GeneratorPredictor baseline: "
        f"{seq_rps:.2f} req/s ({dim}d x {depth}L {dtype_name}, "
        f"{prompt_len}+{max_new} tokens)")

    # host ceiling: a dense generate() scan over max_batch rows at once —
    # the perfect static batcher (no scheduling, no admission, every
    # request identical). Continuous batching is measured against BOTH:
    # speedup_vs_sequential is the deployment claim, bound_fraction says
    # how much of the host's batching headroom the scheduler captures.
    from distkeras_tpu.models.lm import generate as _generate

    bprompt = np.stack([prompts[i % len(prompts)]
                        for i in range(max_batch)])
    _generate(spec, params, bprompt, max_new)         # compile
    t0 = time.perf_counter()
    _generate(spec, params, bprompt, max_new)
    static_rps = max_batch / (time.perf_counter() - t0)
    log(f"[serve] dense static-batch bound (B={max_batch}): "
        f"{static_rps:.2f} req/s = {static_rps / seq_rps:.2f}x sequential")

    def build_engine(leg):
        if leg == "int8":
            qspec, qparams = quantize_lm(spec, params)
            return GenerationEngine(qspec, qparams, max_batch=max_batch,
                                    block_size=block_size, max_queue=256)
        if leg == "spec":
            return GenerationEngine(spec, params, max_batch=max_batch,
                                    block_size=block_size, max_queue=256,
                                    draft=spec, draft_params=params,
                                    spec_tokens=4)
        if leg != "paged":
            raise ValueError(f"unknown serving leg {leg!r} "
                             f"(choose from paged, int8, spec, hotswap, "
                             f"prefix, tenants)")
        return GenerationEngine(spec, params, max_batch=max_batch,
                                block_size=block_size, max_queue=256)

    out = {}
    if "hotswap" in legs:
        # the live-deployment leg rides the same baseline/prompts but
        # owns its server lifecycle (a deployer thread flips weights
        # mid-window) — see run_serve_hotswap_bench
        out["serve_hotswap"] = run_serve_hotswap_bench(
            spec, params, prompts, seq_rps, max_new=max_new,
            max_batch=max_batch, block_size=block_size, seconds=seconds,
            seed=seed)
        legs = tuple(x for x in legs if x != "hotswap")
    if "prefix" in legs:
        # the shared-system-prompt leg (ISSUE 17) owns its engine pair
        # (cache-off vs prefix_cache=True) — see run_serve_prefix_bench
        out["serve_prefix"] = run_serve_prefix_bench(
            spec, params, vocab, max_batch=max_batch,
            block_size=block_size, seed=seed)
        legs = tuple(x for x in legs if x != "prefix")
    if "tenants" in legs:
        # the mixed-tenant SLO leg (ISSUE 17): FIFO vs slo admission on
        # a block-starved engine — see run_serve_tenants_bench
        out["serve_tenants"] = run_serve_tenants_bench(
            spec, params, vocab, max_batch=max(2, max_batch // 4),
            block_size=block_size, seed=seed)
        legs = tuple(x for x in legs if x != "tenants")
    for leg in legs:
        engine = build_engine(leg)
        server = GenerationServer(engine)
        server.start()
        try:
            # warm the compile caches through the real wire path: a
            # concurrent burst exercises the batched-prefill row buckets
            # and the decode width buckets, not just the single-row path
            import threading as _threading

            from distkeras_tpu.serving import GenerationClient

            def _warm(i):
                c = GenerationClient("127.0.0.1", server.port)
                c.generate(prompts[i % len(prompts)],
                           max_new_tokens=max_new)
                c.close()

            ws = [_threading.Thread(target=_warm, args=(i,))
                  for i in range(max_batch)]
            for w in ws:
                w.start()
            for w in ws:
                w.join(timeout=300)

            per_rate = []
            best = None
            for mult in rates:
                rate = max(0.25, mult * seq_rps)
                lats, wall, errors = _serve_open_loop(
                    server.port, prompts, max_new, rate, seconds, seed)
                if not lats:
                    per_rate.append({"offered_rps": round(rate, 2),
                                     "errors": errors[:3]})
                    continue
                lats_ms = np.sort(np.asarray(lats)) * 1e3
                rec = {
                    "offered_rps": round(rate, 2),
                    "completed": len(lats),
                    "errors": len(errors),
                    "throughput_rps": round(len(lats) / wall, 2),
                    "p50_ms": round(float(np.percentile(lats_ms, 50)), 1),
                    "p99_ms": round(float(np.percentile(lats_ms, 99)), 1),
                }
                per_rate.append(rec)
                if best is None or rec["throughput_rps"] > \
                        best["throughput_rps"]:
                    best = rec
                log(f"[serve] {leg} offered {rate:.2f} req/s -> "
                    f"{rec['throughput_rps']} req/s, p50 {rec['p50_ms']} ms"
                    f", p99 {rec['p99_ms']} ms")
            stats = engine.stats()
            rec = {
                "config": f"serve_{leg}",
                "model": {"vocab": vocab, "maxlen": maxlen, "dim": dim,
                          "heads": heads, "depth": depth,
                          "dtype": dtype_name},
                "prompt_len": prompt_len, "max_new_tokens": max_new,
                "max_batch": max_batch, "block_size": block_size,
                "sequential_rps": round(seq_rps, 2),
                "static_batch_rps": round(static_rps, 2),
                "host_ceiling_x": round(static_rps / seq_rps, 2),
                "rates": per_rate,
                "throughput_rps": best["throughput_rps"] if best else 0.0,
                "p50_ms": best["p50_ms"] if best else None,
                "p99_ms": best["p99_ms"] if best else None,
                "speedup_vs_sequential": (
                    round(best["throughput_rps"] / seq_rps, 2)
                    if best and seq_rps else 0.0
                ),
                "bound_fraction": (
                    round(best["throughput_rps"] / static_rps, 2)
                    if best and static_rps else 0.0
                ),
                "mean_batch_occupancy": stats["mean_batch_occupancy"],
                "blocks_high_water": stats["blocks_high_water"],
                "completed": stats["completed"],
                "rejected": stats["rejected"],
            }
            if leg == "spec":
                rec["spec_acceptance"] = stats.get("spec_acceptance")
            # the >=3x acceptance line for the headline leg (self-draft
            # spec pays 2x model cost, int8 trades dtype for bandwidth —
            # they carry their own context, the paged leg is the claim)
            if leg == "paged":
                rec["target_3x_met"] = rec["speedup_vs_sequential"] >= 3.0
            log(json.dumps(rec))
            out[f"serve_{leg}"] = rec
        finally:
            server.stop(drain=False, timeout=10)
    return out


def run_proxy_only():
    """CPU-proxy denominator as a standalone process (spawned by main with
    ``JAX_PLATFORMS=cpu``): the ~550 s XLA:CPU compile+epochs run CONCURRENTLY
    with the TPU legs instead of serially blocking them (r4: the serial proxy
    alone doubled the budget). Prints one JSON line on stdout."""
    import jax.numpy as jnp
    import optax

    from distkeras_tpu.datasets import mnist
    from distkeras_tpu.models import lenet
    from distkeras_tpu.parallel.merge_rules import ADAGMerge

    cpu = jax.devices("cpu")[0]
    log("[proxy] ADAG/LeNet on single-process CPU "
        "(same batch/window, fewer rows; concurrent subprocess)")
    # 2048 rows is the MINIMUM at the matched b256/w8 config (one
    # superbatch); the ~2-4 min XLA:CPU compile dominates the leg
    train, _ = mnist(n_train=2048, n_test=64)
    # reduce="max": this subprocess shares the 1-core host with the main
    # process's tracing bursts, which SLOW proxy epochs (measured 37%
    # spread in a contended run vs 3% serial). The fastest of 4 timed
    # epochs (~136 s each) is the least-contended estimate, and a faster
    # denominator can only UNDERSTATE vs_baseline — conservative by
    # construction, so the spread gate does not apply to this leg
    # (distinct still does). Four epochs, not fewer: max-of-N is only as
    # conservative as its sample count — with too few epochs they can
    # ALL land on contended windows and the ratio inflates.
    sps, spread, distinct = measure(
        cpu, lenet(dtype=jnp.float32), ADAGMerge(), optax.adam(1e-3),
        train, ["features", "label"], batch_size=256, window=8,
        epochs_timed=4, reduce="max")
    print(json.dumps({"proxy_samples_per_sec": sps,
                      "spread": round(spread, 3),
                      "distinct": distinct}))
    sys.stdout.flush()


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser()
    ap.add_argument("--scaling", action="store_true",
                    help="also run the stacked-worker scaling sweep")
    ap.add_argument("--skip-proxy", action="store_true",
                    help="skip the slow CPU-proxy denominator run")
    ap.add_argument("--proxy-only", action="store_true",
                    help=argparse.SUPPRESS)  # internal: subprocess mode
    ap.add_argument("--full", action="store_true",
                    help="run every beyond-reference leg regardless of the "
                         "elapsed-time budget")
    ap.add_argument("--leg", default=None,
                    help="run ONLY the named beyond-reference leg "
                         "(6, 7, 7b, 8, 9, 10) after a minimal setup")
    ap.add_argument("--ps-bench", action="store_true",
                    help="run ONLY the parameter-server hot-path "
                         "microbenchmark (threads hammering pull/commit)")
    ap.add_argument("--ps-bench-params", type=int, default=10_000_000,
                    help="PS microbenchmark tree size in float32 params")
    ap.add_argument("--ps-bench-workers", type=int, default=4,
                    help="PS microbenchmark worker-thread count")
    ap.add_argument("--ps-bench-seconds", type=float, default=4.0,
                    help="PS microbenchmark seconds per phase")
    ap.add_argument("--chaos", action="store_true",
                    help="run ONLY the PS chaos benchmark (socket transport "
                         "under injected drops/delays with retry + seqno "
                         "dedup + heartbeats; asserts exactly-once folds)")
    ap.add_argument("--chaos-params", type=int, default=1_000_000,
                    help="chaos benchmark tree size in float32 params")
    ap.add_argument("--chaos-ps", action="store_true",
                    help="run ONLY the PS survivability benchmark (primary "
                         "crash-stopped mid-run; WAL restart-in-place and "
                         "hot-standby promotion legs with failover latency, "
                         "WAL replay ms, and rounds/s before vs after) plus "
                         "the group-commit flush-window sweep (no-WAL vs "
                         "w1/w8/w32/time-bounded, socket AND native, "
                         "exactly-once oracle asserted on every leg)")
    ap.add_argument("--serve", action="store_true",
                    help="run ONLY the serving-tier benchmark (continuous-"
                         "batching generation server with a block-paged KV "
                         "cache under Poisson open-loop load vs the "
                         "sequential GeneratorPredictor baseline)")
    ap.add_argument("--serve-seconds", type=float, default=6.0,
                    help="serving benchmark seconds per offered rate")
    ap.add_argument("--serve-max-batch", type=int, default=16,
                    help="serving benchmark engine batch slots")
    ap.add_argument("--serve-legs", default="paged,int8,spec",
                    help="comma-separated serving legs to run "
                         "(paged,int8,spec,hotswap,prefix,tenants — "
                         "hotswap measures p99 across live weight swaps "
                         "vs no-swap; prefix measures prefill ms under "
                         "the shared-system-prompt radix cache; tenants "
                         "measures realtime p99 under slo admission vs "
                         "FIFO with best-effort preemption)")
    ap.add_argument("--trace-dir", default=None,
                    help="enable the flight recorder for every leg and "
                         "write one Perfetto-loadable Chrome trace JSON "
                         "here; each leg's record (and the headline "
                         "blob) carries its path as trace_path")
    ap.add_argument("--regress", action="store_true",
                    help="perf-regression guard (ISSUE 13): measure the "
                         "PS exchange leg now and compare against the "
                         "checked-in BENCH_*.json trajectory plus this "
                         "invocation's own clean repeats (median ± "
                         "measured spread, host_cores-honest); exits "
                         "nonzero on a regression so CI fails the build")
    ap.add_argument("--regress-repeats", type=int, default=2,
                    help="clean baseline repeats seeding the contract")
    ap.add_argument("--regress-seconds", type=float, default=1.0,
                    help="seconds per measured exchange phase")
    ap.add_argument("--regress-params", type=int, default=200_000,
                    help="exchange-leg tree size in float32 params")
    ap.add_argument("--regress-slowdown", type=float, default=0.0,
                    help="self-test seam: inject a real per-round sleep "
                         "of this fraction of the clean fused round "
                         "into the final measured run (0.25 must be "
                         "flagged)")
    ap.add_argument("--regress-glob", default="BENCH_*.json",
                    help="trajectory file glob (repo root)")
    args = ap.parse_args()

    if args.regress:
        # guard mode: measure → compare → ONE stdout verdict blob, exit
        # nonzero on regression (the CI contract). Stays ahead of every
        # other leg: a guard must be cheap enough to run per-commit.
        rec = run_regress_bench(
            repeats=args.regress_repeats,
            seconds=args.regress_seconds,
            n_params=args.regress_params,
            slowdown=args.regress_slowdown,
            glob_pat=args.regress_glob,
            root=os.path.dirname(os.path.abspath(__file__)),
        )
        print(json.dumps(rec))
        sys.stdout.flush()
        sys.exit(1 if rec["verdict"] != "ok" else 0)

    if args.trace_dir:
        from distkeras_tpu.observability import trace as _obs_trace

        _obs_trace.enable()

    def _finish_trace():
        """Write the recorder out (one file per bench invocation; every
        leg's spans land in it), run the post-hoc analyzer over it
        (ISSUE 14 — the regime verdict every traced leg record carries),
        and return ``(path, verdict)`` — ``(None, None)`` untraced."""
        if not args.trace_dir:
            return None, None
        from distkeras_tpu.observability import analyze as _obs_analyze
        from distkeras_tpu.observability import trace as _obs_trace

        path = _obs_trace.save(os.path.join(
            args.trace_dir, f"bench-trace-{os.getpid()}.json"
        ))
        verdict = None
        try:
            report = _obs_analyze.analyze_events(
                _obs_trace.events(),
                dropped=_obs_trace.live_dropped(),
            )
            verdict = report["verdict"]
        except Exception as e:  # diagnosis must not fail the bench
            log(f"[trace analysis failed] {type(e).__name__}: {e}")
        _obs_trace.disable()
        return path, verdict

    if args.ps_bench or args.chaos or args.chaos_ps or args.serve:
        # PS legs are pure host-side numpy/threading; the serve leg runs the
        # tiny LM on whatever accelerator JAX finds. No proxy. Per-leg
        # records stream to stderr; ONE headline JSON blob lands on stdout
        # (same contract as the training headline), so the BENCH_*.json
        # trajectory files capture PS/serving perf history instead of
        # staying empty.
        legs = {}
        if args.ps_bench:
            legs.update(run_ps_microbench(n_params=args.ps_bench_params,
                                          workers=args.ps_bench_workers,
                                          seconds=args.ps_bench_seconds))
            # ISSUE 8: sharded-center scaling — aggregate pull/commit
            # throughput vs shard count, socket + native transports
            legs.update(run_ps_shard_bench(n_params=args.ps_bench_params,
                                           workers=args.ps_bench_workers,
                                           seconds=args.ps_bench_seconds))
            # ISSUE 10 + 12: the exchange leg — serial vs fused (2→1
            # RTTs) vs fused+pipelined at 2 and 4 workers, over socket,
            # native, AND the shm ring lane (with the shm-vs-socket
            # ratio and the batched-fold lock-amortization columns)
            legs.update(run_ps_exchange_bench(
                seconds=max(1.0, args.ps_bench_seconds / 2)))
        if args.chaos:
            legs.update(run_ps_chaos_bench(n_params=args.chaos_params,
                                           workers=args.ps_bench_workers,
                                           seconds=args.ps_bench_seconds))
            # ISSUE 9: the elastic leg — join + preempt sweep at fixed
            # offered load; throughput must track pool size within ±1
            # worker's contribution (host-ceiling honesty in the record)
            legs.update(run_ps_elastic_bench(
                workers=max(2, args.ps_bench_workers - 1),
                seconds=args.ps_bench_seconds))
        if args.chaos_ps:
            legs.update(run_ps_failover_bench(
                n_params=args.chaos_params,
                workers=args.ps_bench_workers,
                seconds=args.ps_bench_seconds))
            # ISSUE 7: the flush-window sweep — durable vs raw rounds/s
            # per transport, exactly-once oracle asserted on every leg
            legs.update(run_ps_group_commit_sweep(
                n_params=args.chaos_params,
                workers=args.ps_bench_workers,
                seconds=args.ps_bench_seconds))
        if args.serve:
            legs.update(run_serving_bench(
                max_batch=args.serve_max_batch,
                seconds=args.serve_seconds,
                legs=tuple(x for x in args.serve_legs.split(",") if x)))
        serve_only = args.serve and not (args.ps_bench or args.chaos
                                         or args.chaos_ps)
        trace_path, trace_verdict = _finish_trace()
        if trace_path is not None:
            # BENCH_* records link to their timeline (ISSUE 11) and its
            # analysis verdict (ISSUE 14): the one trace file carries
            # every leg's spans; the regime names what bounded the run
            for rec in legs.values():
                if isinstance(rec, dict):
                    rec["trace_path"] = trace_path
                    if trace_verdict is not None:
                        rec["analysis_regime"] = trace_verdict["regime"]
        print(json.dumps({
            "metric": "serve_bench" if serve_only else "ps_bench",
            "unit": "requests/sec" if serve_only else "ops/sec",
            "workers": args.ps_bench_workers,
            "legs": legs,
            "trace_path": trace_path,
            "analysis": trace_verdict,
        }))
        sys.stdout.flush()
        return
    t_start = time.perf_counter()
    # Elapsed-time budget for the beyond-reference legs (VERDICT r3 #1: the
    # round-3 run was killed by the driver mid-leg and the headline was never
    # printed; r4's run finished at 1602 s with rc 0, so the driver allows at
    # least that much — the old 780 s default left most of the allowance
    # unused). The BASELINE configs + proxy + headline ALWAYS run; each
    # extra leg then only starts if its estimated cold-cache cost fits the
    # remaining budget. --full disables the guard. Legs run in priority
    # order (flagship training/serving first), so a tight budget truncates
    # the least important legs, not the most.
    budget = float(os.environ.get("DISTKERAS_BENCH_BUDGET", 1500))

    import optax

    from distkeras_tpu.datasets import mnist
    from distkeras_tpu.models import lenet
    from distkeras_tpu.parallel.merge_rules import ADAGMerge
    from distkeras_tpu.utils import enable_compilation_cache

    # Persistent compile cache: repeat runs skip the tens-of-seconds XLA
    # compiles that dominate this script's WALL time. Measured throughput is
    # unaffected — every leg times steady-state post-warm epochs; only the
    # untimed compile+warm phase shrinks. The helper places it:
    # JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache.
    cache_dir = enable_compilation_cache()
    log(f"compilation cache: {cache_dir}")

    if args.proxy_only:
        run_proxy_only()
        return

    accel = jax.devices()[0]
    log(f"accelerator: {accel}")

    if args.leg:
        _run_single_leg(accel, args.leg)
        return

    # Spawn the CPU-proxy denominator FIRST as a concurrent subprocess
    # (JAX_PLATFORMS=cpu): its ~550 s of XLA:CPU compile+epochs overlap the
    # TPU legs instead of serially blocking them (r4: the serial proxy
    # doubled the budget on its own). Joined right before the headline.
    import subprocess
    proxy_proc = None
    if accel.platform != "cpu" and not args.skip_proxy:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=cache_dir)
        proxy_proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--proxy-only"],
            stdout=subprocess.PIPE, stderr=sys.stderr, env=env, text=True,
        )

    results = run_all_configs(accel)
    tta = None
    if accel.platform == "tpu":
        log("[time-to-accuracy] ADAG/LeNet to 0.99 test accuracy")
        tta = run_time_to_accuracy(accel)

    # headline value: the throughput-optimal leg when measured, else the
    # ratio leg; vs_baseline always compares matched configs (b256 both
    # sides — see the config-2 comment in run_all_configs)
    north = results.get("adag_mnist_cnn_peak", results["adag_mnist_cnn"])
    ratio_leg = results["adag_mnist_cnn"]

    # CPU-proxy denominator for the north-star ratio: SAME batch/window
    # (ADVICE.md), one superbatch per epoch; the reported number is the
    # MEDIAN of 3 timed epochs post-warmup (VERDICT r2: a single noisy
    # sample quoted to 2 decimals was a weak foundation for the ratio).
    vs = None
    if proxy_proc is not None:
        try:
            remaining = max(120.0, budget - (time.perf_counter() - t_start))
            out, _ = proxy_proc.communicate(timeout=remaining)
            rec = json.loads(out.strip().splitlines()[-1])
            log(f"[proxy] {rec['proxy_samples_per_sec']:.0f} samples/sec "
                f"(spread {rec['spread']:.0%})")
            # no spread gate here: the proxy reports its FASTEST epoch (see
            # run_proxy_only — contention only slows epochs, so the ratio
            # is a conservative lower bound); a memoized dispatch would
            # still trip `distinct`
            if not rec.get("distinct", True):
                log("[proxy] INVALID timing — omitting vs_baseline")
            else:
                vs = (ratio_leg["samples_per_sec"]
                      / rec["proxy_samples_per_sec"])
        except Exception as e:  # proxy died/timed out — omit the ratio
            log(f"cpu proxy failed: {e}")
            proxy_proc.kill()

    line = {
        "metric": "adag_mnist_cnn_samples_per_sec",
        "value": north["samples_per_sec"],
        "unit": "samples/sec",
        "batch_size": north.get("batch_size"),
    }
    # the headline honors the same validity gate as the stderr records: an
    # invalid north/ratio leg (impossible MFU, wild spread, memoized epoch)
    # must not ship as a clean-looking driver number
    if north.get("invalid") or ratio_leg.get("invalid"):
        line["invalid"] = True
    if vs is not None and not ratio_leg.get("invalid"):
        # matched-config ratio: TPU b256/w8 over CPU b256/w8 (see above)
        line["vs_baseline"] = round(vs, 2)
        if north is not ratio_leg:
            line["vs_baseline_config"] = "b256_w8_both_sides"
    if "mfu" in north:
        line["mfu"] = north["mfu"]
    if tta is not None and tta["reached_target"]:
        line["tta_99_seconds"] = tta["train_seconds"]
    # The headline prints BEFORE the beyond-reference legs: a driver timeout
    # during the extras can then only truncate extras, never the record
    # (VERDICT r3 weak #1). stdout carries exactly this one line either way.
    print(json.dumps(line))
    sys.stdout.flush()

    failed_legs = []
    if accel.platform == "tpu":
        def leg(title, fn, est_cold_secs):
            """Run one beyond-reference leg if its estimated cold-cache cost
            fits the remaining budget; a failure or skip never takes down
            the legs after it (each emits its records as it completes),
            but a failure is recorded and fails the run at its end."""
            elapsed = time.perf_counter() - t_start
            if not args.full and elapsed + est_cold_secs > budget:
                log(f"[skip] {title}: elapsed {elapsed:.0f}s + est "
                    f"{est_cold_secs:.0f}s exceeds budget {budget:.0f}s "
                    f"(run with --full or raise DISTKERAS_BENCH_BUDGET)")
                return
            log(title)
            try:
                fn()
            except Exception as e:
                import traceback

                log(f"[leg failed] {title}: {e}")
                traceback.print_exc(file=sys.stderr)
                failed_legs.append(title)

        # Priority order (VERDICT r4 #1: two straight rounds shipped zero
        # driver-captured evidence for the flagship legs): the flagship
        # TRAINING composition and the composed SERVING answer run first;
        # the decode ablations run last. Estimates are cold-cache; the
        # repo-local cache persists across rounds, so a warm run admits
        # every leg with room to spare.
        for title, fn, est in _LEGS_IN_PRIORITY_ORDER(accel, results):
            leg(title, fn, est)
    if args.scaling:
        run_scaling(accel)
    trace_path, trace_verdict = _finish_trace()
    if trace_path is not None:
        # the training-headline path writes its timeline too — one
        # stderr record links the run to its trace file + its verdict
        log(json.dumps({"metric": "trace", "trace_path": trace_path,
                        "analysis": trace_verdict}))
    log(f"total wall: {time.perf_counter() - t_start:.0f}s")
    if failed_legs:
        log(json.dumps({"metric": "failed_legs", "legs": failed_legs}))
        sys.exit(1)


def _LEGS_IN_PRIORITY_ORDER(accel, results):
    def config6():
        rec_t, rec_tw = run_transformer_config(accel)
        results["transformer_bf16_L2048"] = rec_t
        results["transformer_bf16_L2048_wide_heads"] = rec_tw

    return [
        ("[config 9] causal-LM training via MeshTrainer",
         lambda: results.update(run_lm_train_config(accel)), 150),
        ("[config 10] composed serving: 400M MQA + int8 + speculative",
         lambda: results.update(run_composed_decode_config(accel)), 360),
        ("[config 11] serving tier: continuous batching + paged KV cache "
         "vs sequential GeneratorPredictor",
         lambda: results.update(run_serving_bench()), 240),
        ("[config 7b] int8 weight-only serving @400M params",
         lambda: results.update(run_lm_decode_int8(accel)), 120),
        ("[config 8] speculative decoding (greedy-exact + sampled)",
         lambda: results.update(run_lm_speculative_config(accel)), 300),
        ("[config 6] transformer encoder training", config6, 180),
        ("[config 7] causal-LM KV-cached decode (MHA vs GQA vs MQA)",
         lambda: results.update(run_lm_decode_config(accel)), 120),
    ]


def _run_single_leg(accel, name):
    """--leg N: run one beyond-reference leg with no budget gate (local
    measurement workflow; the full run stays the driver's entry point)."""
    results = {}
    key = {"6": "[config 6]", "7": "[config 7]", "7b": "[config 7b]",
           "8": "[config 8]", "9": "[config 9]", "10": "[config 10]",
           "11": "[config 11]"}
    tag = key.get(str(name))
    if tag is None:
        raise SystemExit(f"unknown --leg {name!r}; choose from {list(key)}")
    for title, fn, _ in _LEGS_IN_PRIORITY_ORDER(accel, results):
        if title.startswith(tag):
            log(title)
            fn()
            return
    raise SystemExit(f"leg {name!r} not found")


if __name__ == "__main__":
    main()
