"""Long-context training tour: flash attention, remat, sequence parallelism.

The reference (2016-era Spark/Keras) had no long-context story at all
(SURVEY.md §5.7); this rebuild makes it first-class. Three legs:

1. **flash attention** (`attn_impl="flash"`, Pallas) — O(block²) on-chip
   score memory for BOTH forward and backward (blockwise dq/dk/dv from the
   saved log-sum-exp); what it buys a training step on the chip is in
   PERF.md (``flash_roofline``).
2. **rematerialization** (`remat=True`) — `jax.checkpoint` per encoder
   block: activations are recomputed in the backward pass and not kept.
3. **sequence parallelism** — the whole forward+backward in one `shard_map`
   with activations sharded along L (`sequence_parallel_transformer_forward`):
   per-chip activation memory O(L/N), so context scales with the mesh.

Run ``--quick`` for CI-sized shapes (used by tests/test_examples.py); on a
CPU-only host set::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/longcontext.py --quick
"""

import argparse
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
os.environ.setdefault("KERAS_BACKEND", "jax")

import jax
import jax.numpy as jnp
import numpy as np


def train_step_fn(spec):
    import optax

    from distkeras_tpu.ops.losses import sparse_softmax_cross_entropy

    tx = optax.adam(1e-3)

    def step(params, opt, nt, toks, mask, y):
        def loss_fn(p):
            out, new_nt = spec.apply(p, nt, (toks, mask), training=True)
            return sparse_softmax_cross_entropy(y, out), new_nt

        (loss, nt2), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, updates), opt, nt2, loss

    return tx, jax.jit(step, donate_argnums=(0, 1))


def demo_flash_and_remat(quick: bool):
    """One full training step at long L with the memory levers on."""
    from distkeras_tpu.models import transformer_classifier

    on_tpu = jax.default_backend() == "tpu"
    L = 512 if quick else 4096
    B = 2 if quick else 8
    dims = dict(dim=64, heads=4, depth=2) if quick else \
        dict(dim=512, heads=8, depth=8)
    impl = "flash" if on_tpu else "reference"
    spec = transformer_classifier(
        vocab=1000, maxlen=L, num_classes=4, attn_impl=impl,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32, remat=True, **dims)
    params, nt = spec.init_np(0)
    tx, step = train_step_fn(spec)
    opt = tx.init(params)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 1000, size=(B, L)).astype(np.int32)
    mask = np.ones((B, L), np.float32)
    y = rng.integers(0, 4, size=(B,)).astype(np.int32)
    params, opt, nt, loss = step(params, opt, nt, toks, mask, y)
    jax.block_until_ready(loss)
    print(f"[flash+remat] L={L} B={B} {dims} attn={impl}: one fwd+bwd+adam "
          f"step OK, loss={float(loss):.4f}")


def demo_sequence_parallel(quick: bool):
    """Model-level SP: forward+grad with activations sharded along L."""
    from distkeras_tpu.models.transformer import (
        TransformerClassifier,
        sequence_parallel_transformer_forward,
    )
    from distkeras_tpu.parallel.mesh import get_mesh

    n = len(jax.devices())
    mesh = get_mesh(n, axis="sp")
    L = 16 * n if quick else 256 * n
    module = TransformerClassifier(vocab=1000, maxlen=L, dim=64, heads=4,
                                   depth=2, num_classes=4,
                                   dtype=jnp.float32)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 1000, size=(2, L)).astype(np.int32)
    mask = np.ones((2, L), np.float32)
    params = module.init(jax.random.PRNGKey(0), toks, mask,
                         training=False)["params"]

    def loss(p):
        lg = sequence_parallel_transformer_forward(
            module, p, toks, mask, mesh)
        return jnp.mean(lg ** 2)

    val, grads = jax.value_and_grad(loss)(params)
    gn = sum(float(jnp.sum(g ** 2)) for g in jax.tree.leaves(grads))
    print(f"[sp] L={L} sharded over {n} device(s): fwd+bwd OK, "
          f"loss={float(val):.4f}, grad norm²={gn:.3e} — per-chip "
          f"activations hold L/N={L // n} positions")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized shapes (small L, tiny model)")
    args = ap.parse_args()
    print(f"devices: {len(jax.devices())} × {jax.devices()[0].platform}")
    demo_flash_and_remat(args.quick)
    demo_sequence_parallel(args.quick)


if __name__ == "__main__":
    main()
