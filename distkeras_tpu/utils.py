"""Utilities: pytree math, model/weight (de)serialization, history helpers.

Parity with reference ``distkeras/utils.py`` (symbols
``serialize_keras_model``, ``deserialize_keras_model``, ``uniform_weights``,
``shuffle``, ``new_dataframe_row``, ``to_dense_vector`` and history helpers —
cited at symbol granularity, SURVEY.md §0/§2b #14).

The reference serialized Keras 1.x models as architecture-JSON + weight lists
and moved them around with pickle. Here the canonical in-memory form is a JAX
pytree of arrays; Keras 3 models are (de)serialized through the same
architecture-JSON + weights contract for API parity.
"""

from __future__ import annotations

import io
import os
import json
import pickle
import time
from typing import Any, Callable, Iterable, Mapping

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

# ---------------------------------------------------------------------------
# Pytree math — host-side building blocks for the async PS backend,
# checkpointing, and serde. (The sync merge rules inline their jax.tree.map
# calls so each fold reads as one formula.)
# ---------------------------------------------------------------------------


def tree_add(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: Pytree, b: Pytree) -> Pytree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(a: Pytree, s) -> Pytree:
    return jax.tree.map(lambda x: x * s, a)


def tree_zeros_like(a: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, a)


def tree_stack(trees: Iterable[Pytree]) -> Pytree:
    """Stack identical pytrees along a new leading (worker) axis."""
    trees = list(trees)
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def tree_unstack(tree: Pytree, n: int) -> list[Pytree]:
    return [jax.tree.map(lambda x: x[i], tree) for i in range(n)]


def tree_broadcast_to_workers(tree: Pytree, num_workers: int) -> Pytree:
    """Replicate a pytree along a new leading worker axis of size W."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (num_workers,) + x.shape), tree
    )


def tree_size_bytes(tree: Pytree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def tree_count_params(tree: Pytree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_to_numpy(tree: Pytree) -> Pytree:
    return jax.tree.map(np.asarray, tree)


# ---------------------------------------------------------------------------
# Weight serialization (host side).
#
# The reference shipped pickled weight lists over TCP
# (``distkeras/networking.py :: send_data/recv_data``). Weights here are
# serialized as an .npz payload plus a pickled treedef — the pickle never
# crosses a trust boundary (same-user processes of this framework only).
# ---------------------------------------------------------------------------


def serialize_weights(tree: Pytree) -> bytes:
    leaves, treedef = jax.tree.flatten(tree)
    buf = io.BytesIO()
    np.savez(buf, *[np.asarray(leaf) for leaf in leaves])
    return pickle.dumps({"treedef": treedef, "npz": buf.getvalue()})


def deserialize_weights(data: bytes) -> Pytree:
    payload = pickle.loads(data)
    with np.load(io.BytesIO(payload["npz"])) as npz:
        leaves = [npz[k] for k in npz.files]
    return jax.tree.unflatten(payload["treedef"], leaves)


def uniform_weights(tree: Pytree, bounds=(-0.5, 0.5), seed: int = 0) -> Pytree:
    """Reinitialize every leaf uniformly in ``bounds``.

    Parity: reference ``distkeras/utils.py :: uniform_weights``.
    """
    lo, hi = bounds
    key = jax.random.PRNGKey(seed)
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    new_leaves = [
        jax.random.uniform(k, l.shape, jnp.float32, lo, hi).astype(l.dtype)
        for k, l in zip(keys, leaves)
    ]
    return jax.tree.unflatten(treedef, new_leaves)


# ---------------------------------------------------------------------------
# Keras 3 model serde — API parity with the reference's
# ``serialize_keras_model`` / ``deserialize_keras_model``.
# ---------------------------------------------------------------------------


def serialize_keras_model(model) -> dict:
    """Serialize a Keras 3 model to {architecture json, weights}.

    Parity: reference ``distkeras/utils.py :: serialize_keras_model`` which
    stored ``model.to_json()`` + ``model.get_weights()``.
    """
    return {
        "model": model.to_json(),
        "weights": [np.asarray(w) for w in model.get_weights()],
    }


def deserialize_keras_model(payload: Mapping) -> "Any":
    import keras

    model = keras.models.model_from_json(payload["model"])
    model.set_weights(payload["weights"])
    return model


def json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON serializable: {type(o)}")


# ---------------------------------------------------------------------------
# Training history — parity with ``Trainer.get_history`` and the history
# helpers in reference ``distkeras/utils.py`` (SURVEY.md §5.5).
# ---------------------------------------------------------------------------


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    First-compile latency is the dominant interactive cost on TPU (tens of
    seconds per trainer program); with the cache, identical programs (same
    model/config/shape) skip XLA compilation on every later run. Call once,
    before the first compile; returns the cache directory. The place is a
    deployment setting, so it comes from outside: ``JAX_COMPILATION_CACHE_DIR``
    (JAX's own variable) when set, and no other directory then; otherwise
    ``.jax_cache`` beside the package — the checkout root. The path is part
    of nothing that changes between runs, so a second run finds the first
    one's programs. (The test suite keeps the cache off — tests/conftest.py.)
    """
    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache",
    )
    jax.config.update("jax_compilation_cache_dir", directory)
    return directory


# ---------------------------------------------------------------------------
# Row/frame parity helpers (reference ``distkeras/utils.py``).
# ---------------------------------------------------------------------------


def shuffle(dataset):
    """Parity: reference ``distkeras/utils.py :: shuffle(df)``."""
    return dataset.shuffle()


def new_dataframe_row(row: Mapping, name: str, value) -> dict:
    """Parity: reference ``new_dataframe_row`` — row + one new column."""
    out = dict(row)
    out[name] = value
    return out


def to_vector(label, n: int) -> np.ndarray:
    """Integer class label → one-hot float vector (parity: ``to_vector``)."""
    v = np.zeros(n, dtype=np.float32)
    v[int(label)] = 1.0
    return v


def to_dense_vector(values, indices=None, n: int | None = None) -> np.ndarray:
    """Sparse (indices, values) → dense vector (parity: ``to_dense_vector``);
    with ``indices=None`` just casts to a dense float array."""
    if indices is None:
        return np.asarray(values, dtype=np.float32)
    out = np.zeros(n, dtype=np.float32)
    out[np.asarray(indices, dtype=np.int64)] = values
    return out


class History:
    """Append-only per-run training history (loss per step/window per worker)."""

    def __init__(self):
        self.records: list[dict] = []

    def append(self, **record):
        self.records.append(record)

    def losses(self) -> list[float]:
        return [r["loss"] for r in self.records if "loss" in r]

    def val_losses(self) -> list[float]:
        """Per-epoch held-out losses (trainers' ``validation_data``)."""
        return [r["val_loss"] for r in self.records if "val_loss" in r]

    def to_json(self) -> str:
        return json.dumps(self.records, default=json_default)

    def __len__(self):
        return len(self.records)

    def __iter__(self):
        return iter(self.records)


class Timer:
    """Wall-clock bookkeeping.

    Parity: reference ``distkeras/trainers.py ::
    Trainer.record_training_start/record_training_end/get_training_time``.
    """

    def __init__(self):
        self.start_time = None
        self.end_time = None

    def start(self):
        self.start_time = time.time()

    def stop(self):
        self.end_time = time.time()

    def elapsed(self) -> float:
        if self.start_time is None:
            return 0.0
        end = self.end_time if self.end_time is not None else time.time()
        return end - self.start_time
