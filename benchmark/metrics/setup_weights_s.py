"""Seconds the one ``train()`` call spent making its weights and putting its
state on the chips: the run log's ``train.init_weights`` (``spec.init_np``, or
a checkpoint's restore) and ``train.init_state`` (``engine.init_state`` or
``place_state``), less what JAX traced, lowered and compiled inside them: that
is ``setup_trace_lower_s`` and ``setup_compile_s``, so the three add up."""

from benchmark import spans

JAX = ("jax.trace", "jax.lower", "jax.compile")


def read(run):
    found = spans.setup_entries(run)
    if found is None:
        return None
    jax = [e for e in found if e["name"] in JAX]
    phases = spans.of_last_call(found, ("train.init_weights", "train.init_state"))
    return spans.union_s(phases + jax) - spans.union_s(jax)
