"""What every driver shares: the look for a chip, the compile counter, the
profiler slice, the device's memory peak and the result line."""

from __future__ import annotations

import json
import os
import sys
import time

from benchmark import loader


def find_chips(chips: int):
    """The devices to run on, or exit 2 having printed no result: any platform
    but ``tpu``, fewer chips than the cell asks, or a chip the table of peaks
    does not know."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); JAX found {len(devices)} x "
              f"{first.platform} ({first.device_kind})", file=sys.stderr)
        sys.exit(2)
    try:
        peaks = loader.load_peaks(first.device_kind)
    except loader.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        sys.exit(2)
    return devices, peaks


def program_lm(m: dict, **options):
    """The program's ``transformer_lm`` at a configuration's sizes (``m`` is the
    file's ``model`` group); ``options`` are the job's or mix's own."""
    import jax.numpy as jnp

    from distkeras_tpu.models import transformer_lm

    if m["ffn"] != 4 * m["dim"]:
        raise ValueError("TransformerLM's MLP is 4 x dim wide; this "
                         f"configuration's is {m['ffn']} for dim {m['dim']}")
    return transformer_lm(
        vocab=m["vocab"], maxlen=m["maxlen"], dim=m["dim"], heads=m["heads"],
        depth=m["depth"], kv_heads=m["kv_heads"], attn_window=m["attn_window"],
        pos_embedding=m["pos_embedding"], tie_embeddings=m["tie_embeddings"],
        dtype=jnp.dtype(m["dtype"]), **options)


class CompileCounter:
    """Clock readings of every program JAX compiles or loads from its cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.at: list[float] = []
        self._open = True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self._open and event == self.EVENT:
            self.at.append(time.perf_counter())

    def close(self):
        self._open = False

    def between(self, start, end) -> int:
        return sum(1 for t in self.at if start <= t <= end)


class Tracer:
    """A profiler slice inside the window, started and stopped by the driver.

    ``epochs=(first, count)``: a train driver calls :meth:`at_epoch` at every
    epoch boundary. A serve driver calls :meth:`start` and :meth:`stop` on its
    timer. Each returns the seconds it took, which the window leaves out."""

    def __init__(self, on: bool, epochs=(0, 0)):
        self.on, self.running = bool(on), False
        self.first, self.count = epochs
        self.directory = os.path.join(loader.ROOT, ".bench_trace") if on else None
        self.t_start = self.t_stop = None
        if on:
            import shutil

            shutil.rmtree(self.directory, ignore_errors=True)   # never a stale trace

    def start(self) -> float:
        if not self.on or self.running or self.t_stop is not None:
            return 0.0
        import jax

        t = time.perf_counter()
        # no Python stack tracing: it slows the host loop that the slice is
        # there to watch, and the reduction reads the device's lines only
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.running, self.t_start = True, time.perf_counter()
        return self.t_start - t

    def stop(self) -> float:
        if not self.running:
            return 0.0
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = False
        return time.perf_counter() - self.t_stop

    @property
    def slice_s(self):
        """How long the profiler ran, by the host's clock."""
        if self.t_start is None or self.t_stop is None:
            return None
        return self.t_stop - self.t_start

    def at_epoch(self, epoch: int) -> float:
        if epoch == self.first:
            return self.start()
        if epoch == self.first + self.count:
            return self.stop()
        return 0.0


def memory_peak_bytes(devices) -> int:
    """The fullest chip's ``peak_bytes_in_use``. On this runtime that is the
    live buffers' peak; a program's temporaries are counted apart, under
    ``peak_bytes_reserved`` (PERF.md section 4 gives both for each cell)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> str:
    """The contract's one last line; ``checks`` comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)
