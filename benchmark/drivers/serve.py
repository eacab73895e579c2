"""The serve driver: ``GenerationClient`` -> ``GenerationServer`` ->
``GenerationEngine`` on loopback, load from threads of this process.

Set-up makes the weights on the device in the types they are served in, drives
every program shape the mix can meet straight through the engine (before its
loop thread exists, so that which rows share a step is decided here), starts
the server and then the clients one after another. The window opens when every
row of the batch is taken. When it closes the clients finish the request they
have in flight, which counts for latency and not for tokens.

``correct``: a sample of the requests finished in the window, drawn from the
seed, the longest always in it, goes through the plain reference once each
(prompt and served tokens in one forward). Two numbers are compared: the
widest gap by which a served token's logit lies below the reference's best,
and the mean of those gaps over the sample's served tokens.

The controls that ``correct`` has to fail are in ``benchmark/controls.py``.
"""

from __future__ import annotations

import gc
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.serving import GenerationClient, GenerationEngine, GenerationServer

from benchmark import reference, traffic_gen, weights
from benchmark.harness import CompileCounter, Tracer, memory_peak_bytes, program_lm

REPLY_WAIT_S = 60.0     # how long past the window's close an answer is waited for


def build_engine(m, mix, key):
    spec = program_lm(m, attn_impl=mix["attn_impl"])
    params = jax.jit(lambda k: weights.program_tree(m, k, mix["served_dtype"]))(key)
    return GenerationEngine(spec, params, **mix["engine"])


def warm_up(engine, m, mix, seed):
    """Every chunk program (rows x table width) and every decode width the
    mix's lengths can reach: ``rows`` requests of the longest prompt admitted
    in one step walk through all chunk widths and the widest decode step; one
    request at each shorter length meets the narrower decode steps."""
    rng = np.random.default_rng([int(seed), 0x7761726D])
    warm = mix["warmup"]
    groups = [[warm["prompt_len"]] * r for r in warm["rows"]]
    groups += [[n] for n in warm["decode_prompt_lens"]]
    for lens in groups:
        reqs = [engine.submit(rng.integers(0, m["vocab"], n, dtype=np.int32),
                              max_new_tokens=2) for n in lens]
        engine.run_until_idle()
        for r in reqs:
            r.result(timeout=1.0)


class Clients:
    """The load: ``clients`` threads, each with one connection."""

    def __init__(self, host, port, mix, plan):
        self.addr, self.plan = (host, port), plan
        self.lock = threading.Lock()
        self.next = 0
        self.stop = threading.Event()
        self.t0 = None                      # open loop: when request 0 is due
        self.records: list[dict] = []
        self.threads = [threading.Thread(target=self._run, daemon=True)
                        for _ in range(int(mix["clients"]))]

    def _take(self):
        with self.lock:
            i, self.next = self.next, self.next + 1
        return i

    def _run(self):
        client = GenerationClient(*self.addr)
        try:
            while not self.stop.is_set():
                i = self._take()
                prompt = self.plan.prompt(i)
                _, asked, due_s = self.plan.sizes(i)
                rec = {"i": i, "prompt_len": len(prompt), "asked": asked}
                if self.plan.open:
                    # timed from when the request was due, not from when a
                    # connection was free to send it
                    due = self.t0 + due_s
                    wait = due - time.perf_counter()
                    if wait > 0 and self.stop.wait(wait):
                        return
                    rec["sent"], rec["late_s"] = due, max(0.0, -wait)
                else:
                    rec["sent"] = time.perf_counter()
                try:
                    rec["tokens"] = client.generate(prompt, max_new_tokens=rec["asked"])
                except Exception as e:  # a refused or failed request is counted, not hidden
                    rec["error"] = repr(e)
                rec["done"] = time.perf_counter()
                with self.lock:
                    self.records.append(rec)
        finally:
            client.close()

    def start(self, prefills, full):
        """One after another, so that no step admits a crowd: a client starts
        once ``prefills()`` says that every request sent so far has had its
        prompt taken in. Returns once ``full()`` says every row is taken."""
        self.t0 = time.perf_counter()
        base = prefills()
        deadline = time.perf_counter() + 300.0
        for n, th in enumerate(self.threads):
            while prefills() < base + n and time.perf_counter() < deadline:
                time.sleep(0.002)
            th.start()
        while not full() and time.perf_counter() < deadline:
            time.sleep(0.01)

    def finish(self):
        self.stop.set()
        deadline = time.perf_counter() + REPLY_WAIT_S
        for th in self.threads:
            th.join(max(0.0, deadline - time.perf_counter()))
        return sum(th.is_alive() for th in self.threads)


def padded(m, r) -> np.ndarray:
    """A sampled request's prompt and served tokens, padded to the
    configuration's context so that one program reads every sequence."""
    seq = np.zeros(m["maxlen"], np.int32)
    lp, k = r["prompt_len"], len(r["tokens"])
    seq[:lp], seq[lp:lp + k] = r["prompt"], r["tokens"]
    return seq


def reference_gaps(m, mix, seed, sample, chosen=None) -> np.ndarray:
    """The reference once over each sampled request's prompt and served tokens:
    at every served position, how far the served token's logit lies below the
    reference's best. ``chosen`` (one array a request) puts other tokens in the
    served ones' place: what a control put first at the same positions."""
    key = weights.seed_key(seed)
    w = jax.jit(lambda k: weights.stacked(m, k, mix["served_dtype"]))(key)
    fwd = jax.jit(lambda w, t: reference.next_logits(m, w, t))
    gaps = []
    for i, r in enumerate(sample):
        lp, k = r["prompt_len"], len(r["tokens"])
        logits = fwd(w, jnp.asarray(padded(m, r)))[lp - 1:lp - 1 + k]
        tokens = r["tokens"] if chosen is None else chosen[i]
        gaps.append(np.asarray(reference.gap_below_best(logits, jnp.asarray(tokens))))
    return np.concatenate(gaps)


def gap_checks(gaps: np.ndarray, limits: dict) -> dict:
    """The two numbers compared: the widest gap and the mean gap."""
    return {"served_token_gap": {"value": float(gaps.max()),
                                 "limit": limits["served_token_gap"],
                                 "tokens": len(gaps),
                                 "tokens_not_the_references_first": int((gaps > 0).sum())},
            "served_gap_mean": {"value": float(gaps.mean()),
                                "limit": limits["served_gap_mean"]}}


def drive(loaded, seed: int, seconds: float, trace: bool, devices, t0: float) -> dict:
    m, mix = loaded["config"]["model"], loaded["traffic"]
    chips = loaded["cell"]["chips"]
    key = weights.seed_key(seed)
    plan = traffic_gen.Plan(mix, m["vocab"], seed)
    compiles = CompileCounter()
    tracer = Tracer(trace)
    engine = build_engine(m, mix, key)
    warm_up(engine, m, mix, seed)
    server = GenerationServer(engine)
    server.start()
    clients = Clients(server.host, server.port, mix, plan)
    try:
        clients.start(lambda: engine.stats()["prefills"],
                      lambda: engine.stats()["active"] >= engine.max_batch)
        t_open = time.perf_counter()
        before = engine.stats()
        paused = 0.0
        if trace:
            time.sleep(mix["trace_after_s"])
            paused += tracer.start()
            time.sleep(mix["trace_for_s"])
            paused += tracer.stop()
        left = t_open + seconds + paused - time.perf_counter()
        time.sleep(max(0.0, left))
        t_close = time.perf_counter()
        after = engine.stats()
        latency = engine.latency_stats(window_s=t_close - t_open)
        hung = clients.finish()
    finally:
        tracer.stop()
        compiles.close()
        server.stop(drain=False)
    window_s = t_close - t_open - paused
    peak = memory_peak_bytes(devices[:chips])
    recs = clients.records
    sent = [r for r in recs if t_open <= r["sent"] <= t_close]
    done = [r for r in recs if "tokens" in r and t_open <= r["done"] <= t_close]
    failed = sum(1 for r in sent if "error" in r) + hung
    latencies = [1e3 * (r["done"] - r["sent"]) for r in sent if "tokens" in r]
    out_tokens = sum(len(r["tokens"]) for r in done)
    # the reference needs the room that the weights and the pools take
    alive = weakref.ref(engine)
    del server, engine, clients
    gc.collect()
    if alive() is not None:
        raise RuntimeError("the engine is still held after its server stopped; "
                           "the reference would not fit beside its weights")
    # the sample: drawn from the seed among the requests finished in the
    # window, the longest always in it
    pick = np.random.default_rng([int(seed), 0x7069636B])
    order = sorted(done, key=lambda r: r["i"])
    if not order:
        raise RuntimeError(f"no request was answered in the window of {window_s:.1f} s "
                           f"({len(sent)} sent, {failed} failed)")
    longest = max(order, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in order if r is not longest]
    chosen = [longest] + [rest[j] for j in pick.permutation(len(rest))[
        : mix["check_requests"] - 1]]
    for r in chosen:
        r["prompt"] = plan.prompt(r["i"])
    checks = gap_checks(reference_gaps(m, mix, seed, chosen), mix["limits"])
    short = sum(1 for r in done if len(r["tokens"]) != r["asked"])
    checks["short_replies"] = {"value": float(short), "limit": 0.0}
    return {
        "checks": checks, "attempted": len(sent), "failed": failed,
        "window": {"seconds": window_s, "paused_for_profiler_s": paused,
                   "requests_done": len(done), "output_tokens": out_tokens,
                   "done": [(r["prompt_len"], len(r["tokens"])) for r in done],
                   "late_s": [r.get("late_s", 0.0) for r in sent]},
        "end_to_end": {"serve_tokens_per_s": out_tokens / window_s,
                       "request_p95_ms": float(np.percentile(latencies, 95)),
                       "setup_s": t_open - t0},
        "counters": {"before": before, "after": after, "latency": latency},
        "memory_peak_bytes": peak,
        "compiles_in_window": compiles.between(t_open, t_close),
        "trace_dir": tracer.directory if trace else None,
        "trace_slice_s": tracer.slice_s,
        "sample": chosen,
    }
