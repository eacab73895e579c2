"""The serving front door (ISSUE 17): radix prefix cache with
copy-on-write, chunked prefill, and SLO-aware multi-tenant admission.

The load-bearing oracle is ENGINE vs ENGINE: with the front door on —
any mix of ``prefix_cache=``, ``prefill_chunk=``, ``admission="slo"``,
with COW copies and preemption-by-recompute exercised — every served
stream must be bit-identical to the cache-off engine at the same seeds,
greedy AND sampled. The bookkeeping invariant the churn tests pin::

    allocator.used_blocks == Σ slots' private blocks + radix-tree blocks

must hold at every step, and after retirement + flush the pool is empty:
reuse never leaks and never corrupts.
"""

import math
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from distkeras_tpu import networking
from distkeras_tpu.deploy.rollout import RolloutController, RolloutPolicy
from distkeras_tpu.models import transformer_lm
from distkeras_tpu.serving import (
    GenerationClient,
    GenerationEngine,
    GenerationServer,
    RadixPrefixCache,
    TenantQueues,
    slo_priority,
)

# depth 1 keeps the whole paged/radix/COW machinery exercised (same
# single-layer fixture as bench._serve_lm) at half the step cost — the
# bit-identity oracles here compare ENGINE vs ENGINE, not model quality
VOCAB, MAXLEN, DIM, HEADS, DEPTH = 64, 64, 32, 4, 1


@pytest.fixture(scope="module")
def lm():
    spec = transformer_lm(vocab=VOCAB, maxlen=MAXLEN, dim=DIM, heads=HEADS,
                          depth=DEPTH, dtype=jnp.float32,
                          pos_embedding="rope", kv_heads=2)
    params, _ = spec.init_np(0)
    return spec, params


# -- radix prefix cache (host-side, no device) --------------------------------


def test_radix_match_insert_release_evict():
    c = RadixPrefixCache(4)
    toks = np.arange(12, dtype=np.int32)          # 3 full blocks
    miss = c.match(toks, 12)
    assert miss.nodes == [] and miss.cow_node is None
    assert c.misses == 1 and len(c) == 0

    new, adopted = c.insert(toks, [5, 6, 7])
    assert adopted == [5, 6, 7] and len(c) == 3
    c.release(new)                                # inserter retires

    m = c.match(toks, 12)
    assert m.blocks == [5, 6, 7] and m.tokens(4) == 12
    assert c.hits == 1
    # max_tokens caps at FULL blocks: 11 serves only two of them
    m2 = c.match(toks, 11)
    assert m2.blocks == [5, 6]
    c.release(m2.nodes)

    # the chain m pinned is eviction-proof; nothing is refcount-0
    assert c.evict(3) == []
    c.release(m.nodes)
    # LRU leaves-first: only the deepest node is childless
    assert c.evict(1) == [7]
    assert c.flush() == [6, 5] and len(c) == 0
    assert c.evictions == 3


def test_radix_cow_partial_block_divergence():
    c = RadixPrefixCache(4)
    a = np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int32)
    new, _ = c.insert(a, [3, 4])
    c.release(new)
    # b shares block 0 whole and the first TWO tokens of block 1
    b = np.array([1, 2, 3, 4, 5, 6, 9, 9], np.int32)
    m = c.match(b, 7)
    assert m.blocks == [3]
    assert m.cow_node is not None and m.cow_node.block == 4
    assert m.cow_len == 2 and m.tokens(4) == 6
    c.release(m.nodes)
    # the cap also bounds the COW span: budget 5 leaves 1 spare position
    m2 = c.match(b, 5)
    assert m2.blocks == [3] and m2.cow_len == 1
    c.release(m2.nodes)
    # total divergence on the first block: no chain, no COW
    m3 = c.match(np.array([9, 9, 9, 9], np.int32), 3)
    assert m3.nodes == [] and m3.cow_node is None


def test_radix_release_unpinned_raises_and_insert_validates():
    c = RadixPrefixCache(4)
    new, _ = c.insert(np.arange(4, dtype=np.int32), [2])
    c.release(new)
    with pytest.raises(ValueError, match="unpinned"):
        c.release(new)
    with pytest.raises(ValueError, match="blocks cover"):
        c.insert(np.arange(4, dtype=np.int32), [1, 2])
    with pytest.raises(ValueError, match="block_size"):
        RadixPrefixCache(0)


def test_radix_twin_insert_keeps_block_private():
    """Two requests prefilling the same prompt: the second's offered
    block is NOT adopted (the chain already owns one) — it stays the
    request's private block and is freed at its retirement."""
    c = RadixPrefixCache(4)
    toks = np.arange(8, dtype=np.int32)
    n1, a1 = c.insert(toks, [3, 4])
    n2, a2 = c.insert(toks, [5, 6])
    assert a1 == [3, 4] and a2 == [] and n2 == []
    assert len(c) == 2
    c.release(n1)


# -- tenant queues ------------------------------------------------------------


class _R:
    def __init__(self, rid, slo="default", tenant="t"):
        self.id, self.slo_class, self.tenant = rid, slo, tenant


def test_slo_priority_map():
    assert slo_priority("realtime") < slo_priority("interactive") \
        < slo_priority("default") < slo_priority("batch") \
        < slo_priority("best_effort")
    # unknown labels are ordinary traffic, not an error
    assert slo_priority("mystery") == slo_priority("default")


def test_tenant_queues_priority_rotation_and_fifo():
    q = TenantQueues()
    a1, a2 = _R("a1", "batch", "A"), _R("a2", "batch", "A")
    b1 = _R("b1", "batch", "B")
    rt = _R("rt", "realtime", "C")
    for r in (a1, a2, b1):
        q.push(r)
    assert len(q) == 3 and q.candidate() is a1
    q.push(rt)
    assert q.candidate() is rt          # higher class served first
    q.pop(rt)
    # round-robin across tenants within the class; FIFO within a tenant
    assert q.candidate() is a1
    q.pop(a1)
    assert q.candidate() is b1
    q.pop(b1)
    assert q.candidate() is a2
    # push_front lands at the TENANT's head (recompute order)
    b2 = _R("b2", "batch", "B")
    q.push_front(b2)
    assert q.candidate() is a2          # rotation still points at A
    a3 = _R("a3", "batch", "A")
    q.push(a3)
    with pytest.raises(ValueError, match="non-head"):
        q.pop(a3)                       # a2 is tenant A's head
    assert q.remove(a2) and not q.remove(a2)
    assert q.drain() == [a3, b2] and len(q) == 0
    assert list(iter(q)) == []


# -- engine bit-identity: the acceptance oracle -------------------------------


def _jobs(rng, n, sys_len=12, tail=5, max_new=8):
    """n requests sharing one system prompt (mixed greedy/sampled) —
    the millions-of-users shape the radix cache exists for."""
    system = rng.integers(0, VOCAB, (sys_len,)).astype(np.int32)
    jobs = []
    for i in range(n):
        p = np.concatenate(
            [system, rng.integers(0, VOCAB, (tail,)).astype(np.int32)])
        kw = dict(max_new_tokens=max_new, seed=i)
        if i % 2:
            kw.update(temperature=0.8, top_k=8)
        jobs.append((p, kw))
    return jobs


def _run_engine(spec, params, jobs, **eng_kw):
    eng = GenerationEngine(spec, params, max_batch=4, block_size=8,
                           max_queue=64, **eng_kw)
    reqs = [eng.submit(p, **kw) for p, kw in jobs]
    eng.run_until_idle()
    return eng, [np.asarray(r.result(0)) for r in reqs]


def test_prefix_hit_rate_is_nought_cold_and_most_of_a_warm_wave(lm):
    """A cold cache serves its first request from nothing; a second wave
    behind the same system prompt maps the cached blocks and prefills only
    its tails. (The hit rate is by tokens, over the engine's life.)"""
    spec, params = lm
    jobs = _jobs(np.random.default_rng(29), 5, sys_len=16, tail=3, max_new=2)
    eng = GenerationEngine(spec, params, max_batch=4, block_size=8,
                           max_queue=64, prefix_cache=True)
    eng.submit(jobs[0][0], **jobs[0][1])
    eng.run_until_idle()
    cold = eng.stats()
    assert cold["prefix_hit_rate"] == 0.0 and cold["prefix_hit_tokens"] == 0
    reqs = [eng.submit(p, **kw) for p, kw in jobs[1:]]
    eng.run_until_idle()
    assert all(r.result(0).shape == (2,) for r in reqs)
    warm = eng.stats()
    wave_tokens = warm["prefix_prompt_tokens"] - cold["prefix_prompt_tokens"]
    assert wave_tokens == sum(len(p) for p, _ in jobs[1:])
    # both whole blocks of the 16-token system prompt, for each of the four
    assert warm["prefix_hit_tokens"] / wave_tokens >= 0.5
    assert eng.prefix_hit_rate() == warm["prefix_hit_rate"] > 0.0
    eng.flush_prefix_cache()
    assert eng.stats()["blocks_in_use"] == 0


def test_frontdoor_bit_identical_to_cache_off(lm):
    """Every front-door knob combination — prefix cache (COW included),
    chunked prefill at a non-block-aligned chunk, SLO admission — serves
    streams bit-identical to the cache-off engine, greedy and sampled,
    and leaks zero blocks once the radix tree is flushed."""
    spec, params = lm
    jobs = _jobs(np.random.default_rng(11), 8)
    ref_eng, ref = _run_engine(spec, params, jobs)
    assert ref_eng.stats()["blocks_in_use"] == 0
    for kw in ({"prefix_cache": True},
               {"prefix_cache": True, "prefill_chunk": 3,
                "admission": "slo"}):
        eng, outs = _run_engine(spec, params, jobs, **kw)
        for o, r in zip(outs, ref):
            np.testing.assert_array_equal(o, r, err_msg=f"{kw}")
        s = eng.stats()
        if kw.get("prefix_cache"):
            # the shared system prompt actually got reused, with at
            # least one partial-block divergence landing as a COW copy
            assert s["prefix_hit_rate"] > 0.0
            assert s["cow_copies"] >= 1
            assert s["blocks_in_use"] == s["prefix_cached_blocks"]
            eng.flush_prefix_cache()
        assert eng.stats()["blocks_in_use"] == 0, f"leak under {kw}"


def test_frontdoor_rejects_draft_and_validates_knobs(lm):
    spec, params = lm
    with pytest.raises(ValueError, match="admission"):
        GenerationEngine(spec, params, admission="lifo")
    with pytest.raises(ValueError, match="prefill_chunk"):
        GenerationEngine(spec, params, prefill_chunk=0)
    with pytest.raises(ValueError, match="draft"):
        GenerationEngine(spec, params, prefix_cache=True, draft=spec,
                         draft_params=params)


def test_preemption_by_recompute_bit_identity(lm):
    """A block-starved SLO engine: realtime arrivals preempt a running
    best-effort row (latest admitted first); the victim re-prefills
    prompt+generated-so-far on re-admission and its final stream is
    bit-identical to an unstarved FIFO engine's."""
    spec, params = lm
    rng = np.random.default_rng(3)
    longs = [rng.integers(0, VOCAB, (24,)).astype(np.int32)
             for _ in range(3)]
    shorts = [rng.integers(0, VOCAB, (8,)).astype(np.int32)
              for _ in range(2)]
    lb = math.ceil((24 + 8) / 8)      # blocks one long row reserves
    eng = GenerationEngine(spec, params, max_batch=4, block_size=8,
                           max_queue=64, num_blocks=2 * lb + 1,
                           admission="slo")
    lreqs = [eng.submit(p, max_new_tokens=8, seed=i,
                        slo_class="best_effort", tenant="bulk")
             for i, p in enumerate(longs)]
    for _ in range(3):
        eng.step()
    # the pool holds exactly two long rows; the third is block-starved
    assert eng.stats()["active"] == 2
    sreqs = [eng.submit(p, max_new_tokens=8, seed=10 + i,
                        temperature=0.7, top_k=8,
                        slo_class="realtime", tenant="rt")
             for i, p in enumerate(shorts)]
    eng.run_until_idle()
    s = eng.stats()
    assert s["preemptions"] >= 1
    assert s["completed"] == 5 and s["blocks_in_use"] == 0
    ref = GenerationEngine(spec, params, max_batch=4, block_size=8,
                           max_queue=64)
    rl = [ref.submit(p, max_new_tokens=8, seed=i,
                     slo_class="best_effort", tenant="bulk")
          for i, p in enumerate(longs)]
    rs = [ref.submit(p, max_new_tokens=8, seed=10 + i,
                     temperature=0.7, top_k=8,
                     slo_class="realtime", tenant="rt")
          for i, p in enumerate(shorts)]
    ref.run_until_idle()
    for got, want in zip(lreqs + sreqs, rl + rs):
        np.testing.assert_array_equal(got.result(0), want.result(0))


@pytest.mark.slow  # randomized stress; the parity/preemption oracles stay fast
def test_randomized_churn_refcounts_leaks_and_bit_identity(lm):
    """The ISSUE's property test: seeded admit/preempt/cancel/eos churn
    against a small pool with every front-door feature on. At every
    scheduler step the ownership invariant holds (allocator.used ==
    Σ private + tree), refcounts never go negative (release would
    raise), nothing leaks at rest, and every COMPLETED stream is
    bit-identical to the cache-off engine."""
    spec, params = lm
    rng = np.random.default_rng(0)
    system = rng.integers(0, VOCAB, (12,)).astype(np.int32)
    jobs = []
    for i in range(14):
        if rng.random() < 0.6:
            p = np.concatenate(
                [system,
                 rng.integers(0, VOCAB,
                              (int(rng.integers(1, 10)),)).astype(np.int32)])
        else:
            p = rng.integers(0, VOCAB,
                             (int(rng.integers(4, 28)),)).astype(np.int32)
        kw = dict(
            max_new_tokens=int(rng.integers(2, 10)), seed=i,
            slo_class=("realtime", "default", "batch",
                       "best_effort")[int(rng.integers(4))],
            tenant=f"t{int(rng.integers(3))}",
        )
        if rng.random() < 0.5:
            kw.update(temperature=0.9, top_k=8)
        if rng.random() < 0.4:
            kw["eos_id"] = 7
        jobs.append((p, kw))

    eng = GenerationEngine(spec, params, max_batch=4, block_size=8,
                           max_queue=64, num_blocks=24,
                           prefix_cache=True, prefill_chunk=4,
                           admission="slo")
    reqs, pending, cancelled = [], list(jobs), set()
    for _ in range(3000):
        for _ in range(int(rng.integers(1, 4))):
            if pending:
                p, kw = pending.pop(0)
                reqs.append(eng.submit(p, **kw))
        eng.step()
        if rng.random() < 0.25 and reqs:
            j = int(rng.integers(len(reqs)))
            if reqs[j].state in ("queued", "running"):
                eng.cancel(reqs[j])
                cancelled.add(j)
        with eng._lock:
            private = sum(len(s.blocks) for s in eng._slots
                          if s is not None)
            assert eng.allocator.used_blocks == \
                private + len(eng._prefix), "ownership invariant broken"
        if not pending and eng._idle():
            break
    else:
        raise AssertionError("churn never drained")

    s = eng.stats()
    assert s["completed"] + s["cancelled"] == len(jobs)
    assert s["prefix_hit_rate"] > 0.0          # the shared prefix reused
    eng.flush_prefix_cache()
    assert eng.allocator.used_blocks == 0, "blocks leaked under churn"

    ref = GenerationEngine(spec, params, max_batch=4, block_size=8,
                           max_queue=64)
    oracle = {}
    for j, (p, kw) in enumerate(jobs):
        if j not in cancelled and reqs[j].state == "done":
            oracle[j] = ref.submit(p, **kw)
    ref.run_until_idle()
    for j, r in oracle.items():
        np.testing.assert_array_equal(
            reqs[j].result(0), r.result(0),
            err_msg=f"request {j} diverged from the cache-off engine")
    assert ref.stats()["blocks_in_use"] == 0


@pytest.mark.slow  # sockets + threads under starvation; parity oracles stay fast
def test_chaos_midstream_kill_and_preemption_storm(lm):
    """The seeded chaos leg: concurrent clients on a block-starved
    prefix-cache + SLO engine, one client killed mid-stream while
    realtime arrivals force preemptions. Every surviving stream
    completes bit-identically to the cache-off engine; the dead
    client's and the preempted rows' blocks all come back."""
    spec, params = lm
    rng = np.random.default_rng(5)
    longs = [rng.integers(0, VOCAB, (20,)).astype(np.int32)
             for _ in range(4)]
    shorts = [rng.integers(0, VOCAB, (8,)).astype(np.int32)
              for _ in range(3)]
    lb = math.ceil((20 + 16) / 8)
    eng = GenerationEngine(spec, params, max_batch=4, block_size=8,
                           max_queue=64, num_blocks=2 * lb + 1,
                           prefix_cache=True, prefill_chunk=4,
                           admission="slo")
    srv = GenerationServer(eng, poll_interval=0.02)
    srv.start()
    results, errs = {}, []

    def client(i, prompt, max_new, slo, tenant):
        try:
            c = GenerationClient("127.0.0.1", srv.port)
            results[i] = c.generate(prompt, max_new_tokens=max_new,
                                    seed=i, slo_class=slo, tenant=tenant)
            c.close()
        except Exception as e:    # surfaced below
            errs.append((i, e))

    try:
        lts = [threading.Thread(
            target=client, args=(i, p, 16, "best_effort", "bulk"))
            for i, p in enumerate(longs)]
        for t in lts:
            t.start()
        # the victim: a long best-effort stream killed mid-flight
        k = networking.connect("127.0.0.1", srv.port)
        networking.send_data(k, {
            "action": "generate", "prompt": np.ones(16, np.int32),
            "max_new_tokens": 24, "slo_class": "best_effort",
            "tenant": "bulk"})
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            s = eng.stats()
            if s["active"] >= 2 and s["blocks_free"] < 2:
                break       # saturated: realtime arrivals must preempt
            time.sleep(0.01)
        k.close()
        sts = [threading.Thread(
            target=client, args=(10 + i, p, 8, "realtime", "rt"))
            for i, p in enumerate(shorts)]
        for t in sts:
            t.start()
        for t in lts + sts:
            t.join(60)
        assert not errs, errs
        assert len(results) == 7
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            s = eng.stats()
            if s["cancelled"] >= 1 and s["active"] == 0:
                break
            time.sleep(0.02)
        s = eng.stats()
        assert s["completed"] == 7 and s["cancelled"] >= 1
        assert s["preemptions"] >= 1, "the storm never preempted"
        assert eng.flush_prefix_cache() >= 0
        assert eng.stats()["blocks_in_use"] == 0, "chaos leaked blocks"
    finally:
        srv.stop(drain=False, timeout=10)
    ref = GenerationEngine(spec, params, max_batch=4, block_size=8,
                           max_queue=64)
    want = {i: ref.submit(p, max_new_tokens=16, seed=i,
                          slo_class="best_effort", tenant="bulk")
            for i, p in enumerate(longs)}
    want.update({10 + i: ref.submit(p, max_new_tokens=8, seed=10 + i,
                                    slo_class="realtime", tenant="rt")
                 for i, p in enumerate(shorts)})
    ref.run_until_idle()
    for i, toks in results.items():
        np.testing.assert_array_equal(toks, want[i].result(0))


# -- wait_for_swap (PR 16 NOTE retired) ---------------------------------------


def test_client_wait_for_swap(lm):
    spec, params = lm
    eng = GenerationEngine(spec, params, max_batch=2, block_size=8,
                           model_version=1)
    srv = GenerationServer(eng, poll_interval=0.02)
    srv.start()
    c = GenerationClient("127.0.0.1", srv.port)
    try:
        # nothing staged: returns the current status immediately
        assert c.wait_for_swap(timeout=2.0)["staged_version"] is None
        # an idle-engine drain swap lands on the next scheduler tick —
        # wait_for_swap replaces the hand-rolled deploy_status poll
        eng.swap_params(params, 2, policy="drain")
        status = c.wait_for_swap(timeout=10.0)
        assert status["staged_version"] is None
        assert status["model_version"] == 2
        # a swap that never lands raises with the stuck status attached
        c.deploy_status = lambda: {"staged_version": 3}
        with pytest.raises(TimeoutError, match="still staged"):
            c.wait_for_swap(timeout=0.08, poll=0.01)
    finally:
        c.close()
        srv.stop(drain=False, timeout=10)


# -- progressive canary ramp --------------------------------------------------


def test_rollout_policy_progressive_ramp():
    pol = RolloutPolicy(bake_s=1.0, green_checks=1, red_checks=1,
                        cooldown_s=0.0, fractions=[0.25, 0.5, 1.0])
    acts = pol.observe(0.0, 7, True, False)
    assert acts == [{"t": 0.0, "action": "canary", "state": "canary",
                     "version": 7, "fraction": 0.25}]
    assert pol.observe(0.5, 7, True, False) == []     # still baking
    acts = pol.observe(1.5, 7, True, False)
    assert acts == [{"t": 1.5, "action": "ramp", "state": "canary",
                     "version": 7, "fraction": 0.5}]
    # each widening re-bakes and needs a FRESH green streak
    assert pol.observe(2.0, 7, True, False) == []
    acts = pol.observe(3.0, 7, True, False)
    assert acts[0]["action"] == "ramp" and acts[0]["fraction"] == 1.0
    acts = pol.observe(4.5, 7, True, False)
    assert acts[0]["action"] == "promote"
    assert pol.state == "idle" and pol.version == 7


def test_rollout_policy_ramp_rollback_and_validation():
    pol = RolloutPolicy(bake_s=0.0, green_checks=1, red_checks=1,
                        cooldown_s=0.0, fractions=[0.1, 0.5])
    assert pol.observe(0.0, 3, True, False)[0]["action"] == "canary"
    assert pol.observe(1.0, 3, True, False)[0]["action"] == "ramp"
    # the SLO firing mid-ramp rolls the WHOLE canary back to baseline
    acts = pol.observe(2.0, 3, False, True)
    assert acts[0]["action"] == "rollback" and pol.state == "idle"
    with pytest.raises(ValueError, match="strictly increasing"):
        RolloutPolicy(fractions=[0.5, 0.5])
    with pytest.raises(ValueError, match="fractions"):
        RolloutPolicy(fractions=[0.0, 0.5])
    # the default ladder is exactly the legacy single-step machine
    assert RolloutPolicy(canary_fraction=0.3).fractions == [0.3]


class _StubRouter:
    def __init__(self, keys):
        self._keys = list(keys)

    def refresh(self):
        pass

    def replica_versions(self):
        return {k: 1 for k in self._keys}


def test_rollout_controller_ramp_activates_only_new_keys():
    calls = []
    router = _StubRouter(f"r{i}" for i in range(4))
    ctrl = RolloutController(
        router, lambda k, v: calls.append((k, v)) or True,
        lambda: (True, False),
        policy=RolloutPolicy(bake_s=0.0, green_checks=1, red_checks=1,
                             cooldown_s=0.0, fractions=[0.25, 0.75]),
    )
    ctrl.begin(2)
    assert [a["action"] for a in ctrl.step(1.0)] == ["canary"]
    first = list(ctrl.canary_keys)
    assert len(first) == 1 and len(calls) == 1
    assert [a["action"] for a in ctrl.step(2.0)] == ["ramp"]
    # ceil(0.75·4) = 3 canaries, but only the TWO new ones activated
    assert len(ctrl.canary_keys) == 3
    assert ctrl.canary_keys[:1] == first
    assert len(calls) == 3
    assert [a["action"] for a in ctrl.step(3.0)] == ["promote"]
    assert len(calls) == 4            # the one non-canary remainder
    assert sorted(k for k, _ in calls) == sorted(
        router.replica_versions())    # each replica activated ONCE
    assert all(v == 2 for _, v in calls)
    assert [j["action"] for j in ctrl.journal] == \
        ["canary", "ramp", "promote"]


# -- router hit-rate affinity -------------------------------------------------


def test_replica_ring_weights_and_hit_affinity():
    from distkeras_tpu.directory.router import (
        RoutedGenerationClient,
        _ReplicaRing,
    )

    keys = [f"rep-{i}" for i in range(3)]
    base = _ReplicaRing(keys, vnodes=32)
    ones = _ReplicaRing(keys, vnodes=32,
                        weights={k: 1.0 for k in keys})
    # weight 1.0 everywhere reproduces the legacy ring point-for-point
    assert base._hashes == ones._hashes and base._owners == ones._owners
    hot = _ReplicaRing(keys, vnodes=32, weights={"rep-0": 2.0})
    points = {k: sum(1 for o in hot._owners if o == k) for k in keys}
    assert points["rep-0"] == 64
    assert points["rep-1"] == points["rep-2"] == 32
    # a warm replica owns more of the keyspace than a cold one
    rng = np.random.default_rng(0)
    owners = [next(hot.successors(int(h))) for h in
              rng.integers(0, 2**63 - 1, (2000,))]
    assert owners.count("rep-0") > owners.count("rep-1")
    # even weight 0 keeps a replica reachable (floor of one vnode)
    floor = _ReplicaRing(keys, vnodes=32, weights={"rep-0": 0.0})
    assert sum(1 for o in floor._owners if o == "rep-0") == 1
    with pytest.raises(ValueError, match="hit_affinity"):
        RoutedGenerationClient(replicas={"a": ("127.0.0.1", 1)},
                               hit_affinity=-0.5)


def test_router_weighs_ring_by_advertised_hit_rate(lm):
    """End to end through the real directory metadata: two registered
    replicas, one advertising a warm prefix cache — with hit_affinity
    on, the warm replica owns more ring points; with the default 0.0
    the ring is exactly the legacy unweighted one."""
    from distkeras_tpu.directory import DirectoryServer
    from distkeras_tpu.directory.router import RoutedGenerationClient

    spec, params = lm
    dsrv = DirectoryServer(default_ttl=5.0)
    dsrv.initialize()
    dsrv.start()
    seeds = [(dsrv.host, dsrv.port)]
    servers = []
    try:
        for i, eng_kw in enumerate(({}, {"prefix_cache": True})):
            eng = GenerationEngine(spec, params, max_batch=2,
                                   block_size=8, **eng_kw)
            srv = GenerationServer(eng, poll_interval=0.02)
            srv.start()
            srv.register_with(seeds, key=f"rep-{i}", ttl=5.0)
            servers.append(srv)
        # warm rep-1's cache so its advertised hit rate is nonzero
        warm = servers[1].engine
        p = np.arange(16, dtype=np.int32)
        for s in (0, 1):
            # drain between the twins: the second request must MATCH the
            # chain the first inserted, not race it into the same wave
            warm.submit(p, max_new_tokens=2, seed=s)
            warm.drain(timeout=20)
        assert warm.prefix_hit_rate() > 0.0
        # re-publish immediately (tests shouldn't wait for the renewer)
        servers[1].register_with(seeds, key="rep-1", ttl=5.0)

        router = RoutedGenerationClient(directory=seeds, vnodes=32,
                                        hit_affinity=4.0,
                                        refresh_interval=0.05)
        try:
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                router.refresh(force=True)
                if router.replica_hit_rates().get("rep-1", 0.0) > 0.0:
                    break
                time.sleep(0.05)
            rates = router.replica_hit_rates()
            assert rates["rep-0"] == 0.0 and rates["rep-1"] > 0.0
            pts = {k: sum(1 for o in router._ring._owners if o == k)
                   for k in ("rep-0", "rep-1")}
            assert pts["rep-1"] > pts["rep-0"]
            assert router.stats()["replica_hit_rates"] == rates
        finally:
            router.close()
        # default affinity 0.0: the exact legacy unweighted ring
        legacy = RoutedGenerationClient(directory=seeds, vnodes=32,
                                        refresh_interval=0.05)
        try:
            legacy.refresh(force=True)
            pts = {k: sum(1 for o in legacy._ring._owners if o == k)
                   for k in ("rep-0", "rep-1")}
            assert pts["rep-0"] == pts["rep-1"] == 32
        finally:
            legacy.close()
    finally:
        for srv in servers:
            srv.stop(drain=False, timeout=10)
        dsrv.stop()


# -- the watchtower rule ------------------------------------------------------


def test_prefix_hit_rate_rule():
    from distkeras_tpu.observability.timeseries import TimeSeriesStore
    from distkeras_tpu.observability.watch import (
        PrefixHitRateRule,
        default_rules,
    )

    st = TimeSeriesStore()
    rule = PrefixHitRateRule(floor=0.2, min_admitted=10)
    # engines without a prefix cache publish no series: never judged
    assert rule.evaluate(st, 0.0)[0] is None
    st.sample("serve.prefix_hit_rate", 1.0, 0.0)
    st.sample("serve.admitted", 1.0, 3, "counter")
    assert rule.evaluate(st, 1.0)[0] is None     # still warming up
    st.sample("serve.prefix_hit_rate", 2.0, 0.05)
    st.sample("serve.admitted", 2.0, 50, "counter")
    firing, worst, detail = rule.evaluate(st, 2.0)
    assert firing is True and worst == 0.05
    assert detail["hit_rate"] == 0.05 and detail["floor"] == 0.2
    st.sample("serve.prefix_hit_rate", 3.0, 0.6)
    assert rule.evaluate(st, 3.0)[0] is False    # resolved
    assert any(isinstance(r, PrefixHitRateRule) for r in default_rules())
