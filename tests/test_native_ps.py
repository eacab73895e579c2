"""Native (C++) parameter-server transport: build, folds, wire, training.

The native PS (``distkeras_tpu/native_ps.py`` + ``native/dkps.cpp``) must be
semantically interchangeable with the Python socket PS — same fold math per
merge rule, same staleness bookkeeping, same trainer surface — while moving
weights as raw float32 frames with no pickle and no GIL on the wire path.
Every test here pins the native path against the Python PS oracle
(``parameter_servers.ParameterServer``) the way the socket tests pin it.
"""

import socket
import struct
import threading

import numpy as np
import pytest

from distkeras_tpu.native import load_dkps
from distkeras_tpu.parallel.merge_rules import (
    ADAGMerge,
    DownpourMerge,
    DynSGDMerge,
    ElasticAverageMerge,
)
from distkeras_tpu.parameter_servers import ParameterServer
from tests.test_trainers import blobs_dataset, final_loss, model_spec

pytestmark = pytest.mark.skipif(
    load_dkps() is None, reason="no C++ toolchain to build libdkps"
)


def test_built_library_is_keyed_by_source_contents(tmp_path, monkeypatch):
    """The library file's name carries a hash of dkps.cpp's CONTENTS: a
    copied tree can reorder mtimes, and a library built from another
    tree's source must not load in place of this one's."""
    import os
    import shutil

    from distkeras_tpu import native

    so = native._so_path()
    assert os.path.exists(so), "load_dkps() built it at import of this file"
    src = tmp_path / "dkps.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    assert native._so_path() == so                 # same bytes, same key
    os.utime(src, (0, 0))
    assert native._so_path() == so                 # mtime plays no part
    src.write_bytes(src.read_bytes() + b"\n// edited\n")
    assert native._so_path() != so                 # other source, other file


def make_server(center, rule, num_workers, ema_decay=None):
    from distkeras_tpu.native_ps import NativeSocketParameterServer

    ps = NativeSocketParameterServer(center, rule, num_workers,
                                     ema_decay=ema_decay)
    ps.initialize()
    ps.start()
    return ps


def make_client(ps, worker_id):
    from distkeras_tpu.native_ps import NativePSClient

    return NativePSClient("127.0.0.1", ps.port, worker_id, ps.spec)


def test_flatspec_roundtrip_mixed_shapes_dtypes():
    from distkeras_tpu.native_ps import FlatSpec

    tree = {
        "dense": {"kernel": np.arange(12, dtype=np.float32).reshape(3, 4),
                  "bias": np.ones(4, np.float32)},
        "scale": np.float32(2.5),
        "emb": np.random.default_rng(0).normal(size=(5, 2)).astype(np.float32),
    }
    spec = FlatSpec(tree)
    vec = spec.flatten(tree)
    assert vec.dtype == np.float32 and vec.shape == (12 + 4 + 1 + 10,)
    back = spec.unflatten(vec)
    import jax

    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(tree)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("rule_factory", [
    lambda: ADAGMerge(),
    lambda: DownpourMerge(),
    lambda: ElasticAverageMerge(alpha=0.05),
    lambda: DynSGDMerge(),
], ids=["adag", "downpour", "elastic", "dynsgd"])
def test_native_fold_matches_python_ps(rule_factory):
    """Identical pull/commit sequences fold to the same center on both
    transports (the single-oracle contract the socket PS already honors)."""
    rng = np.random.default_rng(3)
    center = {"w": rng.normal(size=(4, 3)).astype(np.float32),
              "b": rng.normal(size=(3,)).astype(np.float32)}
    W = 3
    oracle = ParameterServer(center, rule_factory(), W)
    ps = make_server(center, rule_factory(), W)
    try:
        clients = [make_client(ps, i) for i in range(W)]
        script = [(0, "pull"), (1, "pull"), (1, "commit"), (0, "commit"),
                  (2, "pull"), (2, "commit"), (0, "pull"), (0, "commit")]
        for step, (wid, action) in enumerate(script):
            if action == "pull":
                got = clients[wid].pull()
                want = oracle.pull(wid)
                for a, b in zip(np.ravel(got["w"]), np.ravel(want["w"])):
                    np.testing.assert_allclose(a, b, rtol=1e-6)
            else:
                payload = {
                    "w": rng.normal(size=(4, 3)).astype(np.float32),
                    "b": rng.normal(size=(3,)).astype(np.float32),
                }
                clients[wid].commit(wid, payload)
                oracle.commit(wid, payload)
        assert ps.num_updates == oracle.num_updates
        got, want = ps.get_model(), oracle.get_model()
        np.testing.assert_allclose(got["w"], want["w"], rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got["b"], want["b"], rtol=1e-5, atol=1e-6)
        for c in clients:
            c.close()
    finally:
        ps.stop()


def test_native_staleness_dynsgd_over_the_wire():
    """Wire mirror of test_ps_staleness_tracking_dynsgd: worker 0 pulls at
    version 0, two commits land before its commit → τ=2 → scale 1/3."""
    center = {"w": np.zeros(1, np.float32)}
    ps = make_server(center, DynSGDMerge(), 3)
    try:
        c0, c1, c2 = (make_client(ps, i) for i in range(3))
        c0.pull()
        c1.pull(); c1.commit(1, {"w": np.array([3.0], np.float32)})
        c2.pull(); c2.commit(2, {"w": np.array([4.0], np.float32)})
        c0.commit(0, {"w": np.array([3.0], np.float32)})
        np.testing.assert_allclose(ps.get_model()["w"], [3.0 + 4.0 + 1.0],
                                   rtol=1e-6)
        for c in (c0, c1, c2):
            c.close()
    finally:
        ps.stop()


def test_native_concurrent_hammer():
    """N threads pull/commit concurrently; every update lands exactly once
    (the C++ mutex serializes folds without the GIL serializing clients)."""
    center = {"w": np.zeros(2048, np.float32)}
    ps = make_server(center, ADAGMerge(), 4)
    try:
        def worker(i):
            c = make_client(ps, i)
            for _ in range(25):
                c.pull()
                c.commit(i, {"w": np.full(2048, 0.5, np.float32)})
            c.close()

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert ps.num_updates == 100
        np.testing.assert_allclose(ps.get_model()["w"], 100 * 0.5 / 4,
                                   rtol=1e-4)
    finally:
        ps.stop()


def test_native_rejects_garbage_and_wrong_length():
    """A hostile/garbled connection is dropped at the handshake (no
    attacker-sized allocation is even possible — the frame size is pinned by
    the server's own vector length) and the server keeps serving."""
    from distkeras_tpu.native_ps import NativePSClient

    center = {"w": np.zeros(8, np.float32)}
    ps = make_server(center, DownpourMerge(), 1)
    try:
        # wrong magic
        s = socket.create_connection(("127.0.0.1", ps.port), timeout=5)
        s.sendall(b"EVIL!\n" + struct.pack("<IQ", 0, 8))
        try:
            assert s.recv(1) == b""  # dropped without an accept byte
        except ConnectionResetError:
            pass  # an RST is an equally valid "dropped"
        s.close()
        # right magic, wrong vector length → rejected in the handshake ack
        with pytest.raises(ConnectionError, match="vector length"):
            bad_spec = type("S", (), {"n": 9999})()
            NativePSClient("127.0.0.1", ps.port, 0, bad_spec)
        # the server is still alive and correct for a well-formed client
        c = make_client(ps, 0)
        c.commit(0, {"w": np.ones(8, np.float32)})
        np.testing.assert_allclose(ps.get_model()["w"], 1.0)
        c.close()
    finally:
        ps.stop()


def test_native_client_resolves_hostnames_and_bounds_roundtrips():
    """DNS names work (Python owns connection establishment — 'localhost',
    not just dotted quads) and set_timeout turns a wedged server into a
    ConnectionError instead of an eternal hang."""
    from distkeras_tpu.native_ps import NativePSClient

    center = {"w": np.zeros(4, np.float32)}
    ps = make_server(center, DownpourMerge(), 1)
    try:
        c = NativePSClient("localhost", ps.port, 0, ps.spec)
        c.commit(0, {"w": np.ones(4, np.float32)})
        np.testing.assert_allclose(ps.get_model()["w"], 1.0)
        c.close()
    finally:
        ps.stop()

    # a listener that accepts the handshake conversation never gets written:
    # connect to a silent socket and watch the bounded pull fail fast
    lst = socket.socket()
    lst.bind(("127.0.0.1", 0))
    lst.listen(1)
    try:
        silent_spec = type("S", (), {"n": 4})()
        with pytest.raises(ConnectionError, match="handshake"):
            # silent server: handshake ack never arrives — the connect-time
            # bound (connect_timeout also caps the handshake recv) fires
            NativePSClient("127.0.0.1", lst.getsockname()[1], 0,
                           silent_spec, connect_timeout=1.0)
    finally:
        lst.close()


def test_native_num_updates_setter_roundtrip():
    center = {"w": np.zeros(2, np.float32)}
    ps = make_server(center, DownpourMerge(), 1)
    try:
        ps.num_updates = 17  # the resume path in workers.py does exactly this
        assert ps.num_updates == 17
    finally:
        ps.stop()


def test_native_rejects_custom_merge_rules():
    from distkeras_tpu.native_ps import fold_mode
    from distkeras_tpu.parallel.merge_rules import MergeRule

    class Weird(MergeRule):
        def fold(self, center, commit, num_workers, staleness):
            return center

    with pytest.raises(ValueError, match="socket"):
        fold_mode(Weird(), 4)


def test_native_transport_trainer_end_to_end():
    """ADAG on backend='ps' with ps_transport='native' learns, exactly like
    the socket-transport test it mirrors."""
    from distkeras_tpu import ADAG

    ds = blobs_dataset(n=1024)
    t = ADAG(model_spec(), loss="sparse_softmax_cross_entropy",
             worker_optimizer="sgd", learning_rate=0.1, num_workers=2,
             batch_size=32, communication_window=2, num_epoch=2,
             backend="ps", ps_transport="native")
    t.train(ds, shuffle=True)
    assert final_loss(t) < 0.6


def test_native_vs_socket_transport_same_result():
    """Same trainer config, shuffle=False: the native transport's final
    params match the socket transport's (both lower to the same fold
    sequence when workers run the same deterministic schedule)."""
    from distkeras_tpu import DOWNPOUR

    def run(transport):
        ds = blobs_dataset(n=512)
        t = DOWNPOUR(model_spec(), loss="sparse_softmax_cross_entropy",
                     worker_optimizer="sgd", learning_rate=0.05,
                     num_workers=1, batch_size=32, communication_window=2,
                     num_epoch=1, backend="ps", ps_transport=transport)
        return t.train(ds)

    import jax

    a, b = run("socket"), run("native")
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=5e-5, atol=1e-6)


def test_native_int8_commit_wire_matches_codec_decode(rng):
    """Action 4 (segmented int8): the C++ fold must see exactly the tree
    Int8Codec.decode yields — per-leaf scales applied per segment — so
    worker-side error feedback matches what the center received."""
    from distkeras_tpu.parallel.compression import Int8Codec

    center = {"dense": {"kernel": np.zeros((16, 8), np.float32),
                        "bias": np.zeros(8, np.float32)},
              "gain": np.zeros(3, np.float32)}
    ps = make_server(center, DownpourMerge(), num_workers=1)
    try:
        c = make_client(ps, 0)
        codec = Int8Codec(min_size=1)
        delta = {"dense": {"kernel": rng.normal(size=(16, 8)).astype(np.float32),
                           "bias": rng.normal(size=8).astype(np.float32)},
                 "gain": rng.normal(size=3).astype(np.float32)}
        blob = codec.encode(delta)
        c.pull()
        c.commit(0, blob)           # rides the int8 wire
        got = ps.get_model()
        want = codec.decode(blob)   # DOWNPOUR fold: center += decoded
        import jax

        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
        assert ps.num_updates == 1
        c.close()
    finally:
        ps.stop()


def test_native_int8_rejects_malformed_segments(rng):
    """Hostile/garbled segment headers (lengths not summing to the pinned
    n) drop the connection without folding or oversizing anything."""
    import ctypes

    from distkeras_tpu.native_ps import _f32p

    center = {"w": np.zeros(64, np.float32)}
    ps = make_server(center, DownpourMerge(), num_workers=1)
    try:
        c = make_client(ps, 0)
        qv = np.ones(64, np.int8)
        lens = np.asarray([100], np.uint64)  # != n: must be rejected
        scales = np.ones(1, np.float32)
        rc = c._lib.dkps_client_commit_int8(
            c._handle,
            qv.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            lens.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            _f32p(scales), 1,
        )
        assert rc != 0                      # no ack: connection dropped
        assert ps.num_updates == 0
        np.testing.assert_array_equal(ps.get_model()["w"], 0.0)
        c.close()
    finally:
        ps.stop()


def test_native_transport_trains_with_int8_compression():
    """End-to-end: DOWNPOUR over the native transport with
    compression='int8' — commits ride the segmented wire (4x fewer
    payload bytes) and training still converges."""
    from distkeras_tpu import DOWNPOUR

    ds = blobs_dataset(n=2048)
    t = DOWNPOUR(model_spec(), loss="sparse_softmax_cross_entropy",
                 worker_optimizer="sgd", learning_rate=0.02, num_workers=4,
                 batch_size=32, communication_window=2, num_epoch=3,
                 backend="ps", ps_transport="native", compression="int8")
    t.train(ds, shuffle=True)
    assert final_loss(t) < 0.6, final_loss(t)


def test_native_stats_parity_with_python_ps():
    """stats() key parity: the C++ server exposes the identical counter
    set the Python PS does, and counts wire ops the same way (one pull,
    one compressed pull, one raw + one int8 commit here)."""
    from distkeras_tpu.native_ps import FlatSpec, NativePSClient
    from distkeras_tpu.parallel.compression import Int8Codec

    rng = np.random.default_rng(9)
    center = {"w": rng.normal(size=(40, 40)).astype(np.float32)}
    delta = {"w": rng.normal(size=(40, 40)).astype(np.float32)}
    ps = make_server(center, DownpourMerge(), 2)
    try:
        c0 = make_client(ps, 0)
        c1 = NativePSClient("127.0.0.1", ps.port, 1, FlatSpec(center),
                            pull_compression="int8")
        c0.pull()
        c0.commit(0, delta)
        c1.pull()
        c1.commit(1, Int8Codec(min_size=1).encode(delta))
        s = ps.stats()

        py = ParameterServer(center, DownpourMerge(), 2)
        py.pull(0)
        py.commit(0, delta)
        py.pull(1, compressed=True)
        py.commit(1, delta)
        ps_keys, py_keys = set(s), set(py.stats())
        assert ps_keys == py_keys, ps_keys ^ py_keys
        assert s["pulls"] == 1
        assert s["compressed_pulls"] == 1
        assert s["commits"] == 2
        # payload accounting: raw pull reply moves 40·40 f32, plus the
        # compressed pull's scales + int8 payload (protocol headers are
        # excluded on both transports)
        assert s["bytes_out"] >= 40 * 40 * 4 + 40 * 40
        assert s["bytes_in"] >= 40 * 40 * 4 + 40 * 40
        # 2 pull snapshots + 2 commit folds under the center mutex
        assert s["center_lock_acquires"] == 4
        assert s["center_lock_mean_hold_ns"] >= 0
        assert s["pulls_per_sec"] > 0 and s["commits_per_sec"] > 0
        c0.close()
        c1.close()
    finally:
        ps.stop()


def test_native_ema_matches_python_ps(rng):
    """The C++ per-commit EMA fold equals the Python PS's, commit for
    commit (same decay, same fold sequence)."""
    center = {"w": np.zeros(48, np.float32), "b": np.zeros(5, np.float32)}
    d = 0.7
    py = ParameterServer(center, DownpourMerge(), 1, ema_decay=d)
    ps = make_server(center, DownpourMerge(), 1, ema_decay=d)
    try:
        c = make_client(ps, 0)
        for i in range(4):
            delta = {"w": rng.normal(size=48).astype(np.float32),
                     "b": rng.normal(size=5).astype(np.float32)}
            py.pull(0); py.commit(0, delta)
            c.pull(); c.commit(0, delta)
        import jax

        for a, b in zip(jax.tree.leaves(ps.get_ema()),
                        jax.tree.leaves(py.get_ema())):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
        c.close()
    finally:
        ps.stop()


def test_native_transport_trainer_ema_end_to_end():
    from distkeras_tpu import DOWNPOUR

    ds = blobs_dataset(n=1024)
    t = DOWNPOUR(model_spec(), loss="sparse_softmax_cross_entropy",
                 worker_optimizer="sgd", learning_rate=0.02, num_workers=2,
                 batch_size=32, communication_window=2, num_epoch=2,
                 backend="ps", ps_transport="native", ema_decay=0.9)
    t.train(ds, shuffle=True)
    assert t.ema_params_ is not None
    import jax

    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(t.ema_params_))
