"""Stream-scheduler suite (repro.compress.stream + repro.serve.compression,
DESIGN.md §6).

The contract under test: the stream/service layer reorders and overlaps
work but never changes it — every artifact and decompressed field must
be BYTE-identical to its one-shot pipeline counterpart — while honoring
the scheduling invariants: submission-order results under out-of-order
completion, per-spec batching of mixed traffic (or rejection under
``strict_uniform``), the bounded in-flight window (backpressure), and
LRU eviction in the dispatch-spec cache. Sharded parity cases need
emulated devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
the second tier-1 CI job); on a 1-device host they skip cleanly.
"""
import functools
import json
import urllib.request

import numpy as np
import pytest
import jax

from repro.compress import (CompressStream, DecompressStream, SpecCache,
                            StreamBackpressure, StreamClosed,
                            compress_preserving_mss,
                            decompress_preserving_mss)
from repro.compress import pipeline
from repro.data import synthetic_field
from repro.launch.mesh import make_data_mesh
from repro.serve import (CompressionService, ServiceConfig, ServiceOverloaded,
                         start_stats_server)

N_AVAIL = len(jax.devices())

SHAPE_3D = (8, 8, 8)
SHAPE_2D = (12, 10)


def _traffic(shape, n, seed0=0, xi_rel=1e-3):
    fields = [synthetic_field("nyx", shape=shape, seed=seed0 + s)
              .astype(np.float32) for s in range(n)]
    return fields, [xi_rel * float(np.ptp(f)) for f in fields]


@functools.lru_cache(maxsize=None)
def _solo_artifacts(shape, n, base="szlike"):
    fields, xis = _traffic(shape, n)
    return fields, xis, [compress_preserving_mss(f, xi, base=base)
                         for f, xi in zip(fields, xis)]


def _assert_identical(arts, refs):
    assert len(arts) == len(refs)
    for a, r in zip(arts, refs):
        assert a.base_payload == r.base_payload
        assert a.edit_payload == r.edit_payload
        assert tuple(a.shape) == tuple(r.shape) and a.dtype == r.dtype


# ---------------------------------------------------------------------------
# byte-identity + ordering
# ---------------------------------------------------------------------------

def test_stream_matches_one_shot():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 6)
    with CompressStream(window=4, max_batch=4) as cs:
        arts = cs.map(fields, xis)
        st = cs.stats()
    _assert_identical(arts, refs)
    assert st["completed"] == 6 and st["failed"] == 0
    assert st["in_flight"] == 0 and st["batches"] >= 2
    assert 0.0 < st["batch_occupancy"] <= 1.0
    assert st["nbytes_h2d"] > 0 and st["nbytes_d2h"] > 0


def test_ordering_under_out_of_order_completion():
    """Interleaved specs form separate batches that complete in whatever
    order the scheduler reaches them; per-request results must still land
    on the right futures, i.e. map() returns submission order."""
    f3, xi3, ref3 = _solo_artifacts(SHAPE_3D, 3)
    f2, xi2, ref2 = _solo_artifacts(SHAPE_2D, 3)
    fields = [x for pair in zip(f3, f2) for x in pair]
    xis = [x for pair in zip(xi3, xi2) for x in pair]
    refs = [x for pair in zip(ref3, ref2) for x in pair]
    with CompressStream(window=6, max_batch=4) as cs:
        arts = cs.map(fields, xis)
        st = cs.stats()
    _assert_identical(arts, refs)
    # mixed specs may not share a batch: every dispatched batch was
    # uniform, so at least one batch per spec
    assert st["batches"] >= 2


def test_mixed_shapes_batch_separately_and_xi_rides_along():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 4)
    # per-request xi within one batch: tighten two members' bounds
    xis = [xi * (0.5 if i % 2 else 1.0) for i, xi in enumerate(xis)]
    refs = [compress_preserving_mss(f, xi) for f, xi in zip(fields, xis)]
    with CompressStream(window=4, max_batch=4) as cs:
        arts = cs.map(fields, xis)
    _assert_identical(arts, refs)


def test_strict_uniform_rejects_mixed_specs():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 2)
    other = synthetic_field("nyx", shape=SHAPE_2D).astype(np.float32)
    with CompressStream(window=4, strict_uniform=True) as cs:
        fut = cs.submit(fields[0], xis[0])
        with pytest.raises(ValueError, match="strict_uniform"):
            cs.submit(other, 1e-3)
        # the pinned spec still serves
        _assert_identical([fut.result()], [refs[0]])


def test_error_propagates_to_the_request_future():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 2)
    with CompressStream(window=4, device_path=True) as cs:
        bad = cs.submit(fields[0], xis[0], base="zfplike")
        good = cs.submit(fields[1], xis[1])
        with pytest.raises(ValueError, match="szlike"):
            bad.result()
        _assert_identical([good.result()], [refs[1]])
        st = cs.stats()
    assert st["failed"] == 1 and st["completed"] == 1


def test_submit_after_close_raises():
    cs = CompressStream(window=2)
    cs.close()
    with pytest.raises(StreamClosed):
        cs.submit(np.zeros(SHAPE_3D, np.float32), 1e-3)


def test_close_drains_a_never_started_stream():
    """close() must not abandon queued Futures even when the scheduler
    was never started (start=False)."""
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 2)
    cs = CompressStream(window=4, start=False)
    futs = [cs.submit(f, xi) for f, xi in zip(fields, xis)]
    cs.close()
    _assert_identical([f.result(timeout=60) for f in futs], refs)


def test_cancelled_future_does_not_kill_the_scheduler():
    """A caller cancelling a queued request must drop it (slot freed,
    counted as failed) without crashing the scheduler or starving the
    other requests."""
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 3)
    cs = CompressStream(window=3, max_batch=2, start=False)
    futs = [cs.submit(f, xi) for f, xi in zip(fields, xis)]
    assert futs[1].cancel()
    cs.start()
    _assert_identical([futs[0].result(timeout=60),
                       futs[2].result(timeout=60)], [refs[0], refs[2]])
    cs.flush()
    st = cs.stats()
    cs.close()
    assert st["completed"] == 2 and st["failed"] == 1
    assert st["in_flight"] == 0


# ---------------------------------------------------------------------------
# backpressure
# ---------------------------------------------------------------------------

def test_backpressure_window_bound_honored():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 4)
    cs = CompressStream(window=3, max_batch=2, start=False)
    futs = [cs.submit(fields[i], xis[i], block=False) for i in range(3)]
    # window full, scheduler not draining: a non-blocking submit must
    # reject rather than grow the in-flight set
    with pytest.raises(StreamBackpressure):
        cs.submit(fields[3], xis[3], block=False)
    # a blocking submit with a timeout gives up, not deadlocks
    with pytest.raises(StreamBackpressure):
        cs.submit(fields[3], xis[3], timeout=0.05)
    cs.start()
    futs.append(cs.submit(fields[3], xis[3]))   # blocks until a slot frees
    arts = [f.result() for f in futs]
    st = cs.stats()
    cs.close()
    _assert_identical(arts, refs)
    assert st["max_in_flight"] <= 3


def test_service_overload_reject_maps_backpressure():
    with pytest.raises(ValueError):
        ServiceConfig(overload="nope")
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 1)
    svc = CompressionService(ServiceConfig(window=1, overload="reject"))
    # saturate the single-slot window via the stream's own gate, then a
    # service submit must surface ServiceOverloaded
    assert svc._compress._slots.acquire(blocking=False)
    with pytest.raises(ServiceOverloaded):
        svc.submit_compress(fields[0], xis[0])
    svc._compress._slots.release()
    _assert_identical([svc.compress(fields[0], xis[0])], [refs[0]])
    svc.close()


# ---------------------------------------------------------------------------
# spec cache
# ---------------------------------------------------------------------------

def test_spec_cache_lru_eviction():
    with pytest.raises(ValueError):
        SpecCache(maxsize=0)
    c = SpecCache(maxsize=2)
    assert c.get("a", lambda: 1) == 1
    assert c.get("b", lambda: 2) == 2
    assert c.get("a", lambda: -1) == 1          # hit: not rebuilt
    c.get("c", lambda: 3)                        # evicts b (LRU)
    assert c.stats()["evictions"] == 1 and len(c) == 2
    assert c.get("b", lambda: 20) == 20          # b was evicted -> rebuilt
    s = c.stats()
    assert s["hits"] == 1 and s["misses"] == 4 and s["size"] == 2


def test_stream_cache_hits_and_eviction_counters():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 4)
    with CompressStream(window=4, max_batch=2, cache_size=1) as cs:
        arts = cs.map(fields, [xis[0]] * 4)      # one spec: same shape + xi
        cache1 = cs.stats()["cache"]
        # a second spec (different xi) with cache_size=1 must evict
        cs.map(fields[:2], [xis[0] * 0.5] * 2)
        cache2 = cs.stats()["cache"]
    refs0 = [compress_preserving_mss(f, xis[0]) for f in fields]
    _assert_identical(arts, refs0)
    assert cache1["misses"] >= 1 and cache1["hits"] >= 1
    assert cache2["evictions"] >= 1 and cache2["size"] == 1


# ---------------------------------------------------------------------------
# fix-batching policy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["auto", "fused", "pipelined"])
def test_fix_batching_modes_all_byte_identical(mode):
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 4)
    with CompressStream(window=4, max_batch=4, fix_batching=mode) as cs:
        _assert_identical(cs.map(fields, xis), refs)


def test_fix_batching_rejects_unknown_mode():
    with pytest.raises(ValueError, match="fix_batching"):
        CompressStream(fix_batching="eager")


# ---------------------------------------------------------------------------
# decompress stream
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("base", ["szlike", "zfplike"])
def test_decompress_stream_parity(base):
    fields, xis, arts = _solo_artifacts(SHAPE_3D, 4, base=base)
    want = [decompress_preserving_mss(a) for a in arts]
    with DecompressStream(window=4, max_batch=4) as ds:
        gs = ds.map(arts)
        st = ds.stats()
    for g, w in zip(gs, want):
        np.testing.assert_array_equal(g, w)
    assert st["completed"] == 4 and st["failed"] == 0


def test_decompress_stream_mixed_spec_traffic():
    _, _, a3 = _solo_artifacts(SHAPE_3D, 2)
    _, _, a2 = _solo_artifacts(SHAPE_2D, 2)
    arts = [a3[0], a2[0], a3[1], a2[1]]
    want = [decompress_preserving_mss(a) for a in arts]
    with DecompressStream(window=4, max_batch=4) as ds:
        gs = ds.map(arts)
    for g, w in zip(gs, want):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# sharded backend serving stream members across the mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_sharded_stream_parity(n_dev):
    if N_AVAIL < n_dev:
        pytest.skip(
            f"needs {n_dev} devices, have {N_AVAIL} (run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    mesh = make_data_mesh(n_dev)
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 3)
    with CompressStream(window=3, max_batch=2, mesh=mesh) as cs:
        arts = cs.map(fields, xis)
    _assert_identical(arts, refs)       # mesh changes execution, not bytes
    want = [decompress_preserving_mss(a) for a in refs]
    with DecompressStream(window=3, max_batch=2, mesh=mesh) as ds:
        gs = ds.map(arts)
    for g, w in zip(gs, want):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# the service layer
# ---------------------------------------------------------------------------

def test_service_roundtrip_and_stats():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 3)
    with CompressionService(ServiceConfig(window=4, max_batch=2)) as svc:
        futs = [svc.submit_compress(f, xi) for f, xi in zip(fields, xis)]
        arts = [f.result() for f in futs]
        _assert_identical(arts, refs)
        gs = [svc.decompress(a) for a in arts]
        for f, xi, g in zip(fields, xis, gs):
            assert float(np.max(np.abs(f - g))) <= xi * (1 + 1e-6)
        svc.flush()
        st = svc.stats()
    assert st["compress"]["completed"] == 3
    assert st["decompress"]["completed"] == 3
    assert st["uptime_s"] > 0
    assert st["config"]["window"] == 4


def test_service_stats_count_fix_passes():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 3)
    with CompressionService(ServiceConfig(window=4, max_batch=2)) as svc:
        st0 = svc.stats()["compress"]
        arts = [svc.submit_compress(f, xi).result()
                for f, xi in zip(fields, xis)]
        svc.decompress(arts[0])
        svc.flush()
        st = svc.stats()
    assert st0["fix_iters"] == 0 and st0["fix_iters_max"] == 0
    assert st["compress"]["fix_iters"] == sum(a.fix_iters for a in refs)
    assert st["compress"]["fix_iters_max"] == max(a.fix_iters for a in refs)
    assert st["decompress"]["fix_iters"] == 0


def test_service_stats_http_endpoint():
    fields, xis, _ = _solo_artifacts(SHAPE_3D, 1)
    with CompressionService(ServiceConfig(window=2)) as svc:
        svc.compress(fields[0], xis[0])
        server = start_stats_server(svc, port=0)
        try:
            host, port = server.server_address[:2]
            with urllib.request.urlopen(
                    f"http://{host}:{port}/stats", timeout=5) as resp:
                doc = json.loads(resp.read())
            assert doc["compress"]["completed"] == 1
            assert "fields_per_sec" in doc["compress"]
            assert "cache" in doc["compress"]
            with urllib.request.urlopen(
                    f"http://{host}:{port}/healthz", timeout=5) as resp:
                assert resp.read().strip() == b"ok"
        finally:
            server.shutdown()


# ---------------------------------------------------------------------------
# on-device entropy coding through the stream (DESIGN.md §8)
# ---------------------------------------------------------------------------

def _record_pool(stream):
    """Wrap the stream's worker-pool submit so tests can assert exactly
    which jobs (by function name) the scheduler handed off."""
    jobs = []
    orig = stream._pool.submit

    def recording_submit(fn, *args, **kw):
        jobs.append(getattr(fn, "__name__", str(fn)))
        return orig(fn, *args, **kw)

    stream._pool.submit = recording_submit
    return jobs


def test_device_pack_compress_bypasses_worker_pool():
    """A device-pack batch performs ZERO host entropy work: the stream
    scheduler must never hand a device-pack member to the worker pool
    (its entropy stream left the device fully framed), while the
    artifacts stay byte-identical to one-shot device-pack calls."""
    fields, xis = _traffic(SHAPE_3D, 4)
    refs = [compress_preserving_mss(f, xi, entropy="device-pack")
            for f, xi in zip(fields, xis)]
    with CompressStream(window=4, max_batch=4, linger_ms=50) as cs:
        jobs = _record_pool(cs)
        futs = [cs.submit(f, xi, entropy="device-pack")
                for f, xi in zip(fields, xis)]
        arts = [f.result() for f in futs]
        st = cs.stats()
    assert jobs == [], f"worker pool saw {jobs} for device-pack traffic"
    _assert_identical(arts, refs)
    for a in arts:
        assert a.entropy == "device-pack"
    assert st["entropy_codecs"]["device-pack"]["count"] == 4
    assert st["entropy_codecs"]["device-pack"]["bytes"] == \
        sum(len(a.base_payload) for a in arts)


def test_device_pack_batches_sanitized_end_to_end(monkeypatch):
    """The full DESIGN.md §8 claim in one test: a device-pack batch does
    ZERO host entropy work (no worker-pool jobs) AND makes zero
    unexpected host<->device crossings. With ``MSZ_SANITIZERS=1`` the
    scheduler's device stage runs inside ``debug.no_transfers`` — any
    implicit sync would fail the batch — and the ``_transfer_hook``
    count proves the only crossings are the explicit batch-sized seams,
    one each way."""
    fields, xis = _traffic(SHAPE_3D, 4)
    refs = [compress_preserving_mss(f, xi, entropy="device-pack")
            for f, xi in zip(fields, xis)]
    with CompressStream(window=4, max_batch=4, linger_ms=50) as cs:
        # warm-up batch with sanitizers off: first dispatch compiles,
        # and compilation itself may legitimately transfer constants
        [f.result() for f in
         [cs.submit(f, xi, entropy="device-pack")
          for f, xi in zip(fields, xis)]]
        monkeypatch.setenv("MSZ_SANITIZERS", "1")
        log = []
        monkeypatch.setattr(pipeline, "_transfer_hook",
                            lambda d, n: log.append((d, n)))
        jobs = _record_pool(cs)
        futs = [cs.submit(f, xi, entropy="device-pack")
                for f, xi in zip(fields, xis)]
        arts = [f.result() for f in futs]   # raises if the guard fired
    assert jobs == [], f"worker pool saw {jobs} for device-pack traffic"
    _assert_identical(arts, refs)
    batch_bytes = sum(f.nbytes for f in fields)
    assert sum(1 for d, n in log if d == "h2d" and n >= batch_bytes) == 1, log
    # the return traffic is the framed entropy stream, which left the
    # device already compressed: nothing raw-batch-sized crosses back
    assert all(n < batch_bytes for d, n in log if d == "d2h"), log


def test_deflate_compress_still_uses_worker_pool():
    fields, xis, refs = _solo_artifacts(SHAPE_3D, 3)
    with CompressStream(window=3, max_batch=3, linger_ms=50) as cs:
        jobs = _record_pool(cs)
        arts = cs.map(fields, xis)
        st = cs.stats()
    assert "_finish_compress" in jobs   # deflate encode runs on workers
    _assert_identical(arts, refs)
    assert st["entropy_codecs"]["deflate"]["count"] == 3


def test_entropy_is_part_of_the_coalescing_spec():
    """Mixed-codec traffic of one shape must not share a batch — a
    device-pack member inside a deflate batch (or vice versa) would
    force a whole-batch codec decision."""
    fields, xis = _traffic(SHAPE_3D, 4)
    with CompressStream(window=4, max_batch=4, linger_ms=60) as cs:
        futs = [cs.submit(f, xi, entropy=e)
                for (f, xi, e) in zip(fields, xis,
                                      ["deflate", "device-pack"] * 2)]
        arts = [f.result() for f in futs]
        st = cs.stats()
    assert st["batches"] >= 2           # codecs split the batch
    for a, e in zip(arts, ["deflate", "device-pack"] * 2):
        assert a.entropy == e
        ref = compress_preserving_mss(
            fields[arts.index(a)], xis[arts.index(a)], entropy=e)
        assert a.base_payload == ref.base_payload


def test_device_pack_decompress_runs_inline():
    fields, xis = _traffic(SHAPE_3D, 3)
    arts = [compress_preserving_mss(f, xi, entropy="device-pack")
            for f, xi in zip(fields, xis)]
    want = [decompress_preserving_mss(a) for a in arts]
    with DecompressStream(window=3, max_batch=3, linger_ms=50) as ds:
        jobs = _record_pool(ds)
        gs = ds.map(arts)
        st = ds.stats()
    assert jobs == [], f"worker pool saw {jobs} for device-pack artifacts"
    for g, w in zip(gs, want):
        np.testing.assert_array_equal(g, w)
    assert st["entropy_codecs"]["device-pack"]["count"] == 3


def test_stream_submit_rejects_bad_entropy():
    f, xis = _traffic(SHAPE_3D, 1)
    with CompressStream(window=1) as cs:
        with pytest.raises(ValueError, match="entropy"):
            cs.submit(f[0], xis[0], entropy="huffman")
        with pytest.raises(ValueError, match="szlike"):
            cs.submit(f[0], xis[0], base="zfplike", entropy="device-pack")


def test_service_forwards_entropy_and_reports_codecs():
    fields, xis = _traffic(SHAPE_3D, 2)
    ref = compress_preserving_mss(fields[0], xis[0], entropy="device-pack")
    with CompressionService(ServiceConfig(window=4, max_batch=2)) as svc:
        a = svc.compress(fields[0], xis[0], entropy="device-pack")
        b = svc.compress(fields[1], xis[1])            # default: deflate
        g = svc.decompress(a)
        st = svc.stats()
    assert a.base_payload == ref.base_payload
    assert b.entropy == "deflate"
    np.testing.assert_array_equal(g, decompress_preserving_mss(ref))
    assert st["compress"]["entropy_codecs"]["device-pack"]["count"] == 1
    assert st["compress"]["entropy_codecs"]["deflate"]["count"] == 1
    assert st["decompress"]["entropy_codecs"]["device-pack"]["count"] == 1


# ---------------------------------------------------------------------------
# SpecCache build race (one winner per key)
# ---------------------------------------------------------------------------

def test_spec_cache_build_race_single_winner():
    """Concurrent misses of one key must converge on ONE cached instance:
    the old code re-inserted every racer's build unconditionally, so the
    loser's instance replaced the winner's and callers ended up holding
    two distinct backends for one spec (churning jit cache keys). The
    losing build is counted as a hit — the caller got the cached value."""
    import threading

    cache = SpecCache(8)
    n = 6
    barrier = threading.Barrier(n)
    built = []

    def build():
        barrier.wait()          # every thread reaches its miss before
        obj = object()          # anyone can insert: maximal race
        built.append(obj)
        return obj

    results = [None] * n

    def worker(i):
        results[i] = cache.get("spec", build)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(built) == n                     # everyone built (raced)...
    assert len({id(r) for r in results}) == 1  # ...but all hold ONE winner
    st = cache.stats()
    assert st["misses"] == 1                   # one true miss
    assert st["hits"] == n - 1                 # losers reclassified as hits
    assert st["size"] == 1
    assert cache.get("spec", lambda: object()) is results[0]
