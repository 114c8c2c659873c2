"""The edit codec's chunked DEFLATE writer (compress/codec.py).

A stream longer than ``codec.CHUNK`` is DEFLATE'd in parallel chunks that
form one zlib stream; a shorter one is a single ``zlib.compress(buf, 9)``.
These tests pin the readers' view (stock zlib, ``decode_edits``, the
benchmark's independent reference), the byte identity of short streams,
and that the bytes depend on neither the pool's size nor other callers.
"""
from __future__ import annotations

import functools
import struct
import textwrap
import threading
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from bench import reference
from repro.compress import codec
from tools.mszlint import lint_source
from tools.mszlint.config import DEFAULT
from tools.mszlint.rules import locks

C = codec.CHUNK
LENGTHS = [0, 1, C - 1, C, C + 1, 3 * C + 7]
WIDTH = {"f4": 4, "bf16": 2, "f8": 8}


def _patchy(n: int, seed: int) -> np.ndarray:
    """Bytes like a real key stream: runs of 1s (edit patches) between
    geometric gaps, all deltas below 128 so one byte each."""
    rng = np.random.default_rng(seed)
    d = np.where(rng.random(n) < 0.7, 1, rng.geometric(0.05, n))
    return np.minimum(d, 127).astype(np.uint8)


@functools.lru_cache(maxsize=None)
def _stream(length: int) -> bytes:
    return _patchy(length, 2).tobytes()


def _edits(n: int, dtype: str, seed: int = 0):
    """``n`` sorted edits whose key stream is exactly ``n`` raw bytes."""
    rng = np.random.default_rng(seed)
    idx = np.cumsum(_patchy(n, seed).astype(np.int64))
    vdt = np.float64 if dtype == "f8" else np.float32
    val = (rng.normal(size=n) * 1e-3).astype(vdt)
    if dtype == "bf16":                      # bf16-exact, so it round-trips
        val = (val.view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32)
    return idx, val


@functools.lru_cache(maxsize=None)
def _serial_chunks(length: int) -> bytes:
    """The chunked stream of ``_stream(length)`` built on the calling
    thread, one chunk at a time: what any pool must reproduce."""
    buf = _stream(length)
    mv = memoryview(buf)
    body = [codec._deflate_chunk(mv, lo, min(lo + C, len(buf)))
            for lo in range(0, len(buf), C)]
    return b"".join([b"\x78\xda"] + body
                    + [struct.pack(">I", zlib.adler32(buf))])


@pytest.mark.parametrize("length", LENGTHS)
def test_writer_is_one_zlib_stream(length):
    buf = _stream(length)
    (out,) = codec._zlib9([buf])
    assert zlib.decompress(out) == buf
    if length <= C:
        assert out == zlib.compress(buf, 9)
    else:
        assert out == _serial_chunks(length)


@pytest.mark.parametrize("dtype", ["f4", "bf16", "f8"])
@pytest.mark.parametrize("n", LENGTHS)
def test_encode_edits_roundtrip_across_chunk_edges(dtype, n):
    idx, val = _edits(n, dtype)
    blob = codec.encode_edits(idx, val, dtype)
    idx2, val2 = codec.decode_edits(blob)
    np.testing.assert_array_equal(idx2, idx)
    np.testing.assert_array_equal(val2, val)
    assert val2.dtype == (np.float64 if dtype == "f8" else np.float32)


@pytest.mark.parametrize("dtype", ["f4", "bf16", "f8"])
def test_single_chunk_blob_is_byte_identical(dtype):
    """A blob whose streams fit one chunk is exactly the serial layout:
    header, ``zlib.compress(keys, 9)``, ``zlib.compress(values, 9)``."""
    n = C // WIDTH[dtype]
    idx, val = _edits(n, dtype, seed=4)
    keys = codec._varint_encode(np.diff(idx, prepend=np.int64(0)))
    raw = (codec._f32_to_bf16(val).tobytes() if dtype == "bf16"
           else val.tobytes())
    ks, vs = zlib.compress(keys, 9), zlib.compress(raw, 9)
    dt = {"f4": 0, "bf16": 1, "f8": 2}[dtype]
    want = struct.pack("<4sBQQQ", b"MSE1", dt, n, len(ks), len(vs)) + ks + vs
    assert codec.encode_edits(idx, val, dtype) == want


@pytest.mark.parametrize("workers", [1, 2, 8])
def test_bytes_do_not_depend_on_pool_size(monkeypatch, workers):
    with ThreadPoolExecutor(max_workers=workers) as pool:
        monkeypatch.setattr(codec, "_pool", pool)
        (out,) = codec._zlib9([_stream(3 * C + 7)])
    assert out == _serial_chunks(3 * C + 7)


def test_concurrent_callers_share_the_pool_and_get_serial_bytes(monkeypatch):
    # f4 values of 2 chunks each, keys of one
    inputs = [_edits(C // 4 + 1 + 4099 * k, "f4", seed=10 + k)
              for k in range(8)]
    with ThreadPoolExecutor(max_workers=1) as one:
        monkeypatch.setattr(codec, "_pool", one)
        serial = [codec.encode_edits(i, v) for i, v in inputs]
    monkeypatch.setattr(codec, "_pool", None)
    got = [None] * len(inputs)
    start = threading.Barrier(len(inputs))

    def call(k):
        start.wait()
        got[k] = codec.encode_edits(*inputs[k])

    threads = [threading.Thread(target=call, args=(k,))
               for k in range(len(inputs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == serial
    assert codec._pool is not None
    codec._pool.shutdown()


@pytest.mark.parametrize("stream", ["keys", "f4 values"])
def test_multi_chunk_stream_costs_under_a_tenth_percent(stream):
    if stream == "keys":
        buf, chunked = _stream(3 * C + 7), _serial_chunks(3 * C + 7)
    else:
        buf = _edits((3 * C + 7) // 4, "f4")[1].tobytes()
        (chunked,) = codec._zlib9([buf])
    serial = len(zlib.compress(buf, 9))
    assert abs(len(chunked) / serial - 1) < 1e-3, (len(chunked), serial)


@pytest.mark.parametrize("dtype", ["f4", "bf16"])
def test_reference_decoder_reads_multi_chunk_blob(dtype):
    """The benchmark's reference, which imports nothing of the program,
    reads a blob whose streams both took the chunked writer."""
    n = C + 5
    idx, val = _edits(n, dtype, seed=6)
    blob = codec.encode_edits(idx, val, dtype)
    idx2, val2 = reference.decode_edits(blob, int(idx[-1]) + 1)
    np.testing.assert_array_equal(idx2, idx)
    np.testing.assert_array_equal(val2, val)


def test_counter_counts_chunked_streams_only():
    before = codec.coding_counts()
    codec.encode_edits(*_edits(1000, "f4"))
    small = codec.coding_counts()
    assert small == dict(before, blobs=before["blobs"] + 1)
    n = 2 * C          # keys: 2 chunks; f4 values: 8 chunks
    codec.encode_edits(*_edits(n, "f4"))
    big = codec.coding_counts()
    assert big == {"blobs": small["blobs"] + 1,
                   "chunked_streams": small["chunked_streams"] + 2,
                   "chunks": small["chunks"] + 2 + 8}


def test_codec_counters_are_lock_guarded():
    """codec.py is on the lock-guard surface: a counter bumped outside
    its lock is flagged."""
    out = lint_source("src/repro/compress/codec.py", textwrap.dedent("""
        import threading
        _lock = threading.Lock()
        chunks_coded = 0  # guarded-by: _lock

        def bump():
            global chunks_coded
            chunks_coded += 1
        """), DEFAULT, rules=[locks])
    assert [f.rule for f in out] == [locks.RULE]
