"""Truncation contracts of the fixed-shape kernels (VERDICT r1 weak #5).

Every fixed-capacity op documents what happens past ``cap``:
- expand_csr silently truncates its output but returns the TRUE total —
  callers must compare and re-bucket;
- range_rows returns (rows, n) where n > cap signals the caller chose
  too small a cap.

These tests pin those contracts directly AND drive the public query path
across bucket boundaries to prove the engine's cap planning never lets a
truncation escape as a wrong answer.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from dgraph_tpu import ops
from dgraph_tpu.ops.sets import SENT
from dgraph_tpu.models import PostingStore
from dgraph_tpu.query import QueryEngine


def test_expand_csr_truncation_signals_true_total():
    # 4 rows of degree 8 = 32 edges; cap 16 truncates but reports 32
    offsets = jnp.asarray(np.arange(0, 33, 8, dtype=np.int32))
    dst = jnp.asarray(np.arange(32, dtype=np.int32))
    rows = jnp.asarray(np.array([0, 1, 2, 3], dtype=np.int32))
    out, seg, total = ops.expand_csr(offsets, dst, rows, 16)
    assert int(total) == 32, "true total must be reported even when truncated"
    out = np.asarray(out)
    assert (out != SENT).sum() == 16, "output silently truncates at cap"
    # re-bucketing on the reported total recovers everything
    out2, _s, total2 = ops.expand_csr(offsets, dst, rows, ops.bucket(int(total)))
    got = np.asarray(out2)
    assert int(total2) == 32
    assert np.array_equal(got[got != SENT], np.arange(32))


def test_range_rows_reports_n_over_cap():
    rows, n = ops.range_rows(jnp.int32(10), jnp.int32(100), 32)
    assert int(n) == 90, "n must report the TRUE range size"
    rows = np.asarray(rows)
    assert (rows >= 0).sum() == 32, "rows output truncates at cap"
    # caller re-buckets on the signal
    rows2, n2 = ops.range_rows(jnp.int32(10), jnp.int32(100), ops.bucket(int(n)))
    r2 = np.asarray(rows2)
    assert np.array_equal(r2[r2 >= 0], np.arange(10, 100))


@pytest.mark.parametrize("n_vals", [7, 8, 9, 1023, 1024, 1025])
def test_inequality_range_across_bucket_boundaries(n_vals):
    """ge() over an int index whose matching row count lands below/at/
    above power-of-two bucket sizes: the engine's cap planning must
    return every match (no silent truncation escapes to results)."""
    eng = QueryEngine(PostingStore())
    lines = [f'<0x{i:x}> <v> "{i}" .' for i in range(1, n_vals + 1)]
    eng.run(
        "mutation { schema { v: int @index(int) . } set { %s } }"
        % "\n".join(lines)
    )
    out = eng.run("{ q(func: ge(v, 1)) { v } }")
    got = sorted(o["v"] for o in out["q"])
    assert got == list(range(1, n_vals + 1)), (
        f"lost matches at n={n_vals}: got {len(got)}"
    )


def test_huge_fanout_expansion_is_complete():
    """One source uid with a posting list crossing several bucket sizes:
    every target must come back (expand cap planning is exact)."""
    eng = QueryEngine(PostingStore())
    n = 3000  # crosses 2048 → 4096 bucket
    lines = [f"<0x1> <e> <0x{i:x}> ." for i in range(2, n + 2)]
    eng.run("mutation { schema { e: uid . } set { %s } }" % "\n".join(lines))
    out = eng.run("{ q(func: uid(0x1)) { count(e) } }")
    assert out["q"][0]["count(e)"] == n
    out = eng.run("{ q(func: uid(0x1)) { e { _uid_ } } }")
    assert len(out["q"][0]["e"]) == n


# --------------------------------------------------------------------------
# WAL torn-tail truncation (ISSUE 6 satellite): replay_records now streams
# frames with a bounded buffer instead of slurping the file — these tests
# pin that the TRUNCATION contract stayed byte-identical across every
# chunk-boundary shape the streaming reader sees.

import os
import struct
import zlib

from dgraph_tpu.models.wal import Wal, replay_records

_HDR = struct.Struct("<II")
_CHUNK = 1 << 20  # replay_records' read granularity


def _frame(payload: bytes) -> bytes:
    return _HDR.pack(len(payload), zlib.crc32(payload)) + payload


def _write_wal(path, payloads, tail=b""):
    with open(path, "wb") as f:
        for p in payloads:
            f.write(_frame(p))
        f.write(tail)


@pytest.mark.parametrize("tail", [
    b"",                      # clean file
    b"\x07",                  # sub-header garbage
    _HDR.pack(64, 0),         # header promising bytes that never came
    _frame(b"x" * 50)[:-11],  # record torn mid-payload
])
def test_wal_streaming_truncation_byte_identical(tmp_path, tail):
    """For every torn-tail shape: the yielded records, the truncation
    point, and the repaired file bytes are exactly the good prefix."""
    p = str(tmp_path / "w.log")
    payloads = [bytes([i]) * (i + 1) for i in range(40)]
    good = b"".join(_frame(x) for x in payloads)
    _write_wal(p, payloads, tail=tail)
    stats: dict = {}
    got = list(replay_records(p, truncate_torn=True, stats=stats))
    assert got == payloads
    assert open(p, "rb").read() == good  # truncated to the byte
    assert stats["records"] == len(payloads)
    assert stats["torn_bytes"] == len(tail)


def test_wal_streaming_record_larger_than_chunk(tmp_path):
    """A single record bigger than the 1MB read chunk must stream
    through intact (the bounded buffer grows to ONE record, not the
    file), and a torn giant tail must still be cut at the right byte."""
    p = str(tmp_path / "w.log")
    big = os.urandom(2 * _CHUNK + 12345)
    small = b"after-the-big-one"
    _write_wal(p, [big, small], tail=_frame(os.urandom(_CHUNK))[:-7])
    stats: dict = {}
    got = list(replay_records(p, stats=stats))
    assert len(got) == 2
    assert got[0] == big and got[1] == small
    assert os.path.getsize(p) == len(_frame(big)) + len(_frame(small))
    assert stats["torn_bytes"] == _HDR.size + _CHUNK - 7


def test_wal_streaming_frame_straddles_chunk_boundary(tmp_path):
    """Frames sized so headers and payloads land across the 1MB chunk
    boundary: every record must come back exactly once, in order."""
    p = str(tmp_path / "w.log")
    # 7000-byte frames: 1MB/7008 is non-integral, so successive chunks
    # split frames at shifting offsets (header-split and payload-split
    # cases both occur within the first few chunks)
    payloads = [bytes([i % 256]) * 7000 for i in range(400)]
    _write_wal(p, payloads)
    assert list(replay_records(p)) == payloads


def test_wal_crc_mismatch_stops_and_truncates_midfile(tmp_path):
    """A corrupted record MID-file (bitrot, not a crash): lenient replay
    keeps the good prefix and cuts everything from the bad record on —
    identical to the pre-streaming reader's contract."""
    p = str(tmp_path / "w.log")
    payloads = [b"a" * 100, b"b" * 100, b"c" * 100]
    raw = b"".join(_frame(x) for x in payloads)
    flip = len(_frame(payloads[0])) + _HDR.size + 10  # byte inside record 2
    raw = raw[:flip] + bytes([raw[flip] ^ 0xFF]) + raw[flip + 1:]
    with open(p, "wb") as f:
        f.write(raw)
    stats: dict = {}
    got = list(replay_records(p, stats=stats))
    assert got == [payloads[0]]
    assert open(p, "rb").read() == _frame(payloads[0])
    assert stats["torn_bytes"] == 2 * len(_frame(b"x" * 100))


def test_wal_strict_mode_messages_unchanged(tmp_path):
    """Snapshot recovery tells corruption apart by message; the
    streaming reader must keep all three classes distinguishable."""
    p = str(tmp_path / "w.log")
    _write_wal(p, [b"ok"], tail=b"\x01\x02")
    with pytest.raises(ValueError, match="trailing garbage"):
        list(replay_records(p, strict=True))
    _write_wal(p, [b"ok"], tail=_HDR.pack(999, 1) + b"short")
    with pytest.raises(ValueError, match="truncated record"):
        list(replay_records(p, strict=True))
    _write_wal(p, [b"ok"], tail=_HDR.pack(3, 12345) + b"bad")
    with pytest.raises(ValueError, match="CRC mismatch"):
        list(replay_records(p, strict=True))
    # strict never repairs the file in place
    assert os.path.getsize(p) == len(_frame(b"ok")) + _HDR.size + 3


def test_wal_append_single_write_frame(tmp_path):
    """Wal.append builds header+payload in ONE buffer and writes once —
    an exception (or a concurrent writer on a shared fd) can never
    interleave a header with a foreign payload.  Pinned by counting the
    underlying write() calls."""
    calls = []

    class CountingFile:
        def __init__(self, f):
            self._f = f

        def write(self, b):
            calls.append(bytes(b))
            return self._f.write(b)

        def __getattr__(self, name):
            return getattr(self._f, name)

    w = Wal(str(tmp_path / "w.log"))
    w._f = CountingFile(w._f)
    w.append(b"payload-bytes")
    assert len(calls) == 1
    assert calls[0] == _frame(b"payload-bytes")
    w.close()
