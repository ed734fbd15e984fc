"""Native (C++) fast paths.

The reference ships no C/C++ (SURVEY.md §2a) — its native layer is the Go
runtime itself.  Here the ingest hot loop (N-Quad scanning + string
interning) is C++ behind ctypes, compiled on demand with g++ and cached
beside the source; every caller must tolerate ``scanner() is None`` and
fall back to the pure-Python path (images without a toolchain).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "nquad_scan.cpp")
_SO = os.path.join(_HERE, "libnquad.so")
_STAMP = _SO + ".sha256"  # content hash of the source the .so was built from

_lock = threading.Lock()
_lib = None
_tried = False

# flag bits — keep in sync with nquad_scan.cpp
F_OBJ_LITERAL = 1 << 0
F_HAS_LANG = 1 << 1
F_HAS_TYPE = 1 << 2
F_HAS_FACETS = 1 << 3
F_SUBJ_STAR = 1 << 4
F_PRED_STAR = 1 << 5
F_OBJ_STAR = 1 << 6
F_LIT_ESCAPED = 1 << 7
F_HAS_LABEL = 1 << 8


def _source_digest() -> str:
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _build() -> Optional[str]:
    """The scanner library built from THIS source, or None without a
    toolchain.  The binary is a build product outside git, and a copy of
    the tree carries whatever binary lay on disk with mtimes of its own —
    so freshness is the source's content hash stored beside the binary,
    never a timestamp compare."""
    want = _source_digest()
    try:
        with open(_STAMP) as f:
            if os.path.exists(_SO) and f.read().strip() == want:
                return _SO
    except OSError:
        pass
    # per-process temporaries: test workers of a fresh checkout all build
    # at once, and must not write through each other's output
    so_tmp = os.path.join(_HERE, f"libnquad.{os.getpid()}.so.tmp")
    stamp_tmp = f"{_STAMP}.{os.getpid()}.tmp"
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", so_tmp, _SRC],
            check=True,
            capture_output=True,
            timeout=120,
        )
        # build-cache artifacts, not durable state: atomicity only guards
        # against a concurrent builder, no fsync contract needed
        os.replace(so_tmp, _SO)  # graftlint: ignore[naked-atomic-write]
        with open(stamp_tmp, "w") as f:
            f.write(want)
        os.replace(stamp_tmp, _STAMP)  # graftlint: ignore[naked-atomic-write]
        return _SO
    except (OSError, subprocess.SubprocessError):
        return None


def scanner():
    """The loaded scanner library, or None when unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("DGRAPH_TPU_NO_NATIVE"):
            return None
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.nq_scan.restype = ctypes.c_long
        _lib = lib
        return _lib


def scanner_name() -> str:
    """Which N-Quad scanner serves this process: "native" or "python"."""
    return "native" if scanner() is not None else "python"


class ScanResult:
    """SoA view of one scanned buffer (see nq_scan in nquad_scan.cpp)."""

    __slots__ = (
        "buf", "n", "subj_idx", "pred_idx", "obj_idx", "lang_idx", "type_idx",
        "lit_s", "lit_e", "facet_s", "facet_e", "flags",
        "subj_spans", "subj_uid", "pred_spans", "obj_spans", "obj_uid",
        "lang_spans", "type_spans",
    )

    def span_str(self, span) -> str:
        s, e = span
        return self.buf[s:e].decode("utf-8", errors="replace")

    def strings(self, spans) -> list:
        b = self.buf
        return [b[s:e].decode("utf-8", errors="replace") for s, e in spans]


def scan(text: str) -> Optional[ScanResult]:
    """Scan a block of N-Quads.  Returns None when the native scanner is
    unavailable; raises ValueError (with byte offset context) on malformed
    input — callers fall back to the Python parser for identical error
    surfaces."""
    lib = scanner()
    if lib is None:
        return None
    buf = text.encode("utf-8")
    ln = len(buf)
    # worst case one quad per 7 bytes ("* * * ."); size to line count + 1
    max_q = buf.count(b"\n") + 2 if ln else 1
    I32, I64, U16 = np.int32, np.int64, np.uint16
    r = ScanResult()
    r.buf = buf
    subj_idx = np.empty(max_q, I32); pred_idx = np.empty(max_q, I32)
    obj_idx = np.empty(max_q, I32); lang_idx = np.empty(max_q, I32)
    type_idx = np.empty(max_q, I32)
    lit_s = np.empty(max_q, I32); lit_e = np.empty(max_q, I32)
    facet_s = np.empty(max_q, I32); facet_e = np.empty(max_q, I32)
    flags = np.empty(max_q, U16)
    us_s = np.empty(max_q, I32); us_e = np.empty(max_q, I32); us_u = np.empty(max_q, I64)
    up_s = np.empty(max_q, I32); up_e = np.empty(max_q, I32)
    uo_s = np.empty(max_q, I32); uo_e = np.empty(max_q, I32); uo_u = np.empty(max_q, I64)
    ul_s = np.empty(max_q, I32); ul_e = np.empty(max_q, I32)
    ut_s = np.empty(max_q, I32); ut_e = np.empty(max_q, I32)
    counts = (ctypes.c_long * 5)()

    def p(a):
        return a.ctypes.data_as(ctypes.c_void_p)

    got = lib.nq_scan(
        buf, ctypes.c_long(ln), ctypes.c_long(max_q),
        p(subj_idx), p(pred_idx), p(obj_idx), p(lang_idx), p(type_idx),
        p(lit_s), p(lit_e), p(facet_s), p(facet_e), p(flags),
        p(us_s), p(us_e), p(us_u), ctypes.byref(counts, 0 * ctypes.sizeof(ctypes.c_long)),
        p(up_s), p(up_e), ctypes.byref(counts, 1 * ctypes.sizeof(ctypes.c_long)),
        p(uo_s), p(uo_e), p(uo_u), ctypes.byref(counts, 2 * ctypes.sizeof(ctypes.c_long)),
        p(ul_s), p(ul_e), ctypes.byref(counts, 3 * ctypes.sizeof(ctypes.c_long)),
        p(ut_s), p(ut_e), ctypes.byref(counts, 4 * ctypes.sizeof(ctypes.c_long)),
    )
    if got < 0:
        off = -got - 1
        snippet = buf[off : off + 60].decode("utf-8", errors="replace")
        raise ValueError(f"bad N-Quad at byte {off}: {snippet!r}")
    n = int(got)
    ns, npre, no, nl, nt = (int(counts[i]) for i in range(5))
    r.n = n
    r.subj_idx = subj_idx[:n]; r.pred_idx = pred_idx[:n]; r.obj_idx = obj_idx[:n]
    r.lang_idx = lang_idx[:n]; r.type_idx = type_idx[:n]
    r.lit_s = lit_s[:n]; r.lit_e = lit_e[:n]
    r.facet_s = facet_s[:n]; r.facet_e = facet_e[:n]; r.flags = flags[:n]
    r.subj_spans = np.stack([us_s[:ns], us_e[:ns]], axis=1) if ns else np.empty((0, 2), I32)
    r.subj_uid = us_u[:ns]
    r.pred_spans = np.stack([up_s[:npre], up_e[:npre]], axis=1) if npre else np.empty((0, 2), I32)
    r.obj_spans = np.stack([uo_s[:no], uo_e[:no]], axis=1) if no else np.empty((0, 2), I32)
    r.obj_uid = uo_u[:no]
    r.lang_spans = np.stack([ul_s[:nl], ul_e[:nl]], axis=1) if nl else np.empty((0, 2), I32)
    r.type_spans = np.stack([ut_s[:nt], ut_e[:nt]], axis=1) if nt else np.empty((0, 2), I32)
    return r
