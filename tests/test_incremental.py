"""Incremental arena refresh == full rebuild (VERDICT r3 item 6).

Random interleaved set/del mutations against one engine whose arenas
update via the bounded delta journal, compared against a fresh engine
built from scratch over the same final store state.
"""

import numpy as np
import pytest

from dgraph_tpu.models import PostingStore
from dgraph_tpu.models.arena import ArenaManager
from dgraph_tpu.query import QueryEngine


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_incremental_matches_full_rebuild(seed):
    rng = np.random.default_rng(seed)
    st = PostingStore()
    eng = QueryEngine(st)
    eng.run("mutation { schema { name: string @index(exact) . knows: uid @reverse . } }")
    lines = [f'<0x{u:x}> <name> "P{u}" .' for u in range(1, 30)]
    for _ in range(120):
        a, b = rng.integers(1, 30, size=2)
        lines.append(f"<0x{a:x}> <knows> <0x{b:x}> .")
    eng.run("mutation { set { %s } }" % "\n".join(lines))
    # build arenas (data + reverse), then mutate incrementally
    eng.run('{ q(func: uid(0x1)) { knows { name } ~knows { name } } }')
    for step in range(30):
        ops = []
        for _ in range(int(rng.integers(1, 6))):
            a, b = rng.integers(1, 34, size=2)
            if rng.random() < 0.6:
                ops.append(f"set {{ <0x{a:x}> <knows> <0x{b:x}> . }}")
            else:
                ops.append(f"delete {{ <0x{a:x}> <knows> <0x{b:x}> . }}")
        eng.run("mutation { %s }" % " ".join(ops))
        # force arena refresh via a query touching data + reverse
        got = eng.run('{ q(func: has(name)) { knows { name } ~knows { name } } }')
        a = eng.arenas.data("knows")
        r = eng.arenas.reverse("knows")
        # ground truth from the live store
        want_edges = sorted(
            (u, d) for u, s in st.pred("knows").edges.items() for d in s
        )
        got_edges = []
        for i, u in enumerate(a.h_src.tolist()):
            for d in a.host_dst()[a.h_offsets[i] : a.h_offsets[i + 1]].tolist():
                got_edges.append((u, d))
        assert got_edges == want_edges, f"data arena diverged at step {step}"
        want_rev = sorted((d, u) for (u, d) in want_edges)
        got_rev = []
        for i, u in enumerate(r.h_src.tolist()):
            for d in r.host_dst()[r.h_offsets[i] : r.h_offsets[i + 1]].tolist():
                got_rev.append((u, d))
        # reverse arena keeps rows for sources that lost all edges (degree
        # 0) — compare edge multisets, not row sets
        assert got_rev == want_rev, f"reverse arena diverged at step {step}"


def test_incremental_device_consistency():
    """After deltas, a device-path expansion must see the fresh edges
    (ensure_device re-upload)."""
    st = PostingStore()
    eng = QueryEngine(st)
    eng.run("mutation { schema { knows: uid . name: string @index(exact) . } }")
    eng.run('mutation { set { <0x1> <name> "A" . <0x1> <knows> <0x2> . } }')
    eng.expand_device_min = 0  # force the device path
    got = eng.run('{ q(func: eq(name, "A")) { knows { _uid_ } } }')
    assert got["q"][0]["knows"] == [{"_uid_": "0x2"}]
    eng.run("mutation { set { <0x1> <knows> <0x3> . } }")
    got = eng.run('{ q(func: eq(name, "A")) { knows { _uid_ } } }')
    assert got["q"][0]["knows"] == [{"_uid_": "0x2"}, {"_uid_": "0x3"}]
    eng.run("mutation { delete { <0x1> <knows> <0x2> . } }")
    got = eng.run('{ q(func: eq(name, "A")) { knows { _uid_ } } }')
    assert got["q"][0]["knows"] == [{"_uid_": "0x3"}]


def test_delta_overflow_falls_back():
    st = PostingStore()
    st.DELTA_MAX = 4
    am = ArenaManager(st)
    st.bulk_set_uid_edges("e", np.arange(1, 50), np.arange(2, 51))
    a = am.data("e")
    assert a.n_edges == 49
    for i in range(10):  # exceeds the journal cap → full rebuild path
        st.set_edge("e", 100 + i, 200 + i)
    a2 = am.data("e")
    assert a2.n_edges == 59
    assert a2 is not a  # rebuilt, not patched


def test_has_excludes_emptied_rows():
    """Deleting a uid's last edge must drop it from has() even though the
    patched arena keeps its (degree-0) row."""
    st = PostingStore()
    eng = QueryEngine(st)
    eng.run("mutation { schema { knows: uid . name: string @index(exact) . } }")
    eng.run('mutation { set { <0x1> <name> "A" . <0x1> <knows> <0x2> . '
            "<0x3> <knows> <0x4> . } }")
    got = eng.run("{ q(func: has(knows)) { _uid_ } }")
    assert [x["_uid_"] for x in got["q"]] == ["0x1", "0x3"]
    eng.run("mutation { delete { <0x3> <knows> <0x4> . } }")
    got = eng.run("{ q(func: has(knows)) { _uid_ } }")
    assert [x["_uid_"] for x in got["q"]] == ["0x1"]


def test_inline_layout_after_row_bucket_growth():
    """inline_layout() must size its meta from HOST state.  After
    apply_delta adds a new source row that crosses the power-of-two row
    bucket, a fused chain calls a.inline_layout() without
    ensure_device() — sizing from the stale device tensors would break
    the metap[:S] broadcast."""
    st = PostingStore()
    am = ArenaManager(st)
    # exactly 8 rows -> row bucket 8
    st.bulk_set_uid_edges("e", np.arange(1, 9), np.arange(11, 19))
    a = am.data("e")
    assert a.n_rows == 8
    a.inline_layout()  # build once at the old bucket
    st.set_edge("e", 9, 19)  # 9th source row crosses the bucket
    a = am.data("e")
    assert a.n_rows == 9
    metap, _ov = a.inline_layout()  # must not raise
    assert metap.shape[0] >= 9
    # row 8 (uid 9) must be queryable through the inline layout
    m = np.asarray(metap)
    row = int(np.searchsorted(a.h_src, 9))
    assert (int(m[row, 1]), int(m[row, 2])) == (1, 19)
