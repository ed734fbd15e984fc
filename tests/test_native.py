"""Native scanner parity: the C++ bulk path and the pure-Python parser
must produce byte-identical store state on the same mutation body."""

import numpy as np
import pytest

from dgraph_tpu.gql.ast import Mutation
from dgraph_tpu.models import PostingStore
from dgraph_tpu.serve.mutations import apply_mutation

CORPUS = r"""
<0x1> <name> "Noor Haddad" .
<0x1> <age> "44"^^<xs:int> .
<0x2> <name> "Silas \"the\" Reed" .
<0x1> <friend> <0x2> (since=2009-08-15, close=true, weight=1.5) .
<0x2> <friend> <0x3> .
_:blank1 <name> "Blanka" .
_:blank1 <knows> _:blank2 .
<http://example.org/alice> <name> "Alice xid"@en .
<0x3> <bio> "line one\nline two" .
<0x4> <score> "2.75"^^<xs:float> .   # trailing comment
# full comment line
<0x5> <alive> "true"^^<xs:boolean> .
<0x6> <tag> "hola"@es .
<0x6> <tag> "hello"@en .
<0x6> <tag> "fallback" .
"""

SCHEMA = """
    name: string @index(term) .
    age: int @index(int) .
    friend: uid @reverse .
    score: float .
    alive: bool .
"""


def _state(st: PostingStore):
    out = {}
    for pr in st.predicates():
        p = st.pred(pr)
        out[pr] = (
            {u: sorted(s) for u, s in p.edges.items()},
            {k: (v.tid, v.value) for k, v in p.values.items()},
            {k: {fk: (fv.tid, fv.value) for fk, fv in f.items()}
             for k, f in p.edge_facets.items()},
        )
    return out


def _apply(no_native: bool, monkeypatch):
    import dgraph_tpu.native as nat

    if no_native:
        monkeypatch.setenv("DGRAPH_TPU_NO_NATIVE", "1")
    else:
        monkeypatch.delenv("DGRAPH_TPU_NO_NATIVE", raising=False)
    nat._lib = None
    nat._tried = False
    st = PostingStore()
    st.apply_schema(SCHEMA)
    blanks = apply_mutation(st, Mutation(set_nquads=CORPUS))
    nat._lib = None
    nat._tried = False
    return st, blanks


def _canon(st: PostingStore, blanks):
    """State with blank/xid uids replaced by stable labels: assignment
    ORDER differs between the two paths (both are legal — uids for blank
    nodes are arbitrary), so parity is up to renaming."""
    label = {u: f"blank:{b}" for b, u in blanks.items()}
    for xid, u in st.uids.snapshot().items():
        label[u] = f"xid:{xid}"

    def lab(u):
        return label.get(u, u)

    out = {}
    for pr in st.predicates():
        p = st.pred(pr)
        out[pr] = (
            {lab(u): sorted(lab(d) for d in s) for u, s in p.edges.items()},
            {(lab(u), l): (v.tid, v.value) for (u, l), v in p.values.items()},
            {(lab(a), lab(b)): {fk: (fv.tid, fv.value) for fk, fv in f.items()}
             for (a, b), f in p.edge_facets.items()},
        )
    return out


def test_native_matches_python(monkeypatch):
    st_n, blanks_n = _apply(False, monkeypatch)
    st_p, blanks_p = _apply(True, monkeypatch)
    assert sorted(blanks_n) == sorted(blanks_p)
    assert _canon(st_n, blanks_n) == _canon(st_p, blanks_p)


def test_native_rejects_what_python_rejects(monkeypatch):
    from dgraph_tpu.rdf.parse import ParseError

    monkeypatch.delenv("DGRAPH_TPU_NO_NATIVE", raising=False)
    st = PostingStore()
    with pytest.raises(ParseError):
        apply_mutation(st, Mutation(set_nquads='<0x1> <name> "unterminated .'))
    with pytest.raises(ParseError):
        apply_mutation(st, Mutation(set_nquads="<0x1> <name> missing_dot"))
    # '*' is delete-only; in a set block both paths must reject it
    with pytest.raises((ParseError, ValueError)):
        apply_mutation(st, Mutation(set_nquads="<0x1> * * ."))
    # the grammar requires \s+ BETWEEN terms and [^\S\n]+ before a label:
    # whether a g++ toolchain was present must not decide acceptance
    with pytest.raises(ParseError):
        apply_mutation(st, Mutation(set_nquads="<0x1><p> <0x2> ."))
    with pytest.raises(ParseError):
        apply_mutation(st, Mutation(set_nquads="<0x1> <p><0x2> ."))
    with pytest.raises(ParseError):
        apply_mutation(st, Mutation(set_nquads='<0x1> <p> "v"<g> .'))


def test_bulk_edges_wal_roundtrip(tmp_path):
    from dgraph_tpu.models.wal import DurableStore

    st = DurableStore(str(tmp_path / "d"))
    st.bulk_set_uid_edges("friend", np.array([1, 1, 2]), np.array([2, 3, 4]))
    st.close()
    st2 = DurableStore(str(tmp_path / "d"))
    assert st2.neighbors("friend", 1) == [2, 3]
    assert st2.neighbors("friend", 2) == [4]
    st2.close()


def test_value_order_preserved_across_facet_quads(monkeypatch):
    """Last-write-wins for the same (pred, src, lang) must follow input
    order even when the earlier write carries facets (the native path
    must not segregate faceted quads into a later phase)."""
    body = '<0x1> <name> "old" (src=a) .\n<0x1> <name> "new" .'
    for no_native in (False, True):
        st, _ = _apply(no_native, monkeypatch)  # warms schema
    for no_native in (False, True):
        import dgraph_tpu.native as nat

        if no_native:
            monkeypatch.setenv("DGRAPH_TPU_NO_NATIVE", "1")
        else:
            monkeypatch.delenv("DGRAPH_TPU_NO_NATIVE", raising=False)
        nat._lib = None
        nat._tried = False
        st = PostingStore()
        apply_mutation(st, Mutation(set_nquads=body))
        assert st.value("name", 1).value == "new", f"no_native={no_native}"
        nat._lib = None
        nat._tried = False


def test_bad_value_in_set_applies_no_edges(monkeypatch):
    """All-or-nothing within one set block: a schema type-conversion
    error on a LATER value quad must fail the request before the fast
    path durably applies EARLIER uid edges (both paths must agree)."""
    body = '<0x1> <link> <0x2> .\n<0x1> <age> "notanint" .'
    for no_native in (False, True):
        import dgraph_tpu.native as nat

        if no_native:
            monkeypatch.setenv("DGRAPH_TPU_NO_NATIVE", "1")
        else:
            monkeypatch.delenv("DGRAPH_TPU_NO_NATIVE", raising=False)
        nat._lib = None
        nat._tried = False
        st = PostingStore()
        st.apply_schema("age: int .\nlink: uid .")
        with pytest.raises(Exception):
            apply_mutation(st, Mutation(set_nquads=body))
        assert st.neighbors("link", 1) == [], f"no_native={no_native}"
        nat._lib = None
        nat._tried = False


def test_bad_delete_applies_no_sets(monkeypatch):
    """A delete that fails uid conversion must fail the whole mutation
    BEFORE the fast path durably applies the set block."""
    monkeypatch.delenv("DGRAPH_TPU_NO_NATIVE", raising=False)
    st = PostingStore()
    with pytest.raises(ValueError):
        apply_mutation(
            st,
            Mutation(set_nquads='<0x1> <name> "x" .', del_nquads="<0x1> <p> <0xzz> ."),
        )
    assert st.value("name", 1) is None


def test_binary_is_rebuilt_when_the_source_hash_differs(tmp_path, monkeypatch):
    """The .so is a build product outside git: a copy of the tree may carry
    a stale binary with any mtime.  Freshness is the source's content hash
    stored beside the binary — a mismatch (or no stamp) rebuilds, a match
    does not."""
    import shutil

    from dgraph_tpu import native

    if shutil.which("g++") is None:
        pytest.skip("no toolchain: the scanner falls back to Python")
    src = tmp_path / "nquad_scan.cpp"
    shutil.copy(native._SRC, src)
    so = tmp_path / "libnquad.so"
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_SO", str(so))
    monkeypatch.setattr(native, "_STAMP", str(so) + ".sha256")
    so.write_bytes(b"a stale binary, newer than the source")
    assert native._build() == str(so)            # no stamp: rebuilt
    built = so.read_bytes()
    assert built != b"a stale binary, newer than the source"
    assert (tmp_path / "libnquad.so.sha256").read_text() == native._source_digest()
    so.write_bytes(b"tampered")                  # stamp matches: kept as is
    assert native._build() == str(so) and so.read_bytes() == b"tampered"
    src.write_text(src.read_text() + "\n// edited\n")
    assert native._build() == str(so) and so.read_bytes() != b"tampered"
