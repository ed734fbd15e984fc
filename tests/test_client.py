"""Client SDK + bulk loader tests.

Mirrors client/client_test.go (batching, allocator) and the loader's
checkpoint/resume contract (client/checkpoint.go), over both the
embedded transport (reference InMemoryComm) and real HTTP.
"""

import dataclasses
import gzip
from dataclasses import dataclass, field
from typing import List

import pytest

from dgraph_tpu.client import (
    BatchMutationOptions,
    ClientEdge,
    DgraphClient,
    EmbeddedTransport,
    HttpTransport,
    SyncMarks,
    unmarshal,
)
from dgraph_tpu.cli.loader import load_file
from dgraph_tpu.models import PostingStore
from dgraph_tpu.serve.server import DgraphServer


@pytest.fixture()
def srv():
    server = DgraphServer(PostingStore())
    server.start()
    yield server
    server.stop()


def test_batching_client_embedded(srv):
    c = DgraphClient(EmbeddedTransport(srv), BatchMutationOptions(size=10, pending=3))
    c.add_schema("name: string @index(term) .")
    for i in range(95):
        c.batch_set(ClientEdge.value(f"0x{i + 1:x}", "name", f"person {i}"))
        c.batch_set(ClientEdge.value(f"0x{i + 1:x}", "rank", i))
    c.batch_set(ClientEdge.connect("0x1", "knows", "0x2"))
    c.flush()
    out = c.query('{ q(func: uid(0x5)) { name rank } }')
    assert out["q"] == [{"name": "person 4", "rank": 4}]
    assert c.mutation_count() >= 95 * 2 // 10  # batched, not per-quad
    c.close()


def test_batching_client_http(srv):
    c = DgraphClient(HttpTransport(srv.addr), BatchMutationOptions(size=5, pending=2))
    for i in range(12):
        c.batch_set(ClientEdge.value(f"_:n{i}", "score", float(i) / 2))
    c.flush()
    out = c.query("{ q(func: has(score)) { score } }")
    assert len(out["q"]) == 12
    c.close()


def test_batch_set_block_is_one_mutation_per_block(srv):
    """The bulk loader's hand-off: a block of lines crosses the queue as
    ONE item and one worker submits it as one mutation — also when single
    quads are queued around it."""
    c = DgraphClient(EmbeddedTransport(srv), BatchMutationOptions(size=4, pending=2))
    c.batch_set(ClientEdge.value("0x1", "name", "single before"))
    c.batch_set_block([f'<0x{i + 10:x}> <name> "block a {i}" .' for i in range(50)])
    c.batch_set_block([f'<0x{i + 100:x}> <name> "block b {i}" .' for i in range(30)])
    c.batch_set_block([])  # nothing to send
    c.batch_set(ClientEdge.value("0x2", "name", "single after"))
    c.flush()
    assert len(c.query("{ q(func: has(name)) { name } }")["q"]) == 82
    # 2 blocks + at most 2 batches of singles — never one mutation per line
    assert 3 <= c.mutation_count() <= 4
    c.close()


def test_batch_delete(srv):
    c = DgraphClient(EmbeddedTransport(srv), BatchMutationOptions(size=4, pending=2))
    c.batch_set(ClientEdge.value("0x1", "name", "temp"))
    c.flush()
    c.batch_delete(ClientEdge.value("0x1", "name", "temp"))
    c.flush()
    out = c.query("{ q(func: has(name)) { name } }")
    assert out.get("q", []) == []
    c.close()


def test_unmarshal_nested():
    @dataclass
    class Friend:
        name: str = ""
        age: int = 0

    @dataclass
    class Person:
        name: str = ""
        age: int = 0
        alive: bool = False
        friend: List[Friend] = field(default_factory=list)

    node = {
        "name": "Noor Haddad",
        "age": 44,
        "alive": "true",
        "friend": [{"name": "Silas", "age": 51}, {"name": "Imre"}],
    }
    p = unmarshal(node, Person)
    assert p.name == "Noor Haddad" and p.age == 44 and p.alive is True
    assert [f.name for f in p.friend] == ["Silas", "Imre"]
    assert p.friend[0].age == 51


def test_unmarshal_field_override():
    @dataclass
    class Row:
        display: str = dataclasses.field(default="", metadata={"dgraph": "name"})

    assert unmarshal({"name": "x"}, Row).display == "x"


def _write_rdf_gz(path, n):
    with gzip.open(path, "wt") as f:
        for i in range(n):
            f.write(f'_:p{i} <name> "bulk {i}" .\n')


def test_loader_gzip_and_checkpoint(srv, tmp_path):
    rdf = tmp_path / "data.rdf.gz"
    _write_rdf_gz(rdf, 57)
    marks = SyncMarks(str(tmp_path / "cd"))
    c = DgraphClient(HttpTransport(srv.addr), BatchMutationOptions(size=10, pending=2))
    n = load_file(c, str(rdf), marks, batch=10)
    c.close()
    assert n == 57
    out = DgraphClient(EmbeddedTransport(srv)).query("{ q(func: has(name)) { name } }")
    assert len(out["q"]) == 57
    # resume: a fresh SyncMarks over the same dir skips everything
    marks2 = SyncMarks(str(tmp_path / "cd"))
    c2 = DgraphClient(HttpTransport(srv.addr), BatchMutationOptions(size=10, pending=2))
    n2 = load_file(c2, str(rdf), marks2, batch=10)
    c2.close()
    assert n2 == 0


def test_checkpoint_partial_resume(tmp_path):
    marks = SyncMarks(str(tmp_path))
    marks.begin("f.rdf", 100)
    marks.done("f.rdf", 100)
    marks.begin("f.rdf", 250)  # in flight, never done
    # new process: only the contiguous prefix survives
    marks2 = SyncMarks(str(tmp_path))
    assert marks2.done_until("f.rdf") == 100


def test_set_then_delete_ordering(srv):
    """A delete enqueued after a set of the same quad must win even with
    multiple pending workers (cross-op barrier)."""
    c = DgraphClient(EmbeddedTransport(srv), BatchMutationOptions(size=4, pending=3))
    e = ClientEdge.value("0x200", "tag", "x")
    for _ in range(8):
        c.batch_set(e)
        c.batch_delete(e)
    c.flush()
    out = c.query("{ q(func: uid(0x200)) { tag } }")
    assert out.get("q", []) == []
    # and delete-then-set leaves it present
    c.batch_delete(e)
    c.batch_set(e)
    c.flush()
    out = c.query("{ q(func: uid(0x200)) { tag } }")
    assert out["q"] == [{"tag": "x"}]
    c.close()


def test_server_stop_idempotent(srv):
    srv.stop()
    srv.stop()  # second call must be a no-op, not a double-close


def test_http_transport_binary_protobuf(srv):
    """binary=True speaks the protobuf wire surface end-to-end and yields
    the same result dict as the JSON path — including EMPTY blocks, which
    must not vanish from the wire."""
    from dgraph_tpu.client.client import HttpTransport

    HttpTransport(srv.addr).run(
        'mutation { set { <0x61> <name> "Alice" . <0x61> <follows> <0x62> . '
        '<0x62> <name> "Bob" . } }'
    )
    q = "{ q(func: uid(0x61)) { name follows { name } } }"
    jout = HttpTransport(srv.addr).run(q)
    bout = HttpTransport(srv.addr, binary=True).run(q)
    assert bout["q"] == jout["q"]
    # empty result set: JSON reports {"q": []}; binary must match, not drop
    q0 = "{ q(func: uid(0x5f)) { name } }"
    jout = HttpTransport(srv.addr).run(q0)
    bout = HttpTransport(srv.addr, binary=True).run(q0)
    assert jout["q"] == [] and bout["q"] == []
