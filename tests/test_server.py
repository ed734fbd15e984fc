"""HTTP serving surface e2e tests.

Mirrors cmd/dgraph/main_test.go (27 handler-level tests) and
contrib/simple-e2e.sh: boot a real server on a loopback port, mutate
and query over HTTP, hit every admin/debug endpoint.
"""

import gzip
import json
import urllib.request

import pytest

from dgraph_tpu.models import PostingStore
from dgraph_tpu.serve.server import DgraphServer


def _post(addr, path, body):
    req = urllib.request.Request(addr + path, data=body.encode(), method="POST")
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


def _get(addr, path, raw=False):
    with urllib.request.urlopen(addr + path, timeout=30) as r:
        data = r.read()
    return data if raw else json.loads(data.decode())


@pytest.fixture(scope="module")
def srv(tmp_path_factory):
    server = DgraphServer(
        PostingStore(),
        export_path=str(tmp_path_factory.mktemp("export")),
    )
    server.start()
    _post(server.addr, "/query", """
    mutation {
      schema { name: string @index(term) . }
      set {
        <0x1> <name> "Alice" .
        <0x2> <name> "Bob" .
        <0x1> <follows> <0x2> .
      }
    }
    """)
    yield server
    server.stop()


def test_health(srv):
    assert _get(srv.addr, "/health", raw=True) == b"OK"


def test_query_http(srv):
    out = _post(srv.addr, "/query", '{ q(func: anyofterms(name, "Alice")) { name } }')
    assert out["q"] == [{"name": "Alice"}]
    assert "server_latency" in out and "total" in out["server_latency"]


def test_mutation_returns_blank_uids(srv):
    out = _post(srv.addr, "/query", 'mutation { set { _:new <name> "Carol" . } }')
    assert "new" in out["uids"]
    uid = out["uids"]["new"]
    assert uid.startswith("0x")
    got = _post(srv.addr, "/query", '{ q(func: uid(%s)) { name } }' % uid)
    assert got["q"] == [{"name": "Carol"}]


def test_query_error_is_400(srv):
    req = urllib.request.Request(srv.addr + "/query", data=b"{ bad", method="POST")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=10)
    assert e.value.code == 400


def test_debug_store(srv):
    out = _get(srv.addr, "/debug/store")
    assert out["predicates"]["name"]["values"] >= 2
    assert out["predicates"]["follows"]["edges"] == 1


def test_prometheus_metrics(srv):
    text = _get(srv.addr, "/debug/prometheus_metrics", raw=True).decode()
    assert "dgraph_num_queries_total" in text


def test_share_roundtrip(srv):
    q = "{ q(func: has(name)) { name } }"
    out = _post(srv.addr, "/share", q)
    sid = out["uids"]["share"]
    got = _get(srv.addr, f"/share/{sid}")
    assert got["share"] == q


def test_dashboard_served(srv):
    html = _get(srv.addr, "/", raw=True).decode()
    assert "dgraph-tpu console" in html


def test_export_endpoint(srv):
    out = _get(srv.addr, "/admin/export")
    assert out["code"] == "Success"
    with gzip.open(out["rdf"], "rt") as f:
        lines = f.read().strip().splitlines()
    assert any("<follows>" in l for l in lines)
    assert out["nquads"] == len(lines)


def test_gql_variables_header(srv):
    req = urllib.request.Request(
        srv.addr + "/query",
        data=b"query test($a: string) { q(func: anyofterms(name, $a)) { name } }",
        method="POST",
    )
    req.add_header("X-Dgraph-Vars", json.dumps({"$a": "Bob"}))
    with urllib.request.urlopen(req, timeout=10) as r:
        out = json.loads(r.read().decode())
    assert out["q"] == [{"name": "Bob"}]


def test_debug_attaches_uids(srv):
    out = _post(srv.addr, "/query?debug=true", '{ q(func: anyofterms(name, "Alice")) { name } }')
    assert out["q"][0]["_uid_"] == "0x1"
    out2 = _post(srv.addr, "/query", '{ q(func: anyofterms(name, "Alice")) { name } }')
    assert "_uid_" not in out2["q"][0]


def test_yaml_config_values_survive(tmp_path):
    """YAML-only values (sync_writes, workers) must not be silently dropped
    by flag parsing; explicit flags still win."""
    from dgraph_tpu.cli.server import build_options

    cfg = tmp_path / "conf.yaml"
    cfg.write_text("sync_writes: true\nworkers: 9\nport: 7001\n")
    opts = build_options(["--config", str(cfg)])
    assert opts.sync_writes is True
    assert opts.workers == 9
    assert opts.port == 7001
    opts = build_options(["--config", str(cfg), "--port", "7002"])
    assert opts.port == 7002 and opts.sync_writes is True


def test_tls_serving(tmp_path):
    """HTTPS termination (reference x/tls_helper.go, contrib/tlstest)."""
    import ssl
    import subprocess
    import urllib.request

    cert = tmp_path / "cert.pem"
    key = tmp_path / "key.pem"
    try:
        r = subprocess.run(
            ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
             "-keyout", str(key), "-out", str(cert), "-days", "1",
             "-subj", "/CN=localhost"],
            capture_output=True,
        )
    except FileNotFoundError:
        pytest.skip("openssl unavailable")
    if r.returncode != 0:
        pytest.skip("openssl unavailable")
    from dgraph_tpu.models import PostingStore
    from dgraph_tpu.serve.server import DgraphServer

    srv = DgraphServer(PostingStore(), tls_cert=str(cert), tls_key=str(key))
    srv.start()
    try:
        ctx = ssl.create_default_context()
        ctx.check_hostname = False
        ctx.verify_mode = ssl.CERT_NONE
        req = urllib.request.Request(
            f"https://127.0.0.1:{srv.port}/query",
            data=b'mutation { set { <0x1> <name> "tls" . } }',
        )
        with urllib.request.urlopen(req, context=ctx, timeout=10) as resp:
            assert b"Success" in resp.read()
    finally:
        srv.stop()


def test_dumpsg_writes_execution_shape(tmp_path):
    """--dumpsg analog (cmd/dgraph/main.go:347-358): each query drops a
    JSON execution-shape tree for offline plan inspection."""
    import os

    server = DgraphServer(PostingStore(), dumpsg_path=str(tmp_path / "sg"))
    server.start()
    try:
        _post(server.addr, "/query",
              'mutation { set { <0x1> <name> "A" . <0x1> <follows> <0x2> . } }')
        _post(server.addr, "/query", "{ q(func: uid(0x1)) { name follows { _uid_ } } }")
        files = os.listdir(tmp_path / "sg")
        assert files, "no dump written"
        with open(tmp_path / "sg" / sorted(files)[-1]) as f:
            dump = json.load(f)
        root = dump[0]
        assert root["n_dest"] == 1
        attrs = {c["attr"] for c in root.get("children", [])}
        assert "follows" in attrs and "name" in attrs
    finally:
        server.stop()


def test_dumpsg_no_stale_plan_on_mutation_only(tmp_path):
    """A mutation-only request must not re-dump the previous query's plan
    (the shared write-path engine resets last_dump per request)."""
    import os

    server = DgraphServer(PostingStore(), dumpsg_path=str(tmp_path / "sg"))
    server.start()
    try:
        _post(server.addr, "/query", 'mutation { set { <0x1> <name> "A" . } }')
        _post(server.addr, "/query", "{ q(func: uid(0x1)) { name } }")
        n_after_query = len(os.listdir(tmp_path / "sg"))
        _post(server.addr, "/query", 'mutation { set { <0x2> <name> "B" . } }')
        assert len(os.listdir(tmp_path / "sg")) == n_after_query
    finally:
        server.stop()
