"""Tests for the shared infra (utils/ ≈ reference x/)."""

import threading

import pytest

from dgraph_tpu.utils import Options, WaterMark
from dgraph_tpu.utils.metrics import MetricsRegistry
from dgraph_tpu.utils.trace import Latency, _fmt_ns


def test_watermark_contiguous():
    wm = WaterMark()
    for i in (1, 2, 3, 5):
        wm.begin(i)
    wm.done(1)
    wm.done(2)
    assert wm.done_until() == 2
    wm.done(5)
    assert wm.done_until() == 2  # 3 still pending blocks 5
    wm.done(3)
    assert wm.done_until() == 5


def test_watermark_wait():
    wm = WaterMark()
    wm.begin(7)
    t = threading.Thread(target=lambda: wm.done(7))
    t.start()
    assert wm.wait_for_mark(7, timeout=5)
    t.join()


def test_metrics_prometheus_text():
    r = MetricsRegistry()
    r.counter("reads_total").add(3)
    r.gauge("pending").set(2)
    r.labeled("per_pred_total").add("name", 5)
    text = r.prometheus_text()
    assert "reads_total 3" in text
    assert "pending 2" in text
    assert 'per_pred_total{predicate="name"} 5' in text
    assert "# TYPE reads_total counter" in text


def test_metrics_new_gauge_kinds():
    r = MetricsRegistry()
    r.func_gauge("up_seconds", lambda: 12.5)
    r.multilabeled_gauge("build_info", ("version", "backend")).set(
        ("0.1.0", "cpu"), 1
    )
    text = r.prometheus_text()
    assert "up_seconds 12.5" in text
    assert "# TYPE up_seconds gauge" in text
    assert 'build_info{version="0.1.0",backend="cpu"} 1' in text
    assert "# TYPE build_info gauge" in text
    with pytest.raises(ValueError):
        r.multilabeled_gauge("build_info", ("version", "backend")).set(
            ("only-one",), 1
        )


def _valid_openmetrics(body: str) -> None:
    """Structural validity: # EOF exactly at the end, every non-comment
    line is `name{labels} value [exemplar]`, and each histogram's
    cumulative bucket counts are non-decreasing with count == +Inf."""
    import re

    lines = body.splitlines()
    assert lines[-1] == "# EOF"
    assert "# EOF" not in lines[:-1]
    line_re = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.e+-]+(inf)?"
        r"( # \{[^{}]*\} [0-9.e+-]+ [0-9.]+)?$"
    )
    buckets = {}  # (name, labels-sans-le) -> cumulative counts in order
    for ln in lines[:-1]:
        if ln.startswith("#"):
            assert ln.startswith("# TYPE "), ln
            continue
        assert line_re.match(ln), ln
        if "_bucket{" in ln:
            name, rest = ln.split("{", 1)
            # first "} " closes the label set; an exemplar's own braces
            # come later on the line
            labels, val = rest.split("} ", 1)
            series = (name, re.sub(r'le="[^"]*",?', "", labels))
            buckets.setdefault(series, []).append(
                float(val.split(" # ", 1)[0])
            )
    for series, cum in buckets.items():
        assert all(a <= b for a, b in zip(cum, cum[1:])), (series, cum)


def test_exposition_valid_under_mutation_storm():
    """Satellite acceptance (ISSUE 13): /metrics exposition under an
    8-thread observe() storm renders structurally valid OpenMetrics on
    EVERY scrape — no torn lines, no bucket-count regressions, the
    terminator in place."""
    r = MetricsRegistry()
    h = r.histogram("storm_seconds", (0.001, 0.01, 0.1, 1.0))
    lh = r.labeled_histogram("storm_tenant_seconds", "tenant", (0.01, 1.0))
    c = r.counter("storm_total")
    ml = r.multilabeled("storm_rpc_total", ("peer", "outcome"))
    stop = threading.Event()

    def storm(tid: int):
        i = 0
        while not stop.is_set():
            h.observe((i % 7) / 100.0, trace_id=f"{tid:032x}")
            lh.observe(f"t{i % 5}", (i % 3) / 10.0)
            c.add(1)
            ml.add((f"p{tid}", "ok"))
            i += 1

    threads = [
        threading.Thread(target=storm, args=(t,), daemon=True)
        for t in range(8)
    ]
    for t in threads:
        t.start()
    try:
        for _ in range(30):
            _valid_openmetrics(r.openmetrics_text())
            # the classic format must stay parseable too
            classic = r.prometheus_text()
            assert classic.endswith("\n") and "# EOF" not in classic
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    # post-storm: the terminal scrape agrees with the counters
    assert c.value() > 0
    _valid_openmetrics(r.openmetrics_text())


def test_latency_map():
    lat = Latency()
    lat.record_parsing()
    lat.record_processing()
    lat.record_json()
    m = lat.to_map()
    assert "total" in m and "parsing" in m and "processing" in m


def test_fmt_ns():
    assert _fmt_ns(500) == "500ns"
    assert _fmt_ns(79_300_000) == "79.3ms"
    assert _fmt_ns(2_000_000_000) == "2s"


def test_options_yaml_merge(tmp_path):
    cfg = tmp_path / "conf.yaml"
    cfg.write_text("port: 9999\nsync_writes: true\n# comment\npostings_dir: /data/p\n")
    opts = Options().merged_with_yaml(str(cfg))
    assert opts.port == 9999
    assert opts.sync_writes is True
    assert opts.postings_dir == "/data/p"


def test_flags_beat_yaml(tmp_path):
    from dgraph_tpu.cli.server import build_options

    cfg = tmp_path / "conf.yaml"
    cfg.write_text("port: 8080\nexport_path: /from/yaml\n")
    opts = build_options(["--config", str(cfg), "--port", "9000"])
    assert opts.port == 9000          # explicit flag wins over YAML
    assert opts.export_path == "/from/yaml"  # YAML beats the built-in default
