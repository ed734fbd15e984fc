"""CPU rehearsals of chip_smoke.py's control flow, and the compile-cache
helper it shares with the server binary and the bench scripts.

A rehearsal proves the script loads, answers and agrees with its numpy
reference end to end through the real server and loader children — and that
without a TPU it still FAILS: non-zero exit, no ``"ok": true`` line.  The
chip run itself is ``python chip_smoke.py`` through the chip tool.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what a rehearsal on the CPU backend must fail on, and nothing else
ONLY_FOR_WANT_OF_A_CHIP = [
    "the server runs on a TPU",
    "calibration measured on this backend",
]


def _rehearse(tmp_path, devices: int, *args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--quads", "30000",
         "--workdir", str(tmp_path / "w"), *args],
        env=env, capture_output=True, text=True, timeout=600,
    )
    lines = [json.loads(l) for l in r.stdout.splitlines() if l.startswith("{")]
    return r, lines


def _phase(lines, name):
    return next(l for l in lines if l.get("phase") == name)


def test_rehearsal_answers_match_then_fails_at_the_platform_check(tmp_path):
    r, lines = _rehearse(tmp_path, 1)
    assert r.returncode != 0, r.stdout
    assert not any(l.get("ok") is True for l in lines), r.stdout
    assert '"ok": true' not in r.stdout
    done = _phase(lines, "done")
    assert done["failed_checks"] == ONLY_FOR_WANT_OF_A_CHIP, r.stdout + r.stderr
    # every phase ran: loaded what it generated, answered every shape with
    # the reference's edge counts, read its write back, repeated from cache
    assert _phase(lines, "load")["quads"] == _phase(lines, "generate")["quads"]
    queries = {l["query"]: l for l in lines if "query" in l}
    assert set(queries) == {
        "point", "two_hop", "three_hop_coactor", "four_level_detail",
        "hot_actor", "fanout",
    }
    for q in queries.values():
        assert q["edges"] == q["expect_edges"], q
    assert queries["fanout"]["edges"] > 10_000
    assert _phase(lines, "mutation")
    rep = _phase(lines, "repeat")
    assert rep["result_cache_hits"] >= 1
    assert rep["compiles_after"] == rep["compiles_before"]
    dev = _phase(lines, "device")
    assert dev["backend"] == "cpu" and dev["resident_bytes"] > 0
    assert dev["guard"] == {"device": "healthy"}


def test_mesh_rehearsal_engages_the_mesh_route_with_balanced_shards(tmp_path):
    r, lines = _rehearse(tmp_path, 4, "--mesh")
    assert r.returncode != 0, r.stdout
    assert '"ok": true' not in r.stdout
    assert _phase(lines, "done")["failed_checks"] == ONLY_FOR_WANT_OF_A_CHIP, (
        r.stdout + r.stderr
    )
    queries = {l["query"]: l for l in lines if "query" in l}
    assert set(queries) == {"two_hop", "three_hop_coactor"}  # nothing else runs
    for q in queries.values():
        assert q["edges"] == q["expect_edges"], q
    dev = _phase(lines, "device")
    assert dev["devices"] == 4
    assert dev["route_edges"].get("mesh", 0) > 0
    per = dev["mesh"]["sharded_bytes_by_device"]
    assert len(per) == 4 and min(per.values()) > 0
    assert max(per.values()) <= sum(per.values()) / 3


def test_default_size_stops_at_the_platform_check(tmp_path):
    """At the default (full) size a run without a TPU does not generate or
    load anything first: it fails as soon as the server names its backend."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--workdir", str(tmp_path / "w")],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert '"phase": "generate"' not in r.stdout


@pytest.fixture
def cache_updates(monkeypatch):
    """jax.config.update calls made by the helper, without letting them
    reach this process's JAX."""
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_cache_helper_sets_nothing_when_the_environment_placed_it(
    monkeypatch, cache_updates
):
    from dgraph_tpu.utils import jaxcache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x/placed")
    assert jaxcache.configure("auto") == "/x/placed"
    assert jaxcache.configure("/somewhere/else") == "/x/placed"
    assert cache_updates == []


def test_cache_helper_fixed_path_is_independent_of_the_working_directory(
    monkeypatch, tmp_path, cache_updates
):
    from dgraph_tpu.utils import jaxcache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    paths = []
    for name in ("a", "b"):
        (tmp_path / name).mkdir()
        monkeypatch.chdir(tmp_path / name)
        paths.append(jaxcache.configure("auto"))
    assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")
    assert paths[0] in [v for _key, v in cache_updates]
    # an explicit directory overrides the fixed one; "" turns the cache off
    assert jaxcache.configure(str(tmp_path / "c")) == str(tmp_path / "c")
    del cache_updates[:]
    assert jaxcache.configure("") == ""
    assert cache_updates == []
