"""Program-contract checker tests (graftcheck tier 2,
dgraph_tpu/analysis/programs.py).

Three layers, mirroring test_analysis.py's discipline:

- **acceptance on the shipped tree**: the full checker (trace + golden
  + donation + transfer + cost + bucket) exits 0 against the shipped
  ``analysis/programs.json``, the registry carries >= 10 full kernel
  contracts, and fingerprints are bit-stable across two independent
  collection runs;
- **seeded golden-bads**: each contract check must catch its canonical
  bug — a reintroduced scan, a lost donation (synthetic AND the real
  ``multi_hop`` carry), an f64/dtype promotion, a host callback, a
  bucket-key fingerprint leak, a budget-exceeding program, and golden
  drift — and each must drive ``python -m dgraph_tpu.analysis
  --programs`` (the exact CLI entry CI runs) to a nonzero exit;
- **plumbing**: ``--update-programs`` refuses to bless a violating
  program, the goldens round-trip, and the scoped donation-warning
  handler (utils/jaxdiag.py) counts the expected case and re-emits
  everything else.
"""

import json
import warnings

import numpy as np
import pytest

from dgraph_tpu.analysis import __main__ as analysis_cli
from dgraph_tpu.analysis import programs
from dgraph_tpu.analysis.programs import (
    ALL_CHECKS,
    BucketProbe,
    ProgramContract,
    ProgramInstance,
    check_contract,
)


def _checks_of(violations):
    return sorted({v.check for v in violations})


def _contract(build, name="seed.bad", **kw):
    kw.setdefault("covers", ())
    return ProgramContract(name=name, build=build, **kw)


def _jnp():
    import jax.numpy as jnp

    return jnp


# --------------------------------------------- acceptance: the shipped tree

def test_registry_has_ten_plus_full_contracts():
    full = [c for c in programs.REGISTRY.values() if not c.experimental]
    assert len(full) >= 10
    # the resident data plane's programs are under full contracts
    for name in ("pallas.gather", "resident.merge"):
        assert not programs.REGISTRY[name].experimental, name
    # every contract's covers + exemptions feed the lint acceptance set
    cov = programs.covered_sites()
    for c in programs.REGISTRY.values():
        for site in c.covers:
            assert site in cov


def test_fingerprints_stable_and_match_shipped_goldens():
    """Acceptance: two same-tree collection runs agree with each other
    AND with the blessed analysis/programs.json (trace-only, no
    compiles)."""
    fp1 = programs.collect_fingerprints()
    fp2 = programs.collect_fingerprints()
    assert fp1 == fp2
    # fingerprints are jaxpr text: goldens blessed under another JAX
    # release fail here with the remedy, not as N drifted hashes
    assert programs.goldens_release_mismatch() is None, (
        programs.goldens_release_mismatch()
    )
    shipped = json.loads(programs.GOLDENS_PATH.read_text())["programs"]
    assert fp1 == shipped


def test_goldens_of_another_jax_release_ask_for_a_rebless(
    monkeypatch, tmp_path, capsys
):
    """Fingerprints are jaxpr text and change with the JAX release: goldens
    blessed under another one fail with ONE finding that names both
    releases and the remedy — not one drifted hash per program."""
    name = "sets.union_many"
    shipped = json.loads(programs.GOLDENS_PATH.read_text())
    stale = tmp_path / "g.json"
    stale.write_text(json.dumps({
        "jax": "0.4.37", "programs": {name: shipped["programs"][name]},
    }))
    monkeypatch.setattr(programs, "REGISTRY", {name: programs.REGISTRY[name]})
    rc = analysis_cli.main(["--programs", "--programs-goldens", str(stale)])
    out = capsys.readouterr().out
    assert rc != 0
    assert "blessed under jax 0.4.37" in out and "re-bless" in out
    assert out.count("[golden]") == 1, out


def test_full_checker_clean_on_shipped_tree(capsys):
    """The CI gate itself: `python -m dgraph_tpu.analysis --programs`
    exits 0 on the shipped tree with the shipped goldens."""
    rc = analysis_cli.main(["--programs"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "programs: clean" in out


# ------------------------------------------------------ seeded golden-bads

def _b_scan():
    import jax
    jnp = _jnp()

    def fold(x):
        return jax.lax.scan(lambda c, v: (c + v, c), jnp.int32(0), x)[0]

    return [ProgramInstance("L8", fold, (jnp.zeros(8, jnp.int32),))]


def _b_callback():
    import jax
    jnp = _jnp()

    def noisy(x):
        jax.debug.print("x = {}", x)
        return x + 1

    return [ProgramInstance("L8", noisy, (jnp.zeros(8, jnp.int32),))]


def _b_float_leak():
    jnp = _jnp()

    def leaky(x):
        return (x * 0.5).astype(jnp.float32)  # int kernel grows a float

    return [ProgramInstance("L8", leaky, (jnp.zeros(8, jnp.int32),))]


def _b_f64():
    jnp = _jnp()

    def widen(x):
        return x * 2.0

    return [
        ProgramInstance("L8", widen, (jnp.zeros(8, jnp.float64),))
    ]


def _b_no_donation():
    import jax
    jnp = _jnp()

    @jax.jit  # donate_argnums lost in a refactor
    def step(carry, v):
        return carry + v

    return [
        ProgramInstance(
            "L8", step, (jnp.zeros(8, jnp.int32), jnp.ones(8, jnp.int32))
        )
    ]


def _b_big():
    jnp = _jnp()

    def mm(a, b):
        return a @ b

    z = jnp.zeros((64, 64), jnp.float32)
    return [ProgramInstance("T64", mm, (z, z))]


def _leaky_bucket_inst(n):
    jnp = _jnp()

    def pad_gather(x):
        return x[::2]

    # BUG under test: pads to the raw size instead of bucket(n)
    return ProgramInstance(f"N{n}", pad_gather, (jnp.zeros(n, jnp.int32),))


SEEDED_BADS = {
    "scan": _contract(_b_scan, scan_free=True),
    "callback": _contract(_b_callback),
    "dtype": _contract(_b_float_leak),
    "donation": _contract(_b_no_donation, donate=(0,)),
    "cost": _contract(
        _b_big,
        dtypes=frozenset({"float32"}),
        max_bytes=128,
    ),
    "bucket": _contract(
        lambda: [],
        bucket_probe=BucketProbe(pairs=((10, 12),), make=_leaky_bucket_inst),
    ),
}


@pytest.mark.parametrize("check", sorted(SEEDED_BADS))
def test_seeded_bad_caught_by_checker(check):
    violations, _, _ = check_contract(SEEDED_BADS[check], checks=ALL_CHECKS)
    assert check in _checks_of(violations), violations


@pytest.mark.parametrize("check", sorted(SEEDED_BADS))
def test_cli_exits_nonzero_on_each_seeded_bad(
    check, monkeypatch, tmp_path, capsys
):
    """Acceptance: the exact CLI entry CI runs goes red for every
    seeded golden-bad class."""
    monkeypatch.setattr(
        programs, "REGISTRY", {"seed.bad": SEEDED_BADS[check]}
    )
    rc = analysis_cli.main(
        ["--programs", "--programs-goldens", str(tmp_path / "g.json")]
    )
    out = capsys.readouterr().out
    assert rc != 0
    assert f"[{check}]" in out, out


def test_seeded_f64_promotion_caught():
    """A literal float64 aval (x64 mode) violates the tile-f32
    discipline — the checker sees the widened dtype in the jaxpr."""
    import jax

    c = _contract(_b_f64, dtypes=frozenset({"float32"}))
    jax.config.update("jax_enable_x64", True)
    try:
        violations, _, _ = check_contract(c, checks=("dtype",))
    finally:
        jax.config.update("jax_enable_x64", False)
    assert _checks_of(violations) == ["dtype"]
    assert "float64" in violations[0].message


def test_real_multi_hop_losing_donation_is_caught():
    """The load-bearing variant of the donation golden-bad: the REAL
    multi_hop program, checked as if the visited carry's fallback had
    never been declared — exactly what the old blanket warning filter
    used to hide."""
    real = programs.REGISTRY["batch.multi_hop"]
    # contract passes as shipped...
    ok, _, _ = check_contract(real, checks=("donation",))
    assert ok == []
    # ...and fails the moment the unused-carry declaration is dropped
    stripped = ProgramContract(
        name=real.name, covers=real.covers, build=real.build,
        scan_free=real.scan_free, dtypes=real.dtypes,
        donate=real.donate, donate_unused_ok=(),
    )
    violations, _, _ = check_contract(stripped, checks=("donation",))
    assert "donation" in _checks_of(violations)


def test_unused_ok_carry_still_requires_the_declaration():
    """donate_unused_ok forgives the missing ALIAS, never the missing
    DECLARATION: a kernel that stops donating the carry entirely (no
    attr, no unusable-donation warning at lower time) must still fail."""
    c = _contract(_b_no_donation, donate=(0,), donate_unused_ok=(0,))
    violations, _, _ = check_contract(c, checks=("donation",))
    assert _checks_of(violations) == ["donation"]
    assert "declaration was lost" in violations[0].message


def test_orphaned_goldens_fail_until_reblessed(
    monkeypatch, tmp_path, capsys
):
    """The golden compare is bidirectional: an entry whose instance
    (or whole contract) no longer exists is dead weight masquerading
    as a blessed review — red until --update-programs drops it."""
    jnp = _jnp()

    def two():
        return [
            ProgramInstance("A", lambda x: x + 1, (jnp.zeros(8, jnp.int32),)),
            ProgramInstance("B", lambda x: x * 2, (jnp.zeros(8, jnp.int32),)),
        ]

    def one():
        return [
            ProgramInstance("A", lambda x: x + 1, (jnp.zeros(8, jnp.int32),)),
        ]

    gpath = tmp_path / "goldens.json"
    monkeypatch.setattr(
        programs, "REGISTRY", {"seed.ok": _contract(two, name="seed.ok")}
    )
    assert analysis_cli.main(
        ["--update-programs", "--programs-goldens", str(gpath)]
    ) == 0
    # instance B removed: its golden is now an orphan
    monkeypatch.setattr(
        programs, "REGISTRY", {"seed.ok": _contract(one, name="seed.ok")}
    )
    capsys.readouterr()
    rc = analysis_cli.main(
        ["--programs", "--programs-goldens", str(gpath)]
    )
    assert rc != 0 and "orphaned golden" in capsys.readouterr().out
    # whole contract gone: same story
    assert analysis_cli.main(
        ["--update-programs", "--programs-goldens", str(gpath)]
    ) == 0
    monkeypatch.setattr(programs, "REGISTRY", {})
    rc = analysis_cli.main(
        ["--programs", "--programs-goldens", str(gpath)]
    )
    assert rc != 0 and "no longer registered" in capsys.readouterr().out


def test_golden_drift_and_missing_golden_fail_cli(
    monkeypatch, tmp_path, capsys
):
    jnp = _jnp()

    def b():
        return [
            ProgramInstance("L8", lambda x: x + 1, (jnp.zeros(8, jnp.int32),))
        ]

    good = {"seed.ok": _contract(b, name="seed.ok")}
    monkeypatch.setattr(programs, "REGISTRY", good)
    gpath = tmp_path / "goldens.json"

    # no goldens yet: missing fingerprints are a failure, not a skip
    rc = analysis_cli.main(
        ["--programs", "--programs-goldens", str(gpath)]
    )
    assert rc != 0 and "[golden]" in capsys.readouterr().out

    # bless, then clean
    assert analysis_cli.main(
        ["--update-programs", "--programs-goldens", str(gpath)]
    ) == 0
    assert analysis_cli.main(
        ["--programs", "--programs-goldens", str(gpath)]
    ) == 0
    capsys.readouterr()

    # the kernel's structure changes: drift fails until re-blessed
    def b2():
        return [
            ProgramInstance("L8", lambda x: x * 2 + 1,
                            (jnp.zeros(8, jnp.int32),))
        ]

    monkeypatch.setattr(
        programs, "REGISTRY", {"seed.ok": _contract(b2, name="seed.ok")}
    )
    rc = analysis_cli.main(
        ["--programs", "--programs-goldens", str(gpath)]
    )
    out = capsys.readouterr().out
    assert rc != 0 and "fingerprint drifted" in out
    assert analysis_cli.main(
        ["--update-programs", "--programs-goldens", str(gpath)]
    ) == 0
    assert analysis_cli.main(
        ["--programs", "--programs-goldens", str(gpath)]
    ) == 0


def test_update_refuses_to_bless_violating_program(monkeypatch, tmp_path):
    """--update-programs must not be a bypass: a program that violates
    its non-golden checks cannot be written into the goldens."""
    monkeypatch.setattr(
        programs, "REGISTRY", {"seed.bad": SEEDED_BADS["scan"]}
    )
    gpath = tmp_path / "goldens.json"
    rc = analysis_cli.main(
        ["--update-programs", "--programs-goldens", str(gpath)]
    )
    assert rc != 0
    assert not gpath.exists()


def test_assert_contract_is_the_bench_seam(monkeypatch):
    """test_spgemm.py's `"scan[" not in jaxpr` greps live on
    assert_contract — prove the seam raises on the bug class they used
    to catch."""
    programs.assert_contract("sets.intersect_many")  # shipped: passes
    monkeypatch.setitem(
        programs.REGISTRY, "seed.bad", SEEDED_BADS["scan"]
    )
    with pytest.raises(AssertionError, match="scan"):
        programs.assert_contract("seed.bad")


def test_bucket_probe_catches_static_value_leak():
    """Second bucket-leak flavor: shapes agree but a raw size rides in
    as a static argument, so same-bucket sizes trace different
    programs (the cache still explodes)."""
    jnp = _jnp()

    def make(n):
        from dgraph_tpu.ops.sets import bucket

        def f(x, raw):
            return x[:4] + raw  # raw n baked into the program

        return ProgramInstance(
            f"B{bucket(n)}", lambda x: f(x, n),
            (jnp.zeros(bucket(n), jnp.int32),),
        )

    c = _contract(
        lambda: [],
        bucket_probe=BucketProbe(pairs=((10, 12),), make=make),
    )
    violations, _, _ = check_contract(c, checks=("bucket",))
    assert _checks_of(violations) == ["bucket"]
    assert "static argument" in violations[0].message


# ----------------------------------------------------------- jaxdiag seam

def test_jaxdiag_counts_expected_and_reemits_rest():
    from dgraph_tpu.utils.jaxdiag import expected_unusable_donation
    from dgraph_tpu.utils.metrics import DONATION_FALLBACK

    before = DONATION_FALLBACK.snapshot().get("test.site", 0)
    with warnings.catch_warnings(record=True) as outer:
        warnings.simplefilter("always")
        with expected_unusable_donation("test.site"):
            warnings.warn("Some donated buffers were not usable: blah")
            warnings.warn("an unrelated diagnostic")
    assert DONATION_FALLBACK.snapshot()["test.site"] == before + 1
    assert [str(w.message) for w in outer] == ["an unrelated diagnostic"]


def test_multi_hop_fallback_is_counted_not_silent():
    """Driving the real kernel at a guaranteed-fresh shape increments
    the donation-fallback counter by exactly one compile's worth (the
    old filterwarnings left nothing) — the warning fires at lower time
    of a new (cap, n_hops) program, so the shape must be unique to this
    test (contract instances use cap=32/hops 2-3, the e2e drives 8/16)."""
    import jax.numpy as jnp

    from dgraph_tpu.ops import batch, sets
    from dgraph_tpu.utils.metrics import DONATION_FALLBACK

    offs = jnp.asarray(np.array([0, 1, 2, 2], np.int32))
    dst = jnp.asarray(np.array([1, 2], np.int32))
    cap, hops = 48, 5
    f = jnp.asarray(sets.pad_to(np.array([0]), cap))
    vis = jnp.asarray(np.full(cap, sets.SENT, np.int32))
    before = DONATION_FALLBACK.snapshot().get("ops.batch.multi_hop", 0)
    batch.multi_hop(offs, dst, f, vis, hops, cap)
    assert (
        DONATION_FALLBACK.snapshot().get("ops.batch.multi_hop", 0)
        == before + 1
    )
