"""Benchmark harness: suite grids, the entry runner, JSON reports, and the
baseline regression gate (what CI's bench-smoke job exercises)."""
import json

import numpy as np
import pytest

from benchmarks import report as report_mod
from benchmarks import run as run_cli
from benchmarks import runner, suites


def _tiny_entry(**kw):
    base = dict(
        problem="ferromagnet", size=4, seed=0, kernel="tau_leap",
        backend="ref", n_steps=24, n_chains=2, sample_every=6,
        schedule=("geometric", 0.5, 2.0), kernel_args=(("dt", 0.25),),
        rel_gap=0.1,
    )
    base.update(kw)
    return suites.SuiteEntry(**base)


def test_smoke_suite_coverage():
    """The acceptance grid: >= 4 problems x >= 3 kernels, unique ids."""
    entries = suites.smoke_suite()
    probs = {e.problem for e in entries}
    kernels = {e.kernel for e in entries}
    assert len(probs) >= 4, probs
    assert len(kernels) >= 3, kernels
    ids = [e.id for e in entries]
    assert len(ids) == len(set(ids))
    # kernel/problem compatibility respected (kind from the zoo registry)
    from repro.core import problems

    for e in entries:
        kind = problems.problem_kind(e.problem)
        assert e.kernel in suites.KERNELS_BY_KIND[kind]
        if e.backend == "pallas":
            # only kernel/problem combinations the driver can honor (it now
            # raises on the rest): dense tau-leap, lattice chromatic gibbs,
            # sparse colored gibbs
            assert (
                (e.kernel == "tau_leap" and kind == "dense")
                or (e.kernel == "chromatic_gibbs" and kind == "lattice")
                or (e.kernel == "colored_gibbs" and kind == "sparse")
            )
    # the fused lattice sweep is in the measured grid (ROADMAP open item 2)
    assert any(
        e.kernel == "chromatic_gibbs" and e.backend == "pallas" for e in entries
    )
    # ...and the fused sparse colored sweep alongside it
    assert any(
        e.kernel == "colored_gibbs" and e.backend == "pallas" for e in entries
    )
    # both sparse zoo families are measured
    assert {"maxcut3r", "king"} <= probs


def test_ctmc_site_draw_entries_in_suites():
    """Both CTMC event-selection paths (and an event-block entry) are
    measured head-to-head on one big dense instance in every suite."""
    for suite in (suites.smoke_suite(), suites.full_suite()):
        ctmc_entries = [e for e in suite if e.kernel == "ctmc" and e.kernel_args]
        draws = {dict(e.kernel_args).get("site_draw") for e in ctmc_entries}
        assert {"scan", "tree"} <= draws
        assert any(e.unroll == 4 for e in ctmc_entries)
        sizes = {e.size for e in ctmc_entries}
        assert max(sizes) >= 256
        # the dense site-draw trio shares instance/steps/chains: the site
        # draw (and the event block) is the only variable
        dense_trio = [e for e in ctmc_entries if e.problem == "sk"]
        assert len({(e.problem, e.size, e.seed, e.n_steps, e.n_chains)
                    for e in dense_trio}) == 1
        # the sparse-vs-dense layout trio: same 3-regular graph at n >= 1024,
        # single chain (the tree-reuse cond degrades under vmap), pinned
        # unroll, constant beta — layout/site-draw is the only variable
        layout_trio = [e for e in ctmc_entries if e.problem == "maxcut3r"]
        assert len(layout_trio) == 3
        assert {e.problem_args for e in layout_trio} == {(), (("dense", True),)}
        assert all(e.n_chains == 1 and e.unroll == 1 for e in layout_trio)
        assert all(e.size >= 1024 for e in layout_trio)
        assert all(e.schedule == ("constant", 1.0) for e in layout_trio)
        assert len({e.id for e in layout_trio}) == 3  # problem_args in the id
    # an explicit unroll is part of the record identity
    a = _tiny_entry(problem="sk", size=6, kernel="ctmc",
                    kernel_args=(("site_draw", "tree"),))
    b = _tiny_entry(problem="sk", size=6, kernel="ctmc",
                    kernel_args=(("site_draw", "tree"),), unroll=4)
    assert a.id != b.id and b.id.endswith("/u4")
    rec = runner.run_entry(b)
    assert rec["unroll"] == 4
    json.dumps(rec)


def test_suite_registry_and_deterministic_seeding():
    assert set(suites.SUITES) >= {"smoke", "full"}
    with pytest.raises(KeyError):
        suites.get_suite("warp")
    e = _tiny_entry()
    assert suites.stable_seed(e.id) == suites.stable_seed(e.id)
    assert suites.stable_seed("a") != suites.stable_seed("b")
    np.testing.assert_array_equal(
        np.asarray(jax_key_data(e.key())), np.asarray(jax_key_data(_tiny_entry().key()))
    )


def jax_key_data(key):
    import jax

    return jax.random.key_data(key)


def test_run_entry_record_schema():
    rec = runner.run_entry(_tiny_entry())
    for field in (
        "id", "problem", "instance", "kernel", "backend", "n_steps", "n_chains",
        "ref_energy", "ref_kind", "target_energy", "compile_s", "wall_s",
        "steps_per_s", "chain_steps_per_s", "best_energy", "final_gap",
        "hit_rate", "tts_model_time", "gap_trajectory",
    ):
        assert field in rec, field
    assert rec["steps_per_s"] > 0 and rec["chain_steps_per_s"] > 0
    assert rec["chain_steps_per_s"] == pytest.approx(rec["steps_per_s"] * 2, rel=1e-6)
    assert 0.0 <= rec["hit_rate"] <= 1.0
    # best-so-far gap trajectory is nonincreasing, in model time
    traj = np.asarray(rec["gap_trajectory"])
    assert traj.shape[1] == 2
    assert np.all(np.diff(traj[:, 1]) <= 1e-6)
    assert np.all(np.diff(traj[:, 0]) >= -1e-6)
    json.dumps(rec)  # JSON-serializable end to end


def test_run_entry_single_chain_and_suite_cache():
    recs = runner.run_suite(
        [_tiny_entry(n_chains=1), _tiny_entry(n_chains=1, kernel="chromatic_gibbs",
                                              kernel_args=())],
        log=lambda m: None,
    )
    assert len(recs) == 2
    assert recs[0]["ref_energy"] == recs[1]["ref_energy"]
    assert recs[0]["n_chains"] == 1


def test_report_roundtrip_and_schema_version(tmp_path):
    rec = runner.run_entry(_tiny_entry())
    rep = report_mod.make_report("unit", "smoke", [rec])
    assert rep["schema_version"] == report_mod.SCHEMA_VERSION
    path = report_mod.write_report(rep, str(tmp_path))
    assert path.endswith("BENCH_unit.json")
    loaded = report_mod.load(path)
    assert loaded["records"][0]["id"] == rec["id"]

    bad = dict(rep, schema_version=1)
    bad_path = tmp_path / "BENCH_bad.json"
    bad_path.write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="schema_version"):
        report_mod.load(str(bad_path))


def _fake_report(throughputs: dict) -> dict:
    recs = [
        {"id": rid, "chain_steps_per_s": v, "steps_per_s": v, "wall_s": 1.0}
        for rid, v in throughputs.items()
    ]
    return report_mod.make_report("fake", "smoke", recs)


def test_baseline_regression_gate():
    baseline = report_mod.to_baseline(_fake_report({"a": 100.0, "b": 100.0}))
    baseline["host"]["ci"] = True  # CI-produced baseline: the gate is armed
    ok, summary = report_mod.compare_to_baseline(
        _fake_report({"a": 90.0, "b": 95.0}), baseline, threshold=0.30
    )
    assert ok and summary["geomean_ratio"] > 0.9

    ok, summary = report_mod.compare_to_baseline(
        _fake_report({"a": 40.0, "b": 50.0}), baseline, threshold=0.30
    )
    assert not ok and summary["geomean_ratio"] < 0.7
    assert summary["worst"] == "a"
    assert "REGRESSION" in report_mod.format_comparison(summary)

    # new + missing ids are reported but do not gate
    ok, summary = report_mod.compare_to_baseline(
        _fake_report({"a": 100.0, "c": 1.0}), baseline, threshold=0.30
    )
    assert ok
    assert summary["new_ids"] == ["c"] and summary["missing_ids"] == ["b"]
    assert "REGRESSION" not in report_mod.format_comparison(summary)


def test_baseline_gate_advisory_for_non_ci_baseline():
    """A regression vs a dev-machine baseline (host.ci false) must be loud
    but non-fatal: absolute throughput is not runner-comparable."""
    baseline = report_mod.to_baseline(_fake_report({"a": 100.0}))
    baseline["host"]["ci"] = False
    ok, summary = report_mod.compare_to_baseline(
        _fake_report({"a": 10.0}), baseline, threshold=0.30
    )
    assert ok and summary["advisory"] and not summary["passed"]
    assert "ADVISORY" in report_mod.format_comparison(summary)


def test_baseline_gate_fails_on_zero_overlap():
    """An id-scheme change must not turn the gate vacuous."""
    baseline = report_mod.to_baseline(_fake_report({"a": 100.0}))
    ok, summary = report_mod.compare_to_baseline(
        _fake_report({"renamed": 100.0}), baseline, threshold=0.30
    )
    assert not ok and summary["error"] is not None
    assert "ERROR" in report_mod.format_comparison(summary)


def test_reports_are_strict_json(tmp_path):
    """No-hit entries serialize tts as null, never the Infinity token."""
    rec = runner.run_entry(_tiny_entry(n_steps=2, rel_gap=0.0))
    rep = report_mod.make_report("strict", "smoke", [rec])
    path = report_mod.write_report(rep, str(tmp_path))
    text = open(path).read()
    assert "Infinity" not in text and "NaN" not in text
    json.loads(text)


def test_cli_end_to_end_tiny_suite(tmp_path, monkeypatch):
    """`python -m benchmarks.run --suite <tiny>` writes a schema-versioned
    report, updates a baseline, and the check gate passes against itself."""
    monkeypatch.setitem(suites.SUITES, "tiny", lambda: [_tiny_entry()])
    baseline = tmp_path / "baseline.json"
    rc = run_cli.main([
        "--suite", "tiny", "--tag", "t0", "--out", str(tmp_path),
        "--update-baseline", "--baseline", str(baseline),
    ])
    assert rc == 0
    rep = report_mod.load(str(tmp_path / "BENCH_t0.json"))
    assert rep["suite"] == "tiny" and len(rep["records"]) == 1

    rc = run_cli.main([
        "--suite", "tiny", "--tag", "t1", "--out", str(tmp_path),
        "--check-baseline", "--baseline", str(baseline), "--threshold", "0.95",
    ])
    assert rc == 0

    # an impossible threshold-violating CI baseline forces exit code 1
    blob = json.loads(baseline.read_text())
    blob["host"]["ci"] = True
    for v in blob["throughput"].values():
        v["chain_steps_per_s"] *= 1e9
    baseline.write_text(json.dumps(blob))
    rc = run_cli.main([
        "--suite", "tiny", "--tag", "t2", "--out", str(tmp_path),
        "--check-baseline", "--baseline", str(baseline),
    ])
    assert rc == 1

    # --update-baseline + --check-baseline: the check must run against the
    # OLD (still-impossible) baseline, not the one written from this run
    rc = run_cli.main([
        "--suite", "tiny", "--tag", "t3", "--out", str(tmp_path),
        "--check-baseline", "--update-baseline", "--baseline", str(baseline),
    ])
    assert rc == 1  # still compared against the 1e9x baseline
    # ...which has now been replaced by this run's numbers:
    assert json.loads(baseline.read_text())["tag"] == "t3"


def test_cli_baseline_from_adopts_report(tmp_path, monkeypatch, capsys):
    """--baseline-from turns an existing report (e.g. a CI artifact) into
    the baseline without running a suite, preserving host.ci."""
    monkeypatch.setitem(suites.SUITES, "tiny", lambda: [_tiny_entry()])
    out_base = tmp_path / "baseline.json"
    rec = runner.run_entry(_tiny_entry())
    rep = report_mod.make_report("ci-artifact", "smoke", [rec])
    rep["host"]["ci"] = True
    path = report_mod.write_report(rep, str(tmp_path))

    rc = run_cli.main(["--baseline-from", path, "--baseline", str(out_base)])
    assert rc == 0
    blob = json.loads(out_base.read_text())
    assert blob["host"]["ci"] is True and blob["tag"] == "ci-artifact"
    assert "ARMED" in capsys.readouterr().out


def _fake_full_report() -> dict:
    recs = []
    for kernel, tp, hit in (("ctmc", 100.0, 1.0), ("ctmc", 400.0, 0.5),
                            ("tau_leap", 200.0, 0.25)):
        recs.append({
            "id": f"{kernel}-{tp}", "kernel": kernel, "chain_steps_per_s": tp,
            "steps_per_s": tp, "wall_s": 1.0, "hit_rate": hit,
        })
    return report_mod.make_report("nightly", "full", recs)


def test_nightly_record_trims_per_kernel():
    rec = report_mod.nightly_record(_fake_full_report())
    assert rec["suite"] == "full" and rec["n_records"] == 3
    k = rec["kernels"]
    assert set(k) == {"ctmc", "tau_leap"}
    assert k["ctmc"]["entries"] == 2
    assert k["ctmc"]["geomean_chain_steps_per_s"] == pytest.approx(200.0)
    assert k["ctmc"]["hit_rate"] == pytest.approx(0.75)
    json.dumps(rec)


def test_append_nightly_trajectory(tmp_path):
    """Repeated appends grow the committed trajectory oldest-first; a
    schema mismatch refuses instead of silently mixing record shapes."""
    path = str(tmp_path / "BENCH_nightly.json")
    rep1 = _fake_full_report()
    rep1["host"]["commit"] = "sha-a"
    t1, appended1 = report_mod.append_nightly(rep1, path)
    assert appended1 and len(t1["records"]) == 1
    rep2 = _fake_full_report()
    rep2["host"]["commit"] = "sha-b"
    t2, appended2 = report_mod.append_nightly(rep2, path)
    assert appended2 and len(t2["records"]) == 2
    on_disk = json.loads(open(path).read())
    assert on_disk["schema_version"] == report_mod.SCHEMA_VERSION
    assert [r["tag"] for r in on_disk["records"]] == ["nightly", "nightly"]
    (tmp_path / "BENCH_nightly.json").write_text(
        json.dumps({"schema_version": 1, "records": []})
    )
    with pytest.raises(ValueError, match="schema_version"):
        report_mod.append_nightly(_fake_full_report(), path)


def test_append_nightly_dedups_commit_sha(tmp_path):
    """Re-running the nightly on an already-recorded commit (workflow
    retries, manual dispatches) must not pile up duplicate trajectory
    points; records with no SHA always append."""
    path = str(tmp_path / "BENCH_nightly.json")
    rep = _fake_full_report()
    rep["host"]["commit"] = "sha-a"
    _, first = report_mod.append_nightly(rep, path)
    traj, second = report_mod.append_nightly(rep, path)
    assert first and not second
    assert len(traj["records"]) == 1
    assert len(json.loads(open(path).read())["records"]) == 1
    # no-SHA reports (non-git checkouts) are never deduped
    rep_nosha = _fake_full_report()
    rep_nosha["host"]["commit"] = None
    _, a = report_mod.append_nightly(rep_nosha, path)
    _, b = report_mod.append_nightly(rep_nosha, path)
    assert a and b
    assert len(json.loads(open(path).read())["records"]) == 3


def test_nightly_trajectory_collision_guards(tmp_path):
    """The committed trajectory file must be unclobberable: the 'nightly'
    report tag is reserved (writing a FULL report to BENCH_nightly.json at
    the repo root destroyed the trajectory before append_nightly read it),
    and append_nightly refuses a file holding full per-entry records."""
    with pytest.raises(ValueError, match="reserved"):
        report_mod.report_path("nightly")
    with pytest.raises(ValueError, match="reserved"):
        report_mod.write_report(report_mod.make_report("nightly", "full", []))
    # other out_dirs are fine — only the repo-root trajectory path is special
    assert report_mod.report_path("nightly", str(tmp_path)).endswith("BENCH_nightly.json")
    assert report_mod.report_path("nightly-full").endswith("BENCH_nightly-full.json")
    # a full report written where the trajectory should be -> refuse append
    # (the fake report's tag IS "nightly", so this lands on the exact name)
    full_path = report_mod.write_report(_fake_full_report(), str(tmp_path))
    assert full_path.endswith("BENCH_nightly.json")
    with pytest.raises(ValueError, match="full per-entry records"):
        report_mod.append_nightly(_fake_full_report(), full_path)


def test_committed_nightly_trajectory_is_seeded():
    """The repo ships a valid BENCH_nightly.json for the workflow to extend."""
    assert json.loads(open(report_mod.NIGHTLY_PATH).read())["records"]


def test_cli_append_nightly(tmp_path, monkeypatch):
    monkeypatch.setitem(suites.SUITES, "tiny", lambda: [_tiny_entry()])
    path = tmp_path / "BENCH_nightly.json"
    rc = run_cli.main([
        "--suite", "tiny", "--tag", "t0", "--out", str(tmp_path),
        "--append-nightly", str(path),
    ])
    assert rc == 0
    blob = json.loads(path.read_text())
    assert len(blob["records"]) == 1
    assert blob["records"][0]["kernels"]["tau_leap"]["entries"] == 1


def test_cli_smoke_suite_conflict():
    with pytest.raises(SystemExit):
        run_cli.main(["--smoke", "--suite", "full"])
    with pytest.raises(SystemExit):  # --only without --figures
        run_cli.main(["--only", "fig3a"])


# ---------------------------------------------------------------------------
# The async-vs-sync scaling-law sweep (benchmarks/scaling.py)
# ---------------------------------------------------------------------------

from benchmarks import scaling  # noqa: E402


def _tiny_spec(**kw):
    base = dict(problem="sk", sizes=(6, 10), n_instances=1, n_trials=4,
                steps_base=300, steps_per_n=30, n_boot=20)
    base.update(kw)
    return scaling.ScalingSpec(**base)


def test_run_scaling_tiny_grid_record_schema():
    rec = scaling.run_scaling(_tiny_spec(), log=lambda m: None)
    assert rec["sync_kernel"] == scaling.SYNC_KERNEL
    assert set(rec["kernels"]) == {"random_scan_gibbs", "ctmc", "tau_leap"}
    assert rec["kernels"]["random_scan_gibbs"]["role"] == "sync"
    for kernel, kr in rec["kernels"].items():
        assert len(kr["tts_median"]) == len(rec["sizes"]) == 2
        assert all(0.0 <= h <= 1.0 for h in kr["hit_rate"])
        if kr["fit"] is not None:
            assert kr["fit"]["B_ci"][0] <= kr["fit"]["B"] <= kr["fit"]["B_ci"][1]
            assert len(kr["sizes_fit"]) >= 2
        assert set(kr["mixing"]) >= {"ess", "split_rhat", "tau_int_steps",
                                     "flip_rate", "size"}
        assert kr["mixing"]["size"] == rec["sizes"][-1]
    assert set(rec["gap_vs_sync"]) == {"ctmc", "tau_leap"}
    for g in rec["gap_vs_sync"].values():
        if g["pvalue"] is not None:
            assert 0.0 <= g["pvalue"] <= 1.0
            assert g["exponent_gap"] == pytest.approx(
                g["B_sync"] - g["B_async"]
            )
    json.dumps(rec)  # the whole record must be JSON-ready


def test_scaling_sparse_problems_include_colored_gibbs():
    spec = _tiny_spec(problem="maxcut3r")
    assert "colored_gibbs" in scaling._spec_kernels(spec)
    assert scaling._spec_kernels(_tiny_spec()) == (
        "random_scan_gibbs", "ctmc", "tau_leap"
    )


def test_scaling_rejects_lattice_problems():
    with pytest.raises(ValueError, match="dense/sparse"):
        scaling._spec_kernels(_tiny_spec(problem="ferromagnet"))


def test_scaling_committed_grids_cover_acceptance_problems():
    """Both committed grids sweep SK and 3-regular MaxCut (the PR's
    acceptance grids), smoke strictly smaller than full."""
    for name in ("smoke", "full"):
        specs = scaling.get_scaling_specs(name)
        assert {s.problem for s in specs} == {"sk", "maxcut3r"}
    smoke = {s.problem: s for s in scaling.get_scaling_specs("smoke")}
    full = {s.problem: s for s in scaling.get_scaling_specs("full")}
    for p in smoke:
        assert max(smoke[p].sizes) <= max(full[p].sizes)
        assert smoke[p].n_boot <= full[p].n_boot
    with pytest.raises(KeyError):
        scaling.get_scaling_specs("warp")


def _fake_scaling_section() -> dict:
    return {
        "schema_version": scaling.SCALING_SCHEMA_VERSION,
        "problems": {
            "sk": {
                "kernels": {
                    "random_scan_gibbs": {"fit": {"B": 0.9}},
                    "ctmc": {"fit": {"B": 0.4}},
                    "tau_leap": {"fit": None},
                },
                "gap_vs_sync": {
                    "ctmc": {"pvalue": 0.01},
                    "tau_leap": {"pvalue": None},
                },
            }
        },
    }


def test_report_embeds_scaling_and_nightly_rollup():
    rep = report_mod.make_report(
        "s", "smoke", [], scaling=_fake_scaling_section()
    )
    assert rep["scaling"]["schema_version"] == scaling.SCALING_SCHEMA_VERSION
    # absent when not swept
    assert "scaling" not in report_mod.make_report("s", "smoke", [])
    # the nightly record trims it to exponents + p-values only
    full = _fake_full_report()
    full["scaling"] = _fake_scaling_section()
    rec = report_mod.nightly_record(full)
    assert rec["scaling"]["sk"]["B"] == {
        "random_scan_gibbs": 0.9, "ctmc": 0.4, "tau_leap": None
    }
    assert rec["scaling"]["sk"]["pvalue_vs_sync"]["ctmc"] == 0.01
    assert "kernels" not in rec["scaling"]["sk"].get("B", {}).get("mixing", {})
    json.dumps(rec)
    # no scaling section -> no rollup key
    assert "scaling" not in report_mod.nightly_record(_fake_full_report())


def test_cli_scaling_tiny_grid(tmp_path, monkeypatch):
    """`--scaling <grid>` embeds the section in the written report."""
    monkeypatch.setitem(suites.SUITES, "tiny", lambda: [_tiny_entry()])
    monkeypatch.setitem(
        scaling.SCALING_SPECS, "tinygrid", lambda: [_tiny_spec()]
    )
    rc = run_cli.main([
        "--suite", "tiny", "--tag", "sc", "--out", str(tmp_path),
        "--scaling", "tinygrid",
    ])
    assert rc == 0
    rep = report_mod.load(str(tmp_path / "BENCH_sc.json"))
    assert "sk" in rep["scaling"]["problems"]
    kr = rep["scaling"]["problems"]["sk"]["kernels"]
    assert {"random_scan_gibbs", "ctmc", "tau_leap"} == set(kr)


def test_committed_pr7_report_has_scaling_section():
    """The acceptance artifact: BENCH_pr7.json carries per-kernel TTS
    exponents with bootstrap CIs and async-vs-sync p-values on the SK and
    3-regular MaxCut grids."""
    import os

    path = os.path.join(report_mod.REPO_ROOT, "BENCH_pr7.json")
    rep = report_mod.load(path)
    section = rep["scaling"]
    assert section["schema_version"] == scaling.SCALING_SCHEMA_VERSION
    assert {"sk", "maxcut3r"} <= set(section["problems"])
    for rec in section["problems"].values():
        sync = rec["kernels"][rec["sync_kernel"]]
        assert sync["fit"] is not None and len(sync["fit"]["B_ci"]) == 2
        assert any(
            g["pvalue"] is not None for g in rec["gap_vs_sync"].values()
        )


# ---------------------------------------------------------------------------
# Crash-safe harness: fault entries, isolation, timeout/retry, partial reports
# ---------------------------------------------------------------------------

from benchmarks import robustness as robustness_mod  # noqa: E402


def test_entry_dict_roundtrip_is_exact():
    """The subprocess wire format: entry -> dict -> JSON -> entry must be
    lossless, including the tuple-of-pairs fields JSON turns into lists."""
    entry = _tiny_entry(
        problem_args=(("dense", True),), faults=(("quantize_bits", 4),
                                                 ("stuck_fraction", 0.1)),
        unroll=4,
    )
    wire = json.loads(json.dumps(suites.entry_to_dict(entry)))
    assert suites.entry_from_dict(wire) == entry
    # ...and for the default-everything entry too
    plain = _tiny_entry()
    assert suites.entry_from_dict(json.loads(json.dumps(suites.entry_to_dict(plain)))) == plain


def test_fault_entries_in_suite_and_id():
    """The smoke suite measures at least one fault-injected entry, and the
    fault spec is part of the record identity (a faulted run must never be
    baselined against the ideal one)."""
    entries = suites.smoke_suite()
    faulted = [e for e in entries if e.faults]
    assert faulted, "smoke suite has no fault-injection entry"
    assert all("/f[" in e.id for e in faulted)
    ideal = _tiny_entry()
    assert ideal.id != _tiny_entry(faults=(("quantize_bits", 4),)).id
    # make_faults: deterministic stuck draw keyed off the entry id
    e = faulted[0]
    zoo = e.make_problem()
    f1, f2 = e.make_faults(zoo.problem), e.make_faults(zoo.problem)
    assert f1 is not None
    np.testing.assert_array_equal(np.asarray(f1.stuck_mask), np.asarray(f2.stuck_mask))
    assert _tiny_entry().make_faults(zoo.problem) is None
    with pytest.raises(ValueError, match="unknown fault"):
        _tiny_entry(faults=(("warp", 9),)).make_faults(zoo.problem)


def test_run_entry_records_fault_description():
    rec = runner.run_entry(_tiny_entry(faults=(("quantize_bits", 4),)))
    assert rec["status"] == "ok"
    assert rec["faults"] == {"quantize_bits": 4}
    assert runner.run_entry(_tiny_entry())["faults"] is None
    json.dumps(rec)


def test_timeout_requires_isolate():
    with pytest.raises(ValueError, match="isolate"):
        runner.run_suite([_tiny_entry()], log=lambda m: None, timeout_s=5.0)
    with pytest.raises(SystemExit):  # the CLI enforces the same invariant
        run_cli.main(["--smoke", "--timeout", "5"])


def test_suite_degrades_on_hang_and_crash(tmp_path, monkeypatch):
    """The acceptance scenario: a suite with one deliberately hanging entry
    and one crashing entry completes, records status timeout/error for
    them (timeout immediately, the crash after one retry), measures the
    healthy entry, and still writes a schema-valid strict-JSON report."""
    entries = [
        _tiny_entry(seed=0),
        _tiny_entry(seed=1),
        _tiny_entry(seed=2, faults=(("quantize_bits", 4),)),
    ]
    monkeypatch.setenv("BENCH_FAULT_INJECT", json.dumps({
        entries[0].id: "hang", entries[1].id: "crash",
    }))
    logs = []
    records = runner.run_suite(
        entries, log=logs.append, timeout_s=60.0, isolate=True,
        retries=1, backoff_s=0.05,
    )
    by_id = {r["id"]: r for r in records}
    assert by_id[entries[0].id]["status"] == "timeout"
    assert by_id[entries[0].id]["attempts"] == 1  # hangs are never retried
    assert "timeout" in by_id[entries[0].id]["error"]
    assert by_id[entries[1].id]["status"] == "error"
    assert by_id[entries[1].id]["attempts"] == 2  # one retry with backoff
    assert "injected crash" in by_id[entries[1].id]["error"]
    ok = by_id[entries[2].id]
    assert ok["status"] == "ok" and ok["chain_steps_per_s"] > 0
    assert ok["faults"] == {"quantize_bits": 4}

    rep = report_mod.make_report("degraded", "smoke", records)
    assert rep["statuses"] == {"timeout": 1, "error": 1, "ok": 1}
    path = report_mod.write_report(rep, str(tmp_path))
    loaded = report_mod.load(path)  # schema-valid, strict JSON
    assert len(loaded["records"]) == 3
    # only the measured entry reaches the baseline / nightly rollup
    assert set(report_mod.to_baseline(loaded)["throughput"]) == {entries[2].id}
    night = report_mod.nightly_record(loaded)
    assert night["statuses"] == rep["statuses"]
    assert set(night["kernels"]) == {"tau_leap"}
    assert night["kernels"]["tau_leap"]["entries"] == 1


def test_status_filtering_in_baseline_gate_and_rollup():
    """Non-ok records are excluded from gating but visible as missing; a
    pre-status report (no status field at all) still counts everything."""
    ok_rec = {"id": "a", "status": "ok", "kernel": "ctmc",
              "chain_steps_per_s": 100.0, "steps_per_s": 100.0,
              "wall_s": 1.0, "hit_rate": 1.0}
    bad_rec = {"id": "b", "status": "timeout", "error": "budget",
               "kernel": "ctmc"}
    assert [r["id"] for r in report_mod.ok_records([ok_rec, bad_rec])] == ["a"]
    assert report_mod.status_counts([ok_rec, bad_rec]) == {"ok": 1, "timeout": 1}
    legacy = {"id": "c", "chain_steps_per_s": 1.0}  # pre-status schema
    assert report_mod.ok_records([legacy]) == [legacy]

    baseline = report_mod.to_baseline(
        report_mod.make_report("base", "smoke", [
            ok_rec, dict(ok_rec, id="b", status="ok"),
        ])
    )
    baseline["host"]["ci"] = True
    ok, summary = report_mod.compare_to_baseline(
        report_mod.make_report("now", "smoke", [ok_rec, bad_rec]),
        baseline, threshold=0.30,
    )
    assert ok  # the timed-out entry does not gate...
    assert summary["missing_ids"] == ["b"]  # ...but is loudly missing


def test_atomic_report_writes_survive_midwrite_failure(tmp_path):
    """Satellite: a writer that dies mid-write must leave the previous
    complete file untouched and no tmp debris (tmp + os.replace)."""
    path = str(tmp_path / "BENCH_nightly.json")
    rep = _fake_full_report()
    rep["host"]["commit"] = "sha-a"
    report_mod.append_nightly(rep, path)
    before = open(path).read()
    # NaN is unserializable under allow_nan=False: the dump dies after the
    # tmp file is partially written — exactly a mid-write crash.
    with pytest.raises(ValueError):
        report_mod._atomic_write_json(path, {"x": float("nan")})
    assert open(path).read() == before
    import os

    assert os.listdir(tmp_path) == ["BENCH_nightly.json"]  # no tmp debris
    # the next good write goes through
    report_mod._atomic_write_json(path, {"ok": True})
    assert json.loads(open(path).read()) == {"ok": True}


def test_report_embeds_robustness_section(tmp_path, monkeypatch):
    fake = {"schema_version": robustness_mod.ROBUSTNESS_SCHEMA_VERSION,
            "grid": "tinygrid", "instances": [], "sanity": [], "sanity_ok": True}
    rep = report_mod.make_report("r", "smoke", [], robustness=fake)
    assert rep["robustness"]["sanity_ok"] is True
    assert "robustness" not in report_mod.make_report("r", "smoke", [])
    # the CLI wires --robustness through to the report
    monkeypatch.setitem(suites.SUITES, "tiny", lambda: [_tiny_entry()])
    monkeypatch.setitem(robustness_mod.SWEEP_SPECS, "tinygrid", [])
    monkeypatch.setattr(
        robustness_mod, "robustness_section", lambda grid, log=print: dict(fake, grid=grid)
    )
    rc = run_cli.main([
        "--suite", "tiny", "--tag", "rb", "--out", str(tmp_path),
        "--robustness", "tinygrid",
    ])
    assert rc == 0
    rep = report_mod.load(str(tmp_path / "BENCH_rb.json"))
    assert rep["robustness"]["grid"] == "tinygrid"


def test_robustness_grids_cover_acceptance_axes():
    """>= 3 levels per severity axis, and every committed grid sweeps one
    dense SK and one sparse 3-regular max-cut instance."""
    assert len(robustness_mod.QUANTIZE_BITS_LEVELS) >= 3
    assert len(robustness_mod.STUCK_FRACTION_LEVELS) >= 3
    assert 0.0 in robustness_mod.STUCK_FRACTION_LEVELS
    for grid, specs in robustness_mod.SWEEP_SPECS.items():
        assert {s["problem"] for s in specs} >= {"sk", "maxcut3r"}, grid
        assert grid in robustness_mod.SANITY_SPECS
    with pytest.raises(KeyError, match="grid"):
        robustness_mod.robustness_section("warp", log=lambda m: None)


def test_cli_exits_nonzero_when_an_entry_fails(tmp_path, monkeypatch):
    """A suite with a failed entry still writes its whole report, and the
    exit code says that it failed."""
    monkeypatch.setitem(
        suites.SUITES, "tiny", lambda: [_tiny_entry(seed=0), _tiny_entry(seed=1)]
    )
    real_run_entry = runner.run_entry

    def failing_second(entry, zoo=None):
        if entry.seed == 1:
            raise RuntimeError("injected failure")
        return real_run_entry(entry, zoo)

    monkeypatch.setattr(runner, "run_entry", failing_second)
    rc = run_cli.main(["--suite", "tiny", "--tag", "bad", "--out", str(tmp_path),
                       "--retries", "0"])
    assert rc == 1
    rep = report_mod.load(str(tmp_path / "BENCH_bad.json"))
    assert rep["statuses"] == {"ok": 1, "error": 1}


def test_host_info_names_the_device():
    import jax

    host = report_mod.host_info()
    assert host["backend"] == jax.devices()[0].platform
    assert host["device_kind"] == jax.devices()[0].device_kind
    assert host["device_count"] == len(jax.devices())


def test_compile_cache_placement(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and is left alone; without it the
    cache goes to the fixed <root>/.jax_cache."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
        assert report_mod.use_compile_cache(str(tmp_path)) == str(tmp_path / "outside")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = report_mod.use_compile_cache(str(tmp_path))
        assert path == str(tmp_path / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_isolated_suite_parent_stays_off_the_device(tmp_path):
    """With isolate=True no JAX backend exists in the parent when a child
    starts: on a TPU host the parent would otherwise hold the chip."""
    import os
    import subprocess
    import sys

    script = tmp_path / "parent.py"
    script.write_text(
        "import json\n"
        "from jax._src import xla_bridge\n"
        "from benchmarks import runner, suites\n"
        "seen = []\n"
        "def child(entry, timeout_s):\n"
        "    seen.append(bool(xla_bridge._backends))\n"
        "    raise RuntimeError('no child in this test')\n"
        "runner._run_entry_subprocess = child\n"
        "entries = suites.get_suite('smoke')[:2]\n"
        "runner.run_suite(entries, log=lambda m: None, isolate=True, retries=0)\n"
        "print(json.dumps(seen))\n"
    )
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([runner.SRC_DIR, runner.REPO_ROOT]))
    out = subprocess.run([sys.executable, str(script)], cwd=runner.REPO_ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == [False, False]
