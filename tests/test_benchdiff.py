"""tools/benchdiff.py over five bench rounds — the checked-in
BENCH_r03–r05 behind two written here in their shape (a crashed round,
and the one pre-series round the driver parsed on a chip): round
loading (parsed / tail-recovery / unparseable), metric alignment with
explicit "n/a" for missing fields, polarity-oriented regression flags,
and the CLI entrypoint."""

import json
import os
import subprocess
import sys

import pytest

from foundationdb_tpu.tools import benchdiff

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CMD = "if [ -f bench.py ]; then python bench.py; else exit 0; fi"
# ROADMAP "What the records are": the one round that ran on a chip
CHIP_ROUND = {
    "metric": "resolved_txns_per_sec_ycsb_a_zipfian99",
    "value": 1675420.4, "unit": "txns/sec", "vs_baseline": 1.675,
    "batch_size": 8192, "p99_batch_ms": 7.63, "kernel_step_ms": 2.968,
    "platform": "tpu", "pallas_kernel_step": False,
    "e2e_committed_txns_per_sec": 8628.7, "e2e_mean_batch": 858.2,
}


@pytest.fixture(scope="module")
def ROUNDS(tmp_path_factory):
    d = tmp_path_factory.mktemp("rounds")
    crashed = d / "BENCH_r01.json"  # rc=1, a traceback, no JSON
    crashed.write_text(json.dumps({
        "n": 1, "cmd": CMD, "rc": 1, "parsed": None,
        "tail": "Traceback (most recent call last):\n  ...\n"
                "RuntimeError: Unable to initialize backend\n"}))
    chip = d / "BENCH_r02.json"  # the driver parsed the headline
    chip.write_text(json.dumps({
        "n": 2, "cmd": CMD, "rc": 0, "parsed": CHIP_ROUND,
        "tail": json.dumps(CHIP_ROUND) + "\n"}))
    return [str(crashed), str(chip)] + [
        os.path.join(REPO, f"BENCH_r0{n}.json") for n in (3, 4, 5)]


@pytest.fixture(scope="module")
def report(ROUNDS):
    return benchdiff.diff_rounds([benchdiff.load_round(p) for p in ROUNDS])


def test_load_round_classifies_the_fixtures(ROUNDS):
    r1 = benchdiff.load_round(ROUNDS[0])  # crashed: rc=1, no JSON
    assert r1["doc"] is None and "unparseable" in r1["note"]
    r2 = benchdiff.load_round(ROUNDS[1])  # driver parsed the headline
    assert r2["note"] == "parsed"
    assert r2["doc"]["metric"] == "resolved_txns_per_sec_ycsb_a_zipfian99"
    r4 = benchdiff.load_round(ROUNDS[3])  # tail cut MID-LINE: no crash,
    assert r4["doc"] is None               # an explicit n/a round
    assert r4["rc"] == 0
    r5 = benchdiff.load_round(ROUNDS[4])  # the compact summary round
    assert r5["doc"].get("summary") is True
    assert isinstance(r5["doc"]["configs"], dict)


def test_rounds_align_with_explicit_na(report):
    assert len(report["rounds"]) == 5
    # the crashed and cut rounds carry zero metrics, not KeyErrors
    assert report["rounds"][0]["n_metrics"] == 0
    assert report["rounds"][3]["n_metrics"] == 0
    assert report["rounds"][0]["metric"] == "n/a"
    # provenance header: these rounds predate schema_rev stamping, so
    # the differ shows explicit n/a rather than failing
    assert report["rounds"][1]["schema_rev"] == "n/a"
    assert report["rounds"][1]["git_rev"] == "n/a"
    by_name = {r["metric"]: r for r in report["metrics"]}
    # the headline metric aligns r02 -> r05 with n/a cells between
    row = by_name["value"]
    assert row["values"][0] == "n/a" and row["values"][3] == "n/a"
    assert row["first"] == 1675420.4 and row["last"] == 650335.8
    # r05's compact-summary configs flatten into per-config rows
    assert by_name["configs.mako"]["last"] == 23403.8
    assert by_name["configs.ring_capacity"]["last"] == 1.331
    # a metric only ONE round carries still gets a row (no trend)
    assert by_name["configs.mako"]["delta"] == "n/a"


def test_regression_flags_follow_polarity(report):
    by_name = {r["metric"]: r for r in report["metrics"]}
    # throughput fell r02 -> r05 (different platform): flagged
    assert by_name["value"]["trend"] == "REGRESSION"
    assert "value" in report["regressions"]
    # latency fell too — for a lower-better metric that's an improvement
    assert by_name["kernel_step_ms"]["pct"] < 0
    assert by_name["kernel_step_ms"]["trend"] == "improved"


def test_polarity_table():
    assert benchdiff.polarity("e2e_committed_txns_per_sec") == +1
    assert benchdiff.polarity("commit_p99_ms") == -1
    assert benchdiff.polarity("pad_waste_pct") == -1
    assert benchdiff.polarity("lane_skew_pct") == -1
    assert benchdiff.polarity("recompiles") == -1
    assert benchdiff.polarity("profile_overhead_pct") == -1
    assert benchdiff.polarity("staging_reuse_rate") == +1
    assert benchdiff.polarity("hot_range_buckets") == 0  # never flagged
    # sharded resolve: the headline speedup climbs, the router's lane
    # imbalance only ever regresses up
    assert benchdiff.polarity("sharded_speedup") == +1
    assert benchdiff.polarity("resolver_shard_smoke") == +1
    # multi-region replication: lag and failovers only ever regress up
    assert benchdiff.polarity("replication_lag_ms") == -1
    assert benchdiff.polarity("replication_lag_versions") == -1
    assert benchdiff.polarity("region_failovers") == -1
    assert benchdiff.polarity("last_failover_ms") == -1


def test_bare_bench_line_accepted(tmp_path):
    """Raw bench.py output saved by hand (no {n, rc, tail} wrapper)
    diffs directly."""
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps({"metric": "m", "value": 100.0,
                             "pad_waste_pct": 10.0}))
    b.write_text(json.dumps({"metric": "m", "value": 200.0,
                             "pad_waste_pct": 40.0}))
    rep = benchdiff.diff_rounds([benchdiff.load_round(str(a)),
                                 benchdiff.load_round(str(b))])
    by_name = {r["metric"]: r for r in rep["metrics"]}
    assert by_name["value"]["trend"] == "improved"
    assert by_name["pad_waste_pct"]["trend"] == "REGRESSION"
    assert "pad_waste_pct" in rep["regressions"]


def test_dict_fields_contribute_totals(tmp_path):
    """bucket_histogram / fallback_causes roll up as <key>.total so the
    trajectory shows volume drift without a column per bucket."""
    a = tmp_path / "a.json"
    a.write_text(json.dumps({
        "metric": "m", "value": 1.0,
        "fallback_causes": {"flat_to_legacy": 2, "too_old_rv": 1},
        "bucket_histogram": {"8": 5},
    }))
    m = benchdiff.extract_metrics(benchdiff.load_round(str(a))["doc"])
    assert m["fallback_causes.total"] == 3
    assert m["bucket_histogram.total"] == 5


def test_format_report_renders_na_and_regressions(report):
    text = benchdiff.format_report(report)
    assert "bench trajectory: 5 rounds" in text
    assert "n/a" in text
    assert "REGRESSIONS" in text and "value" in text


def test_cli_module_entrypoint(ROUNDS):
    """``python -m foundationdb_tpu.tools.benchdiff`` produces the
    aligned report (text and --json) and exits nonzero on regression."""
    proc = subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.tools.benchdiff",
         ROUNDS[1], ROUNDS[4]],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    assert "bench trajectory: 2 rounds" in proc.stdout
    assert "REGRESSION" in proc.stdout
    assert proc.returncode == 1  # the r02->r05 throughput drop gates
    proc2 = subprocess.run(
        [sys.executable, "-m", "foundationdb_tpu.tools.benchdiff",
         "--json", ROUNDS[1], ROUNDS[4]],
        capture_output=True, text=True, cwd=REPO, timeout=120,
    )
    doc = json.loads(proc2.stdout)
    assert {r["metric"] for r in doc["metrics"]} >= {"value",
                                                     "vs_baseline"}
