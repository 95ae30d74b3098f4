"""Longitudinal perf ledger + noise-aware regression gate
(tools/perf_ledger.py, tools/perf_gate.py): row extraction for every
artifact shape the validator knows, provenance stamping, the
median/MAD gate verdicts, and the end-to-end acceptance path — ingest
the checked-in BENCH_rNN.json history, build a synthetic 3-run
baseline, and prove a seeded >=20% throughput drop exits nonzero while
an unchanged run exits 0."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tools(module):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        return __import__(module)
    finally:
        sys.path.pop(0)


perf_ledger = _tools("perf_ledger")
perf_gate = _tools("perf_gate")


# ---------------------------------------------------------------------------
# Row extraction per record shape
# ---------------------------------------------------------------------------

def test_rows_from_bench_summary_and_wrapper():
    summary = {"kind": "bench_summary", "status": "complete",
               "results": [
                   {"metric": "bert_tokens_per_sec", "value": 35440.8,
                    "unit": "tokens/s", "model": "bert"},
                   {"metric": "resnet_img_per_sec", "value": 0.0,
                    "unit": "img/s", "error": "backend unavailable"},
               ]}
    rows, skipped = perf_ledger.rows_from_record(summary)
    # the errored 0.0 result is SKIPPED, never a baseline sample
    assert len(rows) == 1 and skipped == 1
    assert rows[0]["config"] == "bert" \
        and rows[0]["metric"] == "bert_tokens_per_sec" \
        and rows[0]["value"] == 35440.8

    # driver wrapper: a parseable payload recurses...
    rows, skipped = perf_ledger.rows_from_record(
        {"cmd": "python bench.py", "parsed": summary})
    assert len(rows) == 1 and skipped == 1
    # ...a null payload (the r03/r05 timeout shape) is one skip
    rows, skipped = perf_ledger.rows_from_record(
        {"cmd": "python bench.py", "parsed": None})
    assert rows == [] and skipped == 1
    # ...and an errored payload likewise
    rows, skipped = perf_ledger.rows_from_record(
        {"cmd": "x", "parsed": {"error": "timeout"}})
    assert rows == [] and skipped == 1


def test_rows_from_loadgen_sharded_graphopt_memplan():
    gen = {"kind": "generation_loadgen", "mode": "closed",
           "tokens_per_s": 512.5, "throughput_rps": 20.0,
           "latency_ms": {"p50": 10.0, "p99": 30.0},
           "ttft_ms": {"p95": 12.0},
           "config": {"slots": 4, "max_prompt": 8}}
    rows, skipped = perf_ledger.rows_from_record(gen)
    assert skipped == 0
    by_metric = {r["metric"]: r for r in rows}
    assert set(by_metric) == {"tokens_per_s", "throughput_rps",
                              "latency_ms_p50", "latency_ms_p99",
                              "ttft_ms_p95"}
    # config key = mode + stable digest of the config object, so the
    # same invocation lines up across rounds...
    cfg = by_metric["tokens_per_s"]["config"]
    assert cfg.startswith("closed:")
    again, _ = perf_ledger.rows_from_record(gen)
    assert again[0]["config"] == cfg
    # ...and a different config object gets a different key
    other, _ = perf_ledger.rows_from_record(
        dict(gen, config={"slots": 8, "max_prompt": 8}))
    assert other[0]["config"] != cfg

    rows, _ = perf_ledger.rows_from_record(
        {"kind": "sharded_bench", "mesh_shape": [2, 1],
         "metric": "tok_s", "per_chip_throughput": 123.0})
    assert rows[0]["config"] == "mesh2x1" \
        and rows[0]["metric"] == "tok_s_per_chip"

    rows, _ = perf_ledger.rows_from_record(
        {"kind": "graph_opt", "model": "gpt", "opt_level": 2,
         "ops_after": 120, "vars_eliminated": 30})
    assert {r["metric"] for r in rows} == {"ops_after",
                                           "vars_eliminated"}
    assert all(r["config"] == "gpt:O2" for r in rows)

    rows, _ = perf_ledger.rows_from_record(
        {"kind": "memory_plan", "model": "bert",
         "est_peak_bytes": 1 << 30})
    assert rows[0]["metric"] == "est_peak_bytes" \
        and rows[0]["value"] == float(1 << 30)

    # unrelated kinds pass through silently (mixed monitor logs)
    assert perf_ledger.rows_from_record(
        {"kind": "stats_snapshot", "counters": {}}) == ([], 0)
    # non-numeric values never become rows
    rows, skipped = perf_ledger.rows_from_record(
        {"metric": "m", "value": "fast"})
    assert rows == [] and skipped == 1


def test_ingest_stamps_provenance_and_appends(tmp_path):
    art = tmp_path / "a.jsonl"
    with open(art, "w") as f:
        f.write(json.dumps({"metric": "tok_s", "value": 100.0,
                            "unit": "tok/s", "model": "gpt"}) + "\n")
        f.write("not json\n")   # tolerated: counted, not fatal
    ledger = tmp_path / "ledger.jsonl"
    n, skipped = perf_ledger.ingest(
        [str(art)], str(ledger),
        perf_ledger.provenance("abc1234", "tpu", "2x1"))
    assert n == 1 and skipped == 1
    rows = perf_ledger.load_rows(str(ledger))
    assert len(rows) == 1
    r = rows[0]
    assert r["git_rev"] == "abc1234" and r["platform"] == "tpu" \
        and r["mesh_shape"] == "2x1" and r["source"] == "a.jsonl"
    assert r["ingested_ts"] > 0
    # append-only: a second ingest adds, never rewrites
    perf_ledger.ingest([str(art)], str(ledger))
    assert len(perf_ledger.load_rows(str(ledger))) == 2


# ---------------------------------------------------------------------------
# Gate verdicts
# ---------------------------------------------------------------------------

def test_gate_direction_inference():
    assert not perf_gate.lower_is_better("bert_tokens_per_sec",
                                         "tokens/s")
    assert not perf_gate.lower_is_better("throughput_rps", "req/s")
    assert perf_gate.lower_is_better("latency_ms_p99", "ms")
    assert perf_gate.lower_is_better("ttft_ms_p95", "ms")
    assert perf_gate.lower_is_better("est_peak_bytes", "bytes")
    assert perf_gate.lower_is_better("ops_after", "ops")
    # vars_eliminated counts eliminations: more is better even though
    # the unit says "vars"
    assert not perf_gate.lower_is_better("vars_eliminated", "vars")


def test_gate_golden_fixtures_inline_and_cli():
    assert perf_gate.self_check() == 0
    p = subprocess.run([sys.executable, "tools/perf_gate.py",
                        "--self-check"], cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr


def test_gate_rows_groups_by_config_and_metric(tmp_path):
    ledger_rows = [
        {"kind": "ledger_row", "config": "bench", "metric": "tok_s",
         "value": v} for v in (100.0, 101.0, 99.0)
    ] + [
        {"kind": "ledger_row", "config": "other", "metric": "tok_s",
         "value": 5.0},
    ]
    res = perf_gate.gate_rows(
        [{"config": "bench", "metric": "tok_s", "value": 70.0,
          "unit": "tok/s"},
         {"config": "other", "metric": "tok_s", "value": 5.0},
         {"config": "fresh", "metric": "tok_s", "value": 5.0}],
        ledger_rows)
    by_cfg = {r["config"]: r for r in res}
    assert by_cfg["bench"]["status"] == "regression"
    assert by_cfg["other"]["status"] == "too_few_samples"
    assert by_cfg["fresh"]["status"] == "new_config"


# ---------------------------------------------------------------------------
# End-to-end acceptance: checked-in history -> ledger -> gate
# ---------------------------------------------------------------------------

def _run_gate(args, cwd=REPO):
    return subprocess.run(
        [sys.executable, "tools/perf_gate.py"] + args, cwd=cwd,
        capture_output=True, text=True, timeout=120)


def test_e2e_wrapper_history_gate(tmp_path):
    """Ingest five driver wrappers of which one carries a real number
    (the null/errored ones are skipped, not averaged), add two synthetic
    same-config runs to reach min-samples, then: a 25% lower candidate
    exits nonzero with a validated kind="perf_gate" report; the
    unchanged value exits 0; and metrics_report renders the section."""
    ledger = tmp_path / "perf_ledger.jsonl"
    result = {"metric": "bert_base_pretrain_tokens_per_sec_per_chip",
              "value": 35440.8, "unit": "tokens/s", "vs_baseline": 0.2543}
    wrappers = [
        {"rc": 1, "parsed": None},
        {"rc": 0, "parsed": result},
        {"rc": 124, "parsed": None},
        {"rc": 0, "parsed": dict(result, value=0.0,
                                 error="backend unavailable")},
        {"rc": 124, "parsed": None},
    ]
    paths = []
    for i, w in enumerate(wrappers):
        path = tmp_path / f"wrapper{i}.json"
        path.write_text(json.dumps(dict(w, cmd="python bench.py")))
        paths.append(str(path))
    n, skipped = perf_ledger.ingest(
        paths, str(ledger), perf_ledger.provenance("seed", "tpu", ""))
    assert n == 1 and skipped == 4
    row = perf_ledger.load_rows(str(ledger))[0]
    base_val = row["value"]
    assert base_val > 0 and row["platform"] == "tpu"

    # two more rounds of the same config (honest jitter) -> 3 samples
    for i, v in enumerate((base_val * 1.004, base_val * 0.997)):
        art = tmp_path / f"round{i}.json"
        art.write_text(json.dumps(
            {"metric": row["metric"], "value": v, "unit": row["unit"],
             "model": row["config"]}))
        perf_ledger.ingest([str(art)], str(ledger))
    assert len(perf_ledger.load_rows(str(ledger))) == 3

    gate_out = tmp_path / "gate.jsonl"
    # seeded regression: 25% below the median MUST fail the gate
    p = _run_gate(["--ledger", str(ledger), "--out", str(gate_out),
                   "--config", row["config"], "--metric", row["metric"],
                   "--value", str(base_val * 0.75), "--unit",
                   row["unit"]])
    assert p.returncode == 1, p.stdout + p.stderr
    assert "regression" in p.stdout

    # unchanged run: exits 0, verdict ok
    p = _run_gate(["--ledger", str(ledger), "--out", str(gate_out),
                   "--config", row["config"], "--metric", row["metric"],
                   "--value", str(base_val), "--unit", row["unit"]])
    assert p.returncode == 0, p.stdout + p.stderr
    assert " ok" in p.stdout

    # both reports validate against the schema
    validate = _tools("validate_bench_json")
    assert validate.validate_file(str(gate_out)) == []
    reports = [json.loads(ln) for ln in gate_out.read_text()
               .splitlines() if ln.strip()]
    assert len(reports) == 2
    assert reports[0]["regressions"] == 1 \
        and reports[1]["regressions"] == 0
    for rep in reports:
        assert validate.validate_perf_gate(rep, "gate.jsonl") == []

    # metrics_report renders the perf-gate section from the same log
    p = subprocess.run(
        [sys.executable, "tools/metrics_report.py", str(gate_out)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stdout + p.stderr
    assert "perf gate" in p.stdout and "regression" in p.stdout


def test_gate_ingest_makes_todays_run_tomorrows_baseline(tmp_path):
    ledger = tmp_path / "ledger.jsonl"
    for v in (100.0, 101.0, 99.5):
        perf_ledger.append_rows(
            str(ledger),
            [{"kind": "ledger_row", "record_kind": "bench_result",
              "config": "bench", "metric": "tok_s", "value": v,
              "unit": "tok/s"}],
            perf_ledger.provenance("r0", "tpu", ""))
    art = tmp_path / "new.json"
    art.write_text(json.dumps({"metric": "tok_s", "value": 100.5,
                               "unit": "tok/s", "model": "bench"}))
    p = _run_gate(["--ledger", str(ledger), "--ingest", str(art)])
    assert p.returncode == 0, p.stdout + p.stderr
    # --ingest appended the candidate AFTER gating
    assert len(perf_ledger.load_rows(str(ledger))) == 4


def test_incident_bundle_whole_file_validates(tmp_path):
    """validate_file auto-detects a whole-file incident bundle (the
    shape monitor_alerts writes)."""
    validate = _tools("validate_bench_json")
    bundle = {"kind": "incident_bundle", "ts": 123.0, "pid": 1,
              "rule": {"name": "slo", "kind": "burn",
                       "expr": "x", "op": ">", "threshold": 100.0},
              "state": "firing", "value": 400.0,
              "windows": {"10s": {"p": 400.0, "covered": True,
                                  "breach": True}},
              "snapshot": {"counters": {}, "gauges": {},
                           "histograms": {}},
              "exemplar_trace_ids": ["aabb"],
              "spans": [{"trace_id": "aabb", "span_id": "cc",
                         "name": "request"}],
              "n_spans_dropped": 0,
              "flight_records": []}
    f = tmp_path / "incident_slo_123.json"
    f.write_text(json.dumps(bundle))
    assert validate.validate_file(str(f)) == []
    # a mangled one (missing snapshot) is rejected
    bad = dict(bundle)
    del bad["snapshot"]
    f2 = tmp_path / "incident_bad.json"
    f2.write_text(json.dumps(bad))
    assert validate.validate_file(str(f2)) != []
