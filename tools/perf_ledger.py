"""Append-only longitudinal perf ledger over every bench artifact.

Usage:
    python tools/perf_ledger.py ingest --ledger LEDGER.jsonl \
        [--git-rev REV] [--platform P] [--mesh M] FILE [FILE ...]
    python tools/perf_ledger.py show --ledger LEDGER.jsonl \
        [--config C] [--metric M]

The ledger is the history DB behind tools/perf_gate.py: one
`kind="ledger_row"` JSONL line per (config, metric) measurement, with
run provenance (git rev, platform, mesh shape) stamped at ingest so a
regression can be bisected to a commit instead of "some round lost
tok/s". Ingest understands every record shape
tools/validate_bench_json.py knows:

* bench_summary files / bench-log result lines (metric/value/unit)
* driver BENCH_rNN.json wrappers ({"parsed": ...} — a null or errored
  parsed payload is SKIPPED and counted, the r03/r05 failure mode)
* kind="sharded_bench" (per-chip throughput keyed by mesh shape, plus
  the per-op predicted collective bytes/step and — when the record
  carries the closed-form grad_sync_bytes_per_step — the predicted/
  closed-form drift ratio, so perf_gate flags a cost-model drift the
  same way it flags a tok/s loss)
* kind="sharding_report" (program_lint --sharding: predicted
  collective/reshard bytes per step keyed by model + mesh)
* serving/generation/chaos/router loadgen records (throughput, p99
  latency, tokens/s — config keyed by mode + a stable digest of the
  run's config object)
* kind="graph_opt" (ops_after / vars_eliminated per model+opt level)
* kind="memory_plan" (est_peak_bytes per model)

Anything else (stats snapshots, spans, flight records on a mixed log)
is ignored. Rows are append-only and fsynced — the same crash-safety
contract as the monitor's JSONL exporter. Importable API:
`rows_from_record`, `rows_from_file`, `ingest`, `load_rows`, plus the
provenance helpers `detect_git_rev` / `detect_platform` /
`detect_mesh` that bench.py stamps rows with.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import time
from typing import Dict, List, Optional, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _stat_add(name: str, value=1):
    """Record a ledger.* stat IF the paddle_tpu monitor is already
    imported in this process (bench.py auto-ingest, tests). A bare CLI
    run never pays the package import for a counter."""
    mon = sys.modules.get("paddle_tpu.monitor")
    if mon is not None:
        try:
            mon.STAT_ADD(name, value)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# Provenance
# ---------------------------------------------------------------------------

def detect_git_rev() -> str:
    rev = os.environ.get("GIT_REV")
    if rev:
        return rev
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO,
            capture_output=True, text=True, timeout=10)
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.strip()
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def detect_platform() -> str:
    p = os.environ.get("BENCH_PLATFORM") \
        or os.environ.get("JAX_PLATFORMS")
    if p:
        return p.split(",")[0]
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return jax.default_backend()
        except Exception:
            pass
    return "unknown"


def detect_mesh() -> str:
    return os.environ.get("BENCH_MESH") \
        or os.environ.get("FLAGS_sharded_mesh") or ""


def provenance(git_rev: Optional[str] = None,
               platform: Optional[str] = None,
               mesh_shape: Optional[str] = None) -> Dict[str, str]:
    return {"git_rev": git_rev or detect_git_rev(),
            "platform": platform or detect_platform(),
            "mesh_shape": detect_mesh() if mesh_shape is None
            else mesh_shape}


# ---------------------------------------------------------------------------
# Row extraction
# ---------------------------------------------------------------------------

def _config_digest(cfg: dict) -> str:
    """Stable short key for a loadgen config object, so 'the same
    loadgen invocation' lines up across rounds without carrying the
    whole dict in every row."""
    blob = json.dumps(cfg, sort_keys=True, default=str)
    return hashlib.md5(blob.encode()).hexdigest()[:8]


def _row(record_kind, config, metric, value, unit, ts=None, extra=None):
    if not isinstance(value, (int, float)) or isinstance(value, bool):
        return None
    r = {"kind": "ledger_row", "record_kind": record_kind,
         "config": str(config), "metric": str(metric),
         "value": float(value), "unit": str(unit or "")}
    if ts is not None:
        r["ts"] = ts
    if extra:
        r["extra"] = extra
    return r


def _bench_result_rows(rec) -> List[dict]:
    # an errored config (backend unavailable, crash, budget skip) must
    # never be averaged into a baseline — a 0.0 tok/s error line would
    # poison the median
    if rec.get("error"):
        return []
    row = _row("bench_result", rec.get("model") or "bench",
               rec.get("metric"), rec.get("value"), rec.get("unit"),
               ts=rec.get("ts"))
    return [row] if row else []


def _loadgen_rows(rec) -> List[dict]:
    kind = rec.get("kind")
    cfg = rec.get("config") if isinstance(rec.get("config"), dict) \
        else {}
    config = f"{rec.get('mode', kind)}:{_config_digest(cfg)}"
    rows = []
    for metric, unit in (("throughput_rps", "req/s"),
                         ("tokens_per_s", "tok/s")):
        if metric in rec:
            r = _row(kind, config, metric, rec.get(metric), unit,
                     ts=rec.get("ts"))
            if r:
                rows.append(r)
    lat = rec.get("latency_ms")
    if isinstance(lat, dict):
        for q in ("p50", "p99"):
            r = _row(kind, config, f"latency_ms_{q}", lat.get(q), "ms",
                     ts=rec.get("ts"))
            if r:
                rows.append(r)
    ttft = rec.get("ttft_ms")
    if isinstance(ttft, dict):
        r = _row(kind, config, "ttft_ms_p95", ttft.get("p95"), "ms",
                 ts=rec.get("ts"))
        if r:
            rows.append(r)
    if kind == "disagg_loadgen":
        # the disagg headline: shared-cohort TTFT p99 and its ratio vs
        # the same-run symmetric baseline (< 1.0 = disagg winning)
        shared = rec.get("ttft_shared_ms")
        if isinstance(shared, dict):
            r = _row(kind, config, "ttft_shared_ms_p99",
                     shared.get("p99"), "ms", ts=rec.get("ts"))
            if r:
                rows.append(r)
        r = _row(kind, config, "ttft_shared_p99_ratio",
                 rec.get("ttft_shared_p99_ratio"), "x",
                 ts=rec.get("ts"))
        if r:
            rows.append(r)
    return rows


def _spec_loadgen_rows(rec) -> List[dict]:
    """Rows for one speculative-decoding A/B record: the speedup (the
    headline the regression gate should watch), the acceptance rate
    (the drafter-quality canary — a drafter regression shows here
    before it shows in wall clock), and both sides' tokens/s."""
    cfg = rec.get("config") if isinstance(rec.get("config"), dict) \
        else {}
    config = f"spec:{_config_digest(cfg)}"
    rows = []
    r = _row("spec_loadgen", config, "speedup", rec.get("speedup"),
             "x", ts=rec.get("ts"))
    if r:
        rows.append(r)
    spec = rec.get("spec") if isinstance(rec.get("spec"), dict) else {}
    base = rec.get("baseline") \
        if isinstance(rec.get("baseline"), dict) else {}
    for metric, src, key, unit in (
            ("acceptance_rate", spec, "acceptance_rate", "frac"),
            ("spec_tokens_per_s", spec, "tokens_per_s", "tok/s"),
            ("baseline_tokens_per_s", base, "tokens_per_s", "tok/s")):
        r = _row("spec_loadgen", config, metric, src.get(key), unit,
                 ts=rec.get("ts"))
        if r:
            rows.append(r)
    return rows


def rows_from_record(rec) -> Tuple[List[dict], int]:
    """(ledger rows, skipped count) for ONE parsed record/object."""
    if not isinstance(rec, dict):
        return [], 1
    kind = rec.get("kind")
    # driver wrapper: recurse into parsed; null/errored payloads are
    # exactly what the gate must NOT silently average into a baseline
    if kind is None and "parsed" in rec and "cmd" in rec:
        parsed = rec.get("parsed")
        if not isinstance(parsed, dict) or parsed.get("error"):
            return [], 1
        rows, skipped = rows_from_record(parsed)
        return rows, skipped
    if kind == "bench_summary":
        rows, skipped = [], 0
        for r in rec.get("results") or []:
            rr, sk = rows_from_record(
                dict(r, kind=None) if isinstance(r, dict) else r)
            rows.extend(rr)
            skipped += sk
        return rows, skipped
    if kind == "sharded_bench":
        shape = rec.get("mesh_shape") or []
        config = "mesh" + "x".join(str(d) for d in shape)
        rows = []
        row = _row("sharded_bench", config,
                   f"{rec.get('metric', 'throughput')}_per_chip",
                   rec.get("per_chip_throughput"), "per-chip",
                   ts=rec.get("ts"))
        if row:
            rows.append(row)
        coll = rec.get("collective_bytes_per_step")
        r = _row("sharded_bench", config, "collective_bytes_per_step",
                 coll, "bytes", ts=rec.get("ts"))
        if r:
            rows.append(r)
        # drift canary: per-op analyzer prediction over the closed-form
        # gradient-sync bytes — a rule change that silently re-prices
        # the model moves this ratio before anything moves tok/s
        gs = rec.get("grad_sync_bytes_per_step")
        if isinstance(coll, (int, float)) \
                and isinstance(gs, (int, float)) and gs > 0:
            r = _row("sharded_bench", config,
                     "collective_vs_grad_sync_ratio", coll / gs, "x",
                     ts=rec.get("ts"))
            if r:
                rows.append(r)
        return rows, (0 if rows else 1)
    if kind == "sharding_report":
        shape = rec.get("mesh_shape") or []
        config = (f"{rec.get('model') or rec.get('fingerprint', '?')}"
                  f":mesh" + "x".join(str(d) for d in shape))
        rows = []
        for metric in ("collective_bytes_per_step",
                       "reshard_bytes_per_step", "grad_sync_bytes"):
            r = _row("sharding_report", config, metric, rec.get(metric),
                     "bytes", ts=rec.get("ts"))
            if r:
                rows.append(r)
        return rows, (0 if rows else 1)
    if kind in ("serving_loadgen", "generation_loadgen",
                "chaos_loadgen", "router_loadgen", "disagg_loadgen"):
        rows = _loadgen_rows(rec)
        return rows, (0 if rows else 1)
    if kind == "spec_loadgen":
        rows = _spec_loadgen_rows(rec)
        return rows, (0 if rows else 1)
    if kind == "graph_opt":
        config = f"{rec.get('model', '?')}:O{rec.get('opt_level', 0)}"
        rows = []
        for metric, unit in (("ops_after", "ops"),
                             ("vars_eliminated", "vars")):
            r = _row("graph_opt", config, metric, rec.get(metric), unit,
                     ts=rec.get("ts"))
            if r:
                rows.append(r)
        return rows, (0 if rows else 1)
    if kind == "memory_plan":
        row = _row("memory_plan", rec.get("model") or "?",
                   "est_peak_bytes", rec.get("est_peak_bytes"),
                   "bytes", ts=rec.get("ts"))
        return ([row] if row else []), (0 if row else 1)
    if kind == "op_profile":
        model = rec.get("model") or "?"
        rows = []
        for r in rec.get("rows") or []:
            if not isinstance(r, dict) or not r.get("op"):
                continue
            row = _row("op_profile", f"{model}:{r['op']}", "avg_ms",
                       r.get("avg_ms"), "ms", ts=rec.get("ts"))
            if row:
                rows.append(row)
        return rows, (0 if rows else 1)
    if kind == "goodput_report":
        config = rec.get("config") or rec.get("label") or "goodput"
        cats = rec.get("categories") if isinstance(
            rec.get("categories"), dict) else {}
        rows = []
        # goodput_frac gates higher-is-better ("frac" unit hint in
        # perf_gate.lower_is_better); input_wait_s gates lower-is-better
        for metric, value, unit in (
                ("goodput_frac", rec.get("goodput_frac"), "frac"),
                ("input_wait_s", cats.get("input_wait"), "s")):
            r = _row("goodput_report", config, metric, value, unit,
                     ts=rec.get("ts"))
            if r:
                rows.append(r)
        return rows, (0 if rows else 1)
    if kind is None and "metric" in rec and "value" in rec:
        rows = _bench_result_rows(rec)
        return rows, (0 if rows else 1)
    return [], 0  # unrelated record kinds pass through silently


def rows_from_file(path: str) -> Tuple[List[dict], int]:
    """Rows + skipped count from one artifact (whole-file JSON or
    JSONL, auto-detected like validate_bench_json.validate_file)."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return [], 1
    if not text.strip():
        return [], 1
    rows: List[dict] = []
    skipped = 0
    try:
        objs = [json.loads(text)]
    except json.JSONDecodeError:
        objs = []
        for line in text.splitlines():
            line = line.strip()
            if not line:
                continue
            try:
                objs.append(json.loads(line))
            except json.JSONDecodeError:
                skipped += 1
    for obj in objs:
        rr, sk = rows_from_record(obj)
        rows.extend(rr)
        skipped += sk
    for r in rows:
        r["source"] = os.path.basename(path)
    return rows, skipped


# ---------------------------------------------------------------------------
# Ledger I/O
# ---------------------------------------------------------------------------

def append_rows(ledger: str, rows: List[dict],
                prov: Optional[Dict[str, str]] = None) -> int:
    if not rows:
        return 0
    prov = prov or provenance()
    d = os.path.dirname(os.path.abspath(ledger))
    os.makedirs(d, exist_ok=True)
    now = time.time()
    with open(ledger, "a") as f:
        for r in rows:
            out = dict(r)
            out.setdefault("ts", now)
            out["ingested_ts"] = now
            for k, v in prov.items():
                out.setdefault(k, v)
            f.write(json.dumps(out) + "\n")
        f.flush()
        os.fsync(f.fileno())
    return len(rows)


def ingest(paths, ledger: str,
           prov: Optional[Dict[str, str]] = None) -> Tuple[int, int]:
    """Ingest artifacts into the ledger. Returns (rows, skipped)."""
    all_rows: List[dict] = []
    skipped = 0
    for path in paths:
        rows, sk = rows_from_file(path)
        all_rows.extend(rows)
        skipped += sk
    n = append_rows(ledger, all_rows, prov)
    _stat_add("ledger.rows_ingested", n)
    if skipped:
        _stat_add("ledger.rows_skipped", skipped)
    return n, skipped


def load_rows(ledger: str) -> List[dict]:
    """Every ledger_row in the ledger, file order (= ingest order)."""
    rows: List[dict] = []
    try:
        with open(ledger) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) \
                        and rec.get("kind") == "ledger_row":
                    rows.append(rec)
    except OSError:
        pass
    return rows


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    ing = sub.add_parser("ingest", help="ingest artifacts")
    ing.add_argument("files", nargs="+")
    ing.add_argument("--ledger", required=True)
    ing.add_argument("--git-rev", default=None)
    ing.add_argument("--platform", default=None)
    ing.add_argument("--mesh", default=None)
    show = sub.add_parser("show", help="dump ledger rows")
    show.add_argument("--ledger", required=True)
    show.add_argument("--config", default=None)
    show.add_argument("--metric", default=None)
    args = ap.parse_args(argv)

    if args.cmd == "ingest":
        prov = provenance(args.git_rev, args.platform, args.mesh)
        n, skipped = ingest(args.files, args.ledger, prov)
        print(json.dumps({"kind": "ledger_ingest", "rows": n,
                          "skipped": skipped, "ledger": args.ledger,
                          **prov}))
        return 0
    rows = load_rows(args.ledger)
    for r in rows:
        if args.config and r.get("config") != args.config:
            continue
        if args.metric and r.get("metric") != args.metric:
            continue
        print(json.dumps(r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
