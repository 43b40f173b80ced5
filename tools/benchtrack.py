"""benchtrack: BENCH_r*.json trajectory table + regression gates.

The driver commits one ``BENCH_r<N>.json`` per bench round in the shape
``{"n", "cmd", "rc", "tail", "parsed"}`` where ``tail`` carries the
bench's emitted JSON lines (one record per metric, ``parsed`` = the
final record). Before this module the history was write-only: nothing
read the trajectory back, rendered it, or gated a new run against it.

Two halves:

- **Trajectory** — :func:`load_rounds` parses every round file in a
  directory, :func:`trajectory` pivots them per metric, and
  :func:`render_markdown` emits the per-round trajectory table.
- **Regression gates** — :func:`compare_records` holds a current run's
  records against a baseline round: step-time, throughput, MFU,
  compile/trace counts and updater-state bytes. Noise handling follows
  the PR-11 min-over-rounds doctrine: the bench already reports
  median/p10 over >=6 timed chunks, and host-load noise only INFLATES a
  time — so the gate takes the CURRENT run's best (min of median and
  p10) against the BASELINE median plus tolerance. A noisy-but-flat run
  passes; a real regression (every chunk slower) fails. Records whose
  platform differs from the baseline's are SKIPPED with a note, never
  failed — a CPU round against a TPU baseline is not a regression
  signal. ``bench.py --compare-to <round.json>`` wires this in and
  exits non-zero on any violation.

CLI::

    python -m tools.benchtrack [--dir .] [--markdown] [--metrics a,b]
    python -m tools.benchtrack --compare BENCH_r05.json current.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")

# default gate tolerances (fractions)
STEP_TIME_TOL = 0.10
THROUGHPUT_TOL = 0.10
MFU_TOL = 0.10
STATE_BYTES_TOL = 0.05


def _records_from_lines(text: str) -> List[Dict[str, Any]]:
    out = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "metric" in rec:
            out.append(rec)
    return out


def parse_round(path: str) -> Dict[str, Any]:
    """One round file -> {round, path, rc, records: {metric: record}}.
    Accepts the driver round shape ({n, cmd, rc, tail, parsed}), a bare
    bench record ({"metric": ...}), or a file of bench JSON lines."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    records: Dict[str, Dict[str, Any]] = {}
    n: Optional[int] = None
    rc: Optional[int] = None
    try:
        blob = json.loads(text)
    except ValueError:
        blob = None
    if isinstance(blob, dict) and "tail" in blob:
        n = blob.get("n")
        rc = blob.get("rc")
        for rec in _records_from_lines(blob.get("tail", "")):
            records[rec["metric"]] = rec     # last wins (tail truncation)
        parsed = blob.get("parsed")
        if isinstance(parsed, dict) and "metric" in parsed:
            records[parsed["metric"]] = parsed
    elif isinstance(blob, dict) and "metric" in blob:
        records[blob["metric"]] = blob
    else:
        for rec in _records_from_lines(text):
            records[rec["metric"]] = rec
    if n is None:
        m = _ROUND_RE.search(os.path.basename(path))
        if m:
            n = int(m.group(1))
    return {"round": n, "path": path, "rc": rc, "records": records}


def load_rounds(dirpath: str = ".") -> List[Dict[str, Any]]:
    """Every BENCH_r*.json under ``dirpath``, sorted by round number."""
    paths = sorted(glob.glob(os.path.join(dirpath, "BENCH_r*.json")))
    rounds = [parse_round(p) for p in paths]
    return sorted(rounds, key=lambda r: (r["round"] is None, r["round"]))


def trajectory(rounds: List[Dict[str, Any]],
               metrics: Optional[List[str]] = None
               ) -> Dict[str, List[Tuple[Optional[int], Dict[str, Any]]]]:
    """Pivot rounds per metric: {metric: [(round_n, record), ...]}."""
    out: Dict[str, List[Tuple[Optional[int], Dict[str, Any]]]] = {}
    for rnd in rounds:
        for metric, rec in sorted(rnd["records"].items()):
            if metrics is not None and metric not in metrics:
                continue
            out.setdefault(metric, []).append((rnd["round"], rec))
    return out


def _fmt(v: Any, nd: int = 2) -> str:
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:,.{nd}f}".rstrip("0").rstrip(".") or "0"
    if isinstance(v, int):
        return f"{v:,}"
    return str(v)


def render_markdown(rounds: List[Dict[str, Any]],
                    metrics: Optional[List[str]] = None) -> str:
    """The trajectory table: one section per metric, one row
    per round, carrying the roofline-relevant columns."""
    traj = trajectory(rounds, metrics)
    lines: List[str] = []
    for metric, rows in sorted(traj.items()):
        lines.append(f"### `{metric}`")
        lines.append("")
        lines.append("| round | value | unit | step ms (med) | MFU | "
                     "platform | batch |")
        lines.append("|---|---|---|---|---|---|---|")
        for n, rec in rows:
            lines.append(
                "| r{:02d} | {} | {} | {} | {} | {} | {} |".format(
                    n if n is not None else 0,
                    _fmt(rec.get("value")), rec.get("unit", "?"),
                    _fmt(rec.get("step_ms_median"), 3),
                    _fmt(rec.get("mfu_vs_bf16_peak"), 4),
                    rec.get("platform", "?"),
                    _fmt(rec.get("batch"))))
        lines.append("")
    return "\n".join(lines)


def _state_bytes_total(v: Any) -> Optional[float]:
    if isinstance(v, (int, float)):
        return float(v)
    if isinstance(v, dict):
        if "total" in v:
            return float(v["total"])
        vals = [x for x in v.values() if isinstance(x, (int, float))]
        return float(sum(vals)) if vals else None
    return None


def compare_records(baseline: Dict[str, Dict[str, Any]],
                    current: Dict[str, Dict[str, Any]],
                    step_time_tol: float = STEP_TIME_TOL,
                    throughput_tol: float = THROUGHPUT_TOL,
                    mfu_tol: float = MFU_TOL,
                    state_bytes_tol: float = STATE_BYTES_TOL
                    ) -> Dict[str, List[str]]:
    """Gate ``current`` records against ``baseline`` records (both keyed
    by metric). Returns {"violations": [...], "skipped": [...],
    "compared": [...]} — empty ``violations`` means the gate passes.

    Gates per shared metric (missing fields skip that gate, they never
    fail it):

    - **step time**: current best (min of ``step_ms_median`` and
      ``step_ms_p10`` — the noise-aware bound) must be <= baseline
      median * (1 + step_time_tol);
    - **throughput**: current ``value`` >= baseline * (1 -
      throughput_tol), only when unit AND batch match (value scales
      with batch);
    - **MFU**: current ``mfu_vs_bf16_peak`` >= baseline * (1 - mfu_tol);
    - **compile counts**: no ``traces`` counter may EXCEED its baseline
      (new compiles in a steady config are the retrace bug class);
    - **state bytes**: ``updater_state_bytes`` total <= baseline *
      (1 + state_bytes_tol) (the bf16-state win must not silently
      regress).
    """
    violations: List[str] = []
    skipped: List[str] = []
    compared: List[str] = []
    if not baseline:
        # an empty baseline round (e.g. a smoke config that emitted no
        # records, or a truncated file) is NOT a pass-by-vacuity worth
        # silence: say so, gate nothing, exit clean
        skipped.append("baseline round carries no records — nothing to "
                       "compare, skipping the regression gate")
        return {"violations": violations, "skipped": skipped,
                "compared": compared}
    for metric, base in sorted(baseline.items()):
        cur = current.get(metric)
        if cur is None:
            skipped.append(f"{metric}: not in current run")
            continue
        if base.get("platform") != cur.get("platform"):
            skipped.append(
                f"{metric}: platform changed "
                f"({base.get('platform')} -> {cur.get('platform')}) — "
                "cross-platform comparison is not a regression signal")
            continue
        compared.append(metric)
        b_med = base.get("step_ms_median")
        c_med = cur.get("step_ms_median")
        if b_med and c_med:
            c_best = min(x for x in (c_med, cur.get("step_ms_p10"))
                         if x)
            if c_best > b_med * (1.0 + step_time_tol):
                violations.append(
                    f"{metric}: step time regressed — current best "
                    f"{c_best:.3f} ms > baseline {b_med:.3f} ms "
                    f"+{step_time_tol:.0%}")
        if base.get("unit") == cur.get("unit") \
                and base.get("batch") == cur.get("batch") \
                and base.get("value") and cur.get("value") is not None:
            if cur["value"] < base["value"] * (1.0 - throughput_tol):
                violations.append(
                    f"{metric}: throughput regressed — "
                    f"{cur['value']:.2f} {cur.get('unit')} < baseline "
                    f"{base['value']:.2f} -{throughput_tol:.0%}")
        b_mfu = base.get("mfu_vs_bf16_peak")
        c_mfu = cur.get("mfu_vs_bf16_peak")
        if b_mfu and c_mfu is not None:
            if c_mfu < b_mfu * (1.0 - mfu_tol):
                violations.append(
                    f"{metric}: MFU regressed — {c_mfu:.4f} < baseline "
                    f"{b_mfu:.4f} -{mfu_tol:.0%}")
        b_tr = base.get("traces")
        c_tr = cur.get("traces")
        if isinstance(b_tr, dict) and isinstance(c_tr, dict):
            for name, c_n in sorted(c_tr.items()):
                b_n = b_tr.get(name, 0)
                if isinstance(c_n, (int, float)) and c_n > b_n:
                    violations.append(
                        f"{metric}: compile count grew — {name} "
                        f"{c_n} > baseline {b_n}")
        b_sb = _state_bytes_total(base.get("updater_state_bytes"))
        c_sb = _state_bytes_total(cur.get("updater_state_bytes"))
        if b_sb and c_sb is not None:
            if c_sb > b_sb * (1.0 + state_bytes_tol):
                violations.append(
                    f"{metric}: updater-state bytes grew — {c_sb:.0f} > "
                    f"baseline {b_sb:.0f} +{state_bytes_tol:.0%}")
    return {"violations": violations, "skipped": skipped,
            "compared": compared}


def compare_files(baseline_path: str,
                  current_path: str, **tols) -> Dict[str, List[str]]:
    base = parse_round(baseline_path)
    cur = parse_round(current_path)
    return compare_records(base["records"], cur["records"], **tols)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="BENCH_r*.json trajectory and regression gates")
    ap.add_argument("--dir", default=".",
                    help="directory holding BENCH_r*.json rounds")
    ap.add_argument("--metrics", default=None,
                    help="comma-separated metric filter")
    ap.add_argument("--markdown", action="store_true",
                    help="emit the markdown trajectory table")
    ap.add_argument("--compare", nargs=2,
                    metavar=("BASELINE", "CURRENT"),
                    help="gate CURRENT records against BASELINE; exit 1 "
                         "on any violation")
    args = ap.parse_args(argv)
    metrics = args.metrics.split(",") if args.metrics else None

    if args.compare:
        result = compare_files(*args.compare)
        print(json.dumps(result, indent=2))
        return 1 if result["violations"] else 0

    rounds = load_rounds(args.dir)
    if not rounds:
        print(f"no BENCH_r*.json rounds under {args.dir}", file=sys.stderr)
        return 2
    if args.markdown:
        print(render_markdown(rounds, metrics))
    else:
        traj = trajectory(rounds, metrics)
        for metric, rows in sorted(traj.items()):
            print(metric)
            for n, rec in rows:
                print(f"  r{n:02d}: {_fmt(rec.get('value'))} "
                      f"{rec.get('unit', '?')}  "
                      f"step {_fmt(rec.get('step_ms_median'), 3)} ms  "
                      f"mfu {_fmt(rec.get('mfu_vs_bf16_peak'), 4)}  "
                      f"[{rec.get('platform', '?')}]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
