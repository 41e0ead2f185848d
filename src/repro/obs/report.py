"""Telemetry report CLI: ``python -m repro.obs.report``.

Stdlib-only (runs on the nojax CI leg) because the JSONL already carries
computed values -- exact percentiles, bucket counts, final gauges -- so
reporting is pure formatting:

    python -m repro.obs.report summarize run.jsonl [--top N]
    python -m repro.obs.report diff a.jsonl b.jsonl

``summarize`` prints counter/gauge tables, histogram percentile tables,
the top-k hot nodes by DES utilization, and the span tree (with wall
timings and derived swaps/s when the export included wall fields).
``diff`` aligns two runs on ``(kind, name, labels)`` and prints value
deltas plus added/removed metrics -- byte-identical runs diff empty.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple


def load(path: str) -> List[dict]:
    records = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                records.append(json.loads(line))
    return records


def _label_str(labels: Dict[str, object]) -> str:
    if not labels:
        return ""
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return "{" + inner + "}"


def _ident(rec: dict) -> str:
    return f"{rec['name']}{_label_str(rec.get('labels', {}))}"


def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def _by_kind(records: List[dict]) -> Dict[str, List[dict]]:
    out: Dict[str, List[dict]] = {}
    for rec in records:
        out.setdefault(rec.get("kind", "?"), []).append(rec)
    return out


def _span_depth(rec: dict, by_seq: Dict[int, dict]) -> int:
    depth = 0
    parent = rec.get("parent")
    while parent is not None and parent in by_seq:
        depth += 1
        parent = by_seq[parent].get("parent")
    return depth


def summarize(path: str, top: int = 5, out=None) -> None:
    out = sys.stdout if out is None else out  # resolve at call, not import
    records = load(path)
    kinds = _by_kind(records)
    print(f"# {path}: {len(records)} records", file=out)

    for kind in ("counter", "gauge"):
        recs = kinds.get(kind, [])
        if recs:
            print(f"\n## {kind}s ({len(recs)})", file=out)
            for rec in recs:
                print(f"  {_ident(rec)} = {_fmt(rec.get('value'))}", file=out)

    hists = kinds.get("histogram", [])
    if hists:
        print(f"\n## histograms ({len(hists)})", file=out)
        print("  name count mean p50 p95 p99", file=out)
        for rec in hists:
            cells = " ".join(
                _fmt(rec.get(c)) for c in ("count", "mean", "p50", "p95", "p99")
            )
            print(f"  {_ident(rec)} {cells}", file=out)

    series = kinds.get("series", [])
    if series:
        print(f"\n## series ({len(series)})", file=out)
        for rec in series:
            pts = rec.get("points", [])
            last = _fmt(pts[-1][1]) if pts else "-"
            print(f"  {_ident(rec)}: {len(pts)} points, last={last}", file=out)

    # Top-k hot nodes: final DES per-node utilization gauges, hottest first.
    utils = [
        rec
        for rec in kinds.get("gauge", [])
        if rec["name"] == "des.node_utilization" and rec.get("value") is not None
    ]
    if utils:
        utils.sort(key=lambda rec: (-rec["value"], _ident(rec)))
        print(f"\n## top-{top} hot nodes", file=out)
        for rec in utils[:top]:
            print(f"  {_ident(rec)} util={_fmt(rec['value'])}", file=out)

    spans = kinds.get("span", [])
    if spans:
        print(f"\n## spans ({len(spans)})", file=out)
        by_seq = {rec["seq"]: rec for rec in spans}
        # The annealer's wait on its scan, by the anneal span's seq.
        scan_wall = {
            rec["parent"]: rec["wall_s"]
            for rec in spans
            if rec["name"] == "device.wait"
            and rec.get("labels", {}).get("what") == "anneal"
            and rec.get("wall_s") is not None
        }
        for rec in spans:
            indent = "  " * _span_depth(rec, by_seq)
            meta = rec.get("meta", {})
            parts = [f"{indent}[{rec['seq']}] {_ident(rec)}"]
            if meta:
                parts.append(
                    " ".join(f"{k}={_fmt(meta[k])}" for k in sorted(meta))
                )
            wall = rec.get("wall_s")
            if wall is not None:
                parts.append(f"wall={wall * 1e3:.2f}ms")
                # swaps/s: the annealer span carries its proposal count; the
                # scan's time is its device.wait child where it has one (the
                # span's own wall adds dispatch and fetch).
                scan = scan_wall.get(rec["seq"], wall)
                if isinstance(meta.get("proposals"), (int, float)) and scan > 0:
                    parts.append(f"swaps_per_s={meta['proposals'] / scan:.3g}")
            print("  " + " ".join(parts), file=out)


def _scalar_fields(rec: dict) -> Dict[str, object]:
    kind = rec.get("kind")
    if kind in ("counter", "gauge"):
        return {"value": rec.get("value")}
    if kind == "histogram":
        return {c: rec.get(c) for c in ("count", "mean", "p50", "p95", "p99")}
    if kind == "series":
        pts = rec.get("points", [])
        return {"n_points": len(pts), "last": pts[-1][1] if pts else None}
    return {}


def diff(path_a: str, path_b: str, out=None) -> int:
    """Print per-metric deltas; return the number of differing records."""
    out = sys.stdout if out is None else out  # resolve at call, not import

    def index(path: str) -> Dict[Tuple[str, str, str], dict]:
        out_idx = {}
        for rec in load(path):
            if rec.get("kind") == "span":
                key = ("span", str(rec.get("seq")), rec.get("name", ""))
            else:
                key = (
                    rec.get("kind", "?"),
                    rec.get("name", ""),
                    json.dumps(rec.get("labels", {}), sort_keys=True),
                )
            out_idx[key] = rec
        return out_idx

    a, b = index(path_a), index(path_b)
    n_diff = 0
    for key in sorted(set(a) | set(b), key=str):
        ra, rb = a.get(key), b.get(key)
        if ra is None:
            print(f"+ only in {path_b}: {_ident(rb)} ({rb['kind']})", file=out)
            n_diff += 1
            continue
        if rb is None:
            print(f"- only in {path_a}: {_ident(ra)} ({ra['kind']})", file=out)
            n_diff += 1
            continue
        if ra.get("kind") == "span":
            if ra.get("meta") != rb.get("meta") or ra.get("parent") != rb.get("parent"):
                print(f"~ span [{ra['seq']}] {_ident(ra)}: meta/parent differ", file=out)
                n_diff += 1
            continue
        fa, fb = _scalar_fields(ra), _scalar_fields(rb)
        changed = {c for c in fa if fa[c] != fb.get(c)}
        if changed:
            n_diff += 1
            deltas = []
            for c in sorted(changed):
                va, vb = fa[c], fb.get(c)
                if isinstance(va, (int, float)) and isinstance(vb, (int, float)):
                    deltas.append(f"{c}: {_fmt(va)} -> {_fmt(vb)} ({vb - va:+.6g})")
                else:
                    deltas.append(f"{c}: {_fmt(va)} -> {_fmt(vb)}")
            print(f"~ {ra['kind']} {_ident(ra)}: " + "; ".join(deltas), file=out)
    if n_diff == 0:
        print("identical telemetry", file=out)
    return n_diff


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.obs.report",
        description="Summarize or diff deterministic telemetry JSONL exports.",
    )
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_sum = sub.add_parser("summarize", help="one-run summary tables")
    p_sum.add_argument("path")
    p_sum.add_argument("--top", type=int, default=5, help="top-k hot nodes")
    p_diff = sub.add_parser("diff", help="align two runs and print deltas")
    p_diff.add_argument("path_a")
    p_diff.add_argument("path_b")
    args = parser.parse_args(argv)
    try:
        if args.cmd == "summarize":
            summarize(args.path, top=args.top)
            return 0
        return 1 if diff(args.path_a, args.path_b) else 0
    except BrokenPipeError:
        # Piping into `head` closes stdout early; exit quietly instead of
        # tracebacking (dup /dev/null over stdout so interpreter shutdown
        # does not raise again on flush).
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0


if __name__ == "__main__":
    sys.exit(main())
