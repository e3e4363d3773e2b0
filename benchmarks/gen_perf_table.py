#!/usr/bin/env python
"""Generate docs/performance.md's measured table from a BENCH_r*.json.

Round 2's perf doc hand-copied bench numbers and drifted (the doc said
double-buffering measured 0.92x while the driver-captured bench said
1.043x).  This script makes the doc's measured table a *function* of the
driver-captured JSON: the table lives between markers

    <!-- bench-table:begin source=BENCH_rNN.json -->
    ...generated...
    <!-- bench-table:end -->

and the check mode asserts the document byte-matches regeneration from
its declared source, so a hand-edit or a stale number fails
(``tests/test_perf_doc.py`` pins that on a synthetic document).

Usage:
    python benchmarks/gen_perf_table.py            # check (exit 1 on drift)
    python benchmarks/gen_perf_table.py --write    # rewrite the block
    python benchmarks/gen_perf_table.py --source BENCH_r03.json --write
    python benchmarks/gen_perf_table.py --doc ROOT/docs/other.md
"""

import argparse
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "performance.md")
BEGIN_RE = re.compile(
    r"<!-- bench-table:begin source=(?P<src>[\w.]+) -->"
)
END = "<!-- bench-table:end -->"


def _fmt_value(v):
    if v is None:
        return "—"
    if isinstance(v, float):
        return f"{v:,.1f}" if abs(v) >= 100 else f"{v:,.3g}"
    return f"{v:,}" if isinstance(v, int) else str(v)


def _md(s) -> str:
    """Escape cell content: a literal '|' (e.g. 'enc|dec') would split
    the markdown row into extra columns."""
    return str(s).replace("|", "\\|")


def _row(name, entry):
    if "error" in entry:
        return (f"| {name} | {_md(entry.get('metric', name))} | error "
                "| — | — | — | — |")
    mfu = entry.get("mfu")
    # both accountings, always (advisor r4: flash configs' headline MFU
    # includes the analytic attention term XLA cannot count; tables must
    # carry the XLA-only figure alongside so cross-round comparisons can
    # name which accounting they use)
    mfu_x = entry.get("mfu_xla_counted")
    return "| {} | {} | {} | {} | {} | {} | {} |".format(
        _md(name),
        _md(entry.get("metric", name)),
        _fmt_value(entry.get("value")),
        _md(entry.get("unit", "")),
        _fmt_value(entry.get("step_time_ms")),
        f"{mfu:.3f}" if isinstance(mfu, (int, float)) else "—",
        f"{mfu_x:.3f}" if isinstance(mfu_x, (int, float)) else "—",
    )


def _repair_truncated(record: dict) -> dict:
    """Recover a round-3-style driver record whose final bench line
    overflowed the driver's ~2000-char stdout tail: ``parsed`` is null
    and ``tail`` holds the *end* of the line — the complete ``configs``
    dict plus whatever headline fields survived.  Brace-match the
    configs JSON and regex-scrape the surviving headline scalars."""
    tail = record.get("tail", "")
    i = tail.find('"configs": ')
    if i < 0:
        raise SystemExit("bench record is unparseable (no configs in tail)")
    start = tail.index("{", i)
    configs, _ = json.JSONDecoder().raw_decode(tail[start:])
    parsed = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": None,
        "unit": "images/sec/chip (headline value lost to tail truncation)",
        "configs": configs,
    }
    for key in ("value", "vs_baseline", "step_time_ms", "mfu",
                "model_tflops_per_step"):
        m = re.search(rf'"{key}": ([\d.eE+-]+)', tail[:i])
        if m:
            parsed[key] = float(m.group(1))
    return parsed


def generate(bench_path: str) -> str:
    with open(bench_path) as f:
        # the bench file may hold the wrapped driver record or the raw line
        data = json.load(f)
    if "parsed" in data:
        data = data["parsed"] if data["parsed"] is not None else (
            _repair_truncated(data)
        )
    if "configs" not in data and "summary" in data:
        # compact final-line record (round 4+; "mfu_x" since round 5 so
        # the both-accountings column survives a summary-only capture)
        # re-inflating a stored capture for display — not a measurement
        data["configs"] = {  # mnlint: allow(untimed-row)
            k: {"metric": k, "value": s.get("v"), "unit": s.get("u", ""),
                "step_time_ms": s.get("ms"), "mfu": s.get("mfu"),
                "mfu_xla_counted": s.get("mfu_x")}
            for k, s in data["summary"].items()
        }
    lines = [
        "| config | metric | value | unit | step ms | MFU | MFU (XLA-counted) |",
        "|---|---|---|---|---|---|---|",
        _row("resnet50 (headline)", data),
    ]
    for name, entry in data.get("configs", {}).items():
        lines.append(_row(name, entry))
    return "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true")
    ap.add_argument("--source", default=None,
                    help="override the source= file named in the doc")
    ap.add_argument("--doc", default=DOC,
                    help="the marked document (<root>/docs/NAME.md; its "
                         "source file is looked up under <root>)")
    args = ap.parse_args()

    root = os.path.dirname(os.path.dirname(os.path.abspath(args.doc)))
    name = os.path.relpath(args.doc, root)
    with open(args.doc) as f:
        doc = f.read()
    m = BEGIN_RE.search(doc)
    if not m or END not in doc:
        sys.exit(f"{name} is missing the bench-table markers")
    src = args.source or m.group("src")
    begin_line = f"<!-- bench-table:begin source={src} -->"
    table = generate(os.path.join(root, src))
    block = f"{begin_line}\n{table}\n{END}"

    start, stop = m.start(), doc.index(END) + len(END)
    new_doc = doc[:start] + block + doc[stop:]
    if args.write:
        with open(args.doc, "w") as f:
            f.write(new_doc)
        print(f"wrote table from {src}")
        return
    if new_doc != doc:
        sys.exit(
            f"{name} measured table drifted from {src}; "
            "run: python benchmarks/gen_perf_table.py --write"
        )
    print(f"{name} matches {src}")


if __name__ == "__main__":
    main()
