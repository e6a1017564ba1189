#!/usr/bin/env python3
"""Spread study over frame logs: python3 perfbench/tools/study.py <cell> [<dir> ...]

Reads ``chiprun_out/perfbench/<cell>-seed*-trace0/frames.jsonl`` (or the
directories given) and prints, per run, the end-to-end metrics and the
alternative estimators, then each metric's spread (interquartile range
over the median, ``statistics.quantiles(n=4)``, as the driver reads it).
"""
import glob
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from perfbench import reduce  # noqa: E402


def load(path):
    with open(path, encoding="utf-8") as fh:
        head, *reqs = [json.loads(ln) for ln in fh]
    return head, reqs


def main(argv):
    cell = argv[1]
    dirs = argv[2:] or sorted(glob.glob(os.path.join(ROOT, "chiprun_out", "perfbench", f"{cell}-seed*-trace0*")))
    rows = []
    for d in dirs:
        head, reqs = load(os.path.join(d, "frames.jsonl"))
        w0, w1 = head["window_s"]
        e = reduce.end_to_end(reqs, w0, w1)
        tenths = reduce.sub_window_rates(reqs, w0, w1, 10)
        fifths = reduce.sub_window_rates(reqs, w0, w1, 5)
        done = [r for r in reduce.finished_in(reqs, w0, w1) if r["status"] == "ok"]
        row = dict(e, setup_s=head["setup_s"], n=len(done),
                   out_tok_s_median_tenths=reduce.percentile(tenths, 50),
                   out_tok_s_median_fifths=reduce.percentile(fifths, 50),
                   tpot_p50_ms=reduce.percentile(reduce.tpots_ms(reqs, w0, w1), 50),
                   full_len_share=sum(1 for r in done if len(r["frames_s"]) == r["max_tokens"]) / max(1, len(done)))
        rows.append(row)
        print(os.path.basename(d), json.dumps({k: (round(v, 2) if isinstance(v, float) else v) for k, v in row.items()}))
    for key in rows[0]:
        vals = [r[key] for r in rows if r[key] is not None]
        if len(vals) >= 2:
            print(f"{key:28s} median {statistics.median(vals):10.2f}  spread {100 * (reduce.spread(vals) or 0):6.2f} %  "
                  f"min {min(vals):10.2f} max {max(vals):10.2f}")


if __name__ == "__main__":
    main(sys.argv)
