#!/usr/bin/env python3
"""Where tier-1's time goes, from the junit file the driver's command writes.

    python tools/tier1_times.py [/tmp/_t1.xml] [--over 20] [--files 20]

Seconds and cases per file (one worker's job under ``--dist loadfile``), the
cases of ``--over`` seconds or more, the summed case time, what six workers
reach if the files are dealt perfectly, and the wall's share of the cap.
"""

import argparse
import collections
import sys
import xml.etree.ElementTree as ET

CAP_S, WORKERS = 1470, 6     # the driver's limit on the whole run; its ``-n``


def read(path):
    """``(wall seconds, [(file, case, seconds, passed)])`` of a junit file."""
    suite = ET.parse(path).getroot().find("testsuite")
    cases = []
    for case in suite.iter("testcase"):
        parts = case.get("classname", "").split(".")   # tests.test_x.TestY
        n = next((i for i, p in enumerate(parts) if p[:1].isupper()), len(parts))
        passed = not any(c.tag in ("skipped", "failure", "error") for c in case)
        cases.append(("/".join(parts[:n]) + ".py", case.get("name"),
                      float(case.get("time", 0)), passed))
    return float(suite.get("time", 0)), cases


def report(junit, over=20.0, files=20, out=sys.stdout):
    wall, cases = read(junit)
    by_file = collections.defaultdict(lambda: [0.0, 0])
    for file, _, seconds, _ in cases:
        by_file[file] = [by_file[file][0] + seconds, by_file[file][1] + 1]
    ranked = sorted(by_file.items(), key=lambda kv: -kv[1][0])
    print(f"{'seconds':>9} {'cases':>6}  file", file=out)
    for file, (seconds, n) in ranked[:files]:
        print(f"{seconds:9.1f} {n:6d}  {file}", file=out)
    long = sorted((c for c in cases if c[2] >= over), key=lambda c: -c[2])
    total = sum(c[2] for c in cases)
    print(f"\n{len(long)} cases of {over:g} s or more, "
          f"{sum(c[2] for c in long):.0f} s together:", file=out)
    for file, name, seconds, _ in long:
        print(f"{seconds:9.1f}  {file}::{name}", file=out)
    print(f"\n{len(cases)} cases in {len(by_file)} files, "
          f"{sum(c[3] for c in cases)} passed; summed case time {total:.0f} s; "
          f"longest file {ranked[0][1][0] if ranked else 0:.0f} s; "
          f"{WORKERS}-worker lower bound {total / WORKERS:.0f} s; wall "
          f"{wall:.0f} s = {100 * wall / CAP_S:.0f}% of the {CAP_S} s cap",
          file=out)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("junit", nargs="?", default="/tmp/_t1.xml")
    for flag, kind in (("--over", float), ("--files", int)):
        ap.add_argument(flag, type=kind, default=kind(20))
    report(**vars(ap.parse_args()))
