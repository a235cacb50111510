#!/usr/bin/env python3
"""A first look at a trace by hand: planes, lines, sample events with their
statistics, the operations that took most time.

    python3 perfbench/describe_trace.py <trace dir or .xplane.pb> [out.json]
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from pbench import trace  # noqa: E402


def main():
    where = sys.argv[1]
    path = where if where.endswith(".pb") else trace.find_xplane(where)
    if not path:
        print(f"no .xplane.pb under {where}", file=sys.stderr)
        return 1
    text = json.dumps(trace.describe(path), indent=1)
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
