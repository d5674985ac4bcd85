"""Run one benchmark cell once, on the chip, and print its result.

    python3 perfbench/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the result object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks``, the numbers compared with their limits). The
line before it holds what the run learned about itself (cache hits,
compiles in the window, segments). The compared numbers are also the
last lines of standard error. With no accelerator, or fewer chips than
the cell needs, the run exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # caches stay inside the checkout, at a path that does not move, so
    # that only a cell's first run compiles and two checkouts share
    # nothing; the runtime's own logs stay off disk
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".cache",
                                                           "jax")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    try:
        from perfbench import harness
    except ImportError as e:
        print(f"perfbench: cannot import the harness: {e}",
              file=sys.stderr)
        return 1
    try:
        out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except harness.NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    except harness.CellError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 1
    info = out.pop("_info")
    print(json.dumps({"info": info}), flush=True)
    for line in harness.check_line(out["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
