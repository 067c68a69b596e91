#!/usr/bin/env python3
"""tpubench: run one cell of BENCHMARK.json once.

    python3 tpubench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device` and, traced, `breakdown`); everything else is on
earlier lines or in files under the output directory. See tpubench/README.md.
"""
import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
# libtpu would write its logs to /tmp/tpu_logs, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for the run's logs and trace (default "
                         "tpubench_out/<workload>/seed<seed>-trace<t>)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb in the output directory")
    args = ap.parse_args(argv)

    from tpubench import core

    cell = core.Cell(args.workload)
    out = args.out or os.path.join(
        ROOT, "tpubench_out", cell.name,
        f"seed{args.seed}-trace{args.trace}")
    run = core.Run(cell, args.seed, args.seconds, args.trace, out, T_START)
    run.claim_devices()
    core.kind(cell.traffic).run(run)
    line = core.result_line(run)
    if not args.keep_trace:
        run.drop_trace()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
