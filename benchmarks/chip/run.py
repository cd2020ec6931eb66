"""The chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs the chips the cell asks
for: without a TPU, with fewer chips, or on a device kind missing from
``peaks.py`` it exits non-zero and prints no result. The cells, the
metrics and their bounds are in ``BENCHMARK.json`` at the repository
root; what belongs to one configuration, traffic mix, cell or per-layer
metric is in a file of its own under this directory (see ``harness.py``).

The last line of standard output is the result: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics, or with
``--trace 1`` the per-layer ones), ``device``, with ``--trace 1`` a
``breakdown`` of the traced window, and last ``compared``: each number
the correctness check compared, beside its limit. The same numbers are
the last lines of standard error.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def _process_start() -> float:
    """The process's start on the ``perf_counter`` clock (Linux), so that
    set-up counts the interpreter's own start-up too."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return now - max(0.0, up - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return now


T_PROCESS = _process_start()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the reference's float8 control in the "
                         "program's place in the comparison, on the same "
                         "sample (for setting the limit; never part of a "
                         "benchmark run)")
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        print(f"run.py: no program under {root}/src", file=sys.stderr)
        return 2
    import harness
    import peaks
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_process=T_PROCESS,
                             control=args.control)
    except (harness.NoChip, peaks.UnknownDevice) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
