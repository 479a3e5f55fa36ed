"""Run one cell of the benchmark once, on the chip this process holds.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The last line of standard output is the result object; the lines before
it, and the numbers that decide `correct` beside their limits, go to
standard error. With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result. See harness.py for the layout.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
