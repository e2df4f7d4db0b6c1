"""Entry point: ``python3 benchmarks/chip/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` from the root of a checkout.
Prints one JSON object as its last line of standard output; refuses,
with no result and a non-zero exit code, where the cell's chips are not
there."""

import time

T0 = time.time()

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the script's own directory would shadow the standard library's ``trace``
sys.path[0:1] = [_ROOT, os.path.join(_ROOT, "src")]

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t0=T0))
