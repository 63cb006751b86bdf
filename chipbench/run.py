"""Entry point: ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``, run from the root of the checkout."""
import os
import sys
import time

_T0 = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402  (touches no device)

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], started=_T0))
