"""``python -m chipbench ...`` from the checkout's root: the same run as
``python3 chipbench/run.py ...``."""
import sys
import time

from chipbench import harness

sys.exit(harness.main(sys.argv[1:], started=time.perf_counter()))
