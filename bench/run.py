"""Run one cell of the benchmark on the chip this process finds:

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

See ``harness/main.py``. Set-up time counts from this line on.
"""
import time

T0 = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T0))
