"""``python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``

One run of one cell of ``BENCHMARK.json`` on the TPU this process finds; the
last line of standard output is the result object.  See ``harness.py``.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0))
