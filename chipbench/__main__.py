"""``python3 -m chipbench --workload CELL --seed N --seconds S --trace
0|1`` from the checkout's root: one run of one cell (``harness.py``)."""
import time

T_START = time.perf_counter()

import sys  # noqa: E402

from chipbench.harness import main  # noqa: E402

sys.exit(main(sys.argv[1:], t_start=T_START))
