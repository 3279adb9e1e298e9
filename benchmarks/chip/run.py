"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the cell
asks for.  The last line of standard output is the result, one JSON object;
the numbers compared with the reference follow on standard error.  Without
a TPU, or with fewer chips than the cell needs, it exits 2 and prints no
result.
"""

import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

# The TPU runtime maps a host buffer for transfers when it starts; at its
# default size that takes 5-12 s of a run's set-up, and most of its spread.
# Every transfer a cell makes (token batches, fetched tokens, norms) is far
# smaller than 256 MiB.
os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))

ROOT = Path(__file__).resolve().parents[2]
if sys.path and Path(sys.path[0] or ".").resolve() == Path(__file__).resolve().parent:
    sys.path.pop(0)  # the script's own directory would shadow the stdlib's ``trace``
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
