"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration, traffic mix and
metrics are looked up by name from ``BENCHMARK.json`` (``bench/spec.py``).
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``check``, each number compared beside its limit.
Without a TPU, or without the program beside it (``src/repro``), it exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no result: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    return harness.main(sys.argv[1:], t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
