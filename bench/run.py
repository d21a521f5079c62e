"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for. See bench/harness.py for what is printed.
"""

import os
import time


def _since_process_start():
    """Seconds since this process was created (from /proc), so set-up counts
    the interpreter's start and every import."""
    tck = os.sysconf("SC_CLK_TCK")
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19]) / tck
    boot0 = time.clock_gettime(time.CLOCK_BOOTTIME)
    perf0 = time.perf_counter()
    return lambda: time.perf_counter() - perf0 + boot0 - start


since_start = _since_process_start()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 3
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    return harness.main(args, since_start)


if __name__ == "__main__":
    sys.exit(main())
