"""The benchmark's command: one run of one cell on the chip(s) of this
machine.

    python3 benchmarks/tpu/run.py --workload kg-steady --seed 7 \\
        --seconds 51 --trace 0

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics) and
``device``; the numbers that decided ``correct`` come last in it under
``checks``, and as the last lines of standard error.  Exits non-zero,
printing no result, when JAX finds no TPU or fewer chips than the cell
asks for, or when the program is not in the checkout.
"""
import time

STARTED = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from tpubench import harness
    try:
        harness.setup_env()
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), STARTED)
    except (harness.NoDevice, FileNotFoundError, KeyError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
