"""``python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json`` on the chip.

Chip or fail: without a TPU, or with fewer chips than the cell asks for,
the run exits 2 and prints no result.  The last line of standard output is
the result object; everything else goes on earlier lines.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def require_chips(chips: int) -> None:
    """The one device question, asked in the process that measures."""
    import jax

    try:
        devices = jax.devices()
    except Exception as e:  # noqa: BLE001 - the reason is the message
        print(f"benchmark: no JAX backend ({type(e).__name__}: {e})", file=sys.stderr)
        raise SystemExit(2)
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"benchmark: the cell needs {chips} TPU chip(s); JAX reports "
            f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})",
            file=sys.stderr,
        )
        raise SystemExit(2)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--keep-trace", default=None, metavar="DIR",
                        help="with --trace 1, leave the .xplane.pb here "
                        "for a look by hand (benchmark/trace_reduce.py <DIR>)")
    args = parser.parse_args(argv)

    from benchmark.cells import Cell

    cell = Cell(args.workload)
    import murmura_tpu  # noqa: F401 - the system under test has to be there

    require_chips(cell.chips)
    from benchmark import harness

    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_start=T_START,
        keep_trace=args.keep_trace,
    )
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
