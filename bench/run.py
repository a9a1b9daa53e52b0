"""Benchmark of the fused TORTA slot step on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` from the root of a checkout: builds
the cell's fleet and traffic from ``--seed``, warms up, measures for
``--seconds`` seconds (with ``--trace 1`` a shorter profiled window that
reports the per-layer metrics), checks the program's outputs against the
plain reference, and prints one JSON result as the last line of standard
output.  With no TPU, or fewer chips than the cell asks for, or no program
next to the benchmark, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    from harness.manifest import Manifest
    try:
        cell = Manifest(ROOT).cell(args.workload)
    except (OSError, KeyError) as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    from harness.runner import run_cell
    return run_cell(ROOT, args.workload, args.seed, args.seconds,
                    bool(args.trace), T_PROCESS, devices[0])


if __name__ == "__main__":
    sys.exit(main())
