"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload resnet50.b32 --seed 7 --seconds 20 \\
        --trace 0

Prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number the correctness check compared, with its limit.
The same numbers end standard error. Without an accelerator, or with
fewer chips than the cell asks for, it prints no result and exits 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print(f"no accelerator for {cell.name}: found {len(devices)} "
              f"{devices[0].platform} device(s), need {cell.chips}",
              file=sys.stderr)
        return 3
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()

    result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                         devices, T_START,
                         log=lambda s: print(s, file=sys.stderr, flush=True))
    for line in harness.checks_text(result["checks"]):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
