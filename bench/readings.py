"""The readings a cell's correctness limits are set from, in one process:

- the sound program on many seeds (the lower readings);
- the control, the plain reference computed in bfloat16 and put in the
  program's place, on a few seeds;
- each planted fault (``faults.py``) on a few seeds.

Each is compared with the float32 reference exactly as a benchmark run
compares (``harness.check_steps`` / ``harness.compare``), and every
number is printed beside the cell's current limit.

    python3 bench/readings.py --workload resnet50.b32 --seeds 1-12 \\
        --control-seeds 1-3 --faults half_batch --fault-seeds 1-3 \\
        --out chiprun_out/readings.json

Control and fault seeds must be among ``--seeds``: they reuse those
seeds' reference readings. ``--precision highest`` runs the program and
the reference under ``jax.default_matmul_precision``, to see how far
precision alone parts them.
"""
import argparse
import json
import os
import sys
from typing import Callable, Dict, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text):
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def collect(cell, devices, seeds: Sequence[int],
            control_seeds: Sequence[int], fault_names: Sequence[str],
            fault_seeds: Sequence[int],
            log: Callable = lambda s: None) -> Dict:
    """{"sound": {seed: numbers}, "control": {...}, "faults": {fault:
    {seed: numbers}}}, each ``numbers`` as ``harness.compare`` gives."""
    from bench import faults, harness

    def program(job, seed):
        state, prog = harness.check_steps(job, harness.seed_key(seed),
                                          pools[seed],
                                          harness.TimedStep(job.train_step))
        harness.free(state)
        return prog

    out = {"sound": {}, "control": {}, "faults": {}}
    pools, refs = {}, {}
    job = harness.build_job(cell, devices, keep_initial=True)
    reference = harness.Reference(cell, job.prog_seed)
    for seed in seeds:
        key = harness.seed_key(seed)
        pools[seed] = harness.make_pool(cell, key)
        prog = program(job, seed)
        refs[seed] = reference.readings(key, pools[seed])
        out["sound"][seed] = harness.compare(prog, refs[seed])
        log(f"sound seed {seed}: {json.dumps(out['sound'][seed])} loss "
            f"{prog['loss'].tolist()} ref {refs[seed]['loss'].tolist()}")
    control = harness.Reference(cell, job.prog_seed, "bfloat16")
    del job
    for seed in control_seeds:
        got = control.readings(harness.seed_key(seed), pools[seed])
        out["control"][seed] = harness.compare(got, refs[seed])
        log(f"control seed {seed}: {json.dumps(out['control'][seed])}")
    for fault in fault_names:
        out["faults"][fault] = {}
        with faults.planted(fault):
            job = harness.build_job(cell, devices, keep_initial=True)
            for seed in fault_seeds:
                nums = harness.compare(program(job, seed), refs[seed])
                out["faults"][fault][seed] = nums
                log(f"fault {fault} seed {seed}: {json.dumps(nums)}")
        del job
    return out


def summary(cell, report: Dict) -> Dict:
    """Per number: its limit, the largest sound reading, and the smallest
    reading of the control and of each fault."""
    from bench import harness
    rows = {}
    for k in harness.NUMBERS:
        row = {"limit": cell.limits.get(k),
               "sound_max": max(v[k] for v in report["sound"].values())}
        if report["control"]:
            row["control_min"] = min(v[k] for v in report["control"].values())
        for fault, per in report["faults"].items():
            row[f"{fault}_min"] = min(v[k] for v in per.values())
        rows[k] = row
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-3")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="1-3")
    ap.add_argument("--precision", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    cell = harness.load_cell(args.workload, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print("no accelerator", file=sys.stderr)
        return 3
    from repro.launch.train import enable_compile_cache
    enable_compile_cache()

    def log(s):
        print(s, file=sys.stderr, flush=True)

    def go():
        return collect(cell, devices, _seeds(args.seeds),
                       _seeds(args.control_seeds),
                       list(filter(None, args.faults.split(","))),
                       _seeds(args.fault_seeds), log)

    if args.precision:
        with jax.default_matmul_precision(args.precision):
            report = go()
    else:
        report = go()
    report["summary"] = summary(cell, report)
    for k, row in report["summary"].items():
        print(k, json.dumps(row))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
