"""Readings that the limits of a cell's comparison are set from.

    python3 chipbench/readings.py --workload <cell> --seconds <s> \
        --seeds <n> ... --control-seeds <n> ...

In one process (one set-up), runs a short window of the cell for each
seed of `--seeds` with the program, and for each seed of
`--control-seeds` with the control (the reference in the precision below
the configuration's, put in the program's place), and compares each
window's checked jobs with the full-precision reference as a run does.
Prints one JSON line per window, then the largest program reading and the
smallest control reading of each number. The benchmark's own runs never
run the control. Needs a TPU, as `run.py` does.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench.run import use_compile_cache  # noqa: E402


def main(argv=None) -> int:
    """Run the program's and the control's windows; print their readings."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    use_compile_cache()
    import jax

    from chipbench import harness

    if jax.devices()[0].platform != "tpu":
        print("chipbench readings: needs a TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    inst = harness.make_instance(cell)
    worst, least = {}, {}
    for kind, seeds, into, pick in (
        ("program", args.seeds, worst, max),
        ("control", args.control_seeds, least, min),
    ):
        job = None
        for seed in seeds:
            inputs = harness.seed_inputs(cell, inst, seed)
            if job is None:
                job = harness.prepare(cell, inst, inputs, kind)
            m = harness.measure(cell, inst, job, inputs, seed, args.seconds)
            e2e = harness.e2e_metrics(cell, m.window)
            print(json.dumps({"kind": kind, "seed": seed, "jobs": m.window.jobs,
                              "readings": m.readings, "e2e": e2e}), flush=True)
            for k, v in m.readings.items():
                into[k] = pick(into.get(k, v), v)
    print(json.dumps({"program_largest": worst, "control_smallest": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
