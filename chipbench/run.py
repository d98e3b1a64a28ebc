"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `BENCHMARK.json`; everything it names is found by
name under `chipbench/` (see `chipbench/harness.py`). The run builds the
cell's instance, warms up the cell's own shapes (set-up, reported as
`setup_s`), runs jobs through `sampler_api.run(..., backend="pallas")` for
`--seconds`, and compares a seeded sample of the jobs with the plain
reference. With `--trace 0` the result carries the cell's end-to-end
metrics; with `--trace 1` the window is traced and it carries the
per-layer metrics, the device's busy time and a breakdown.

The last line of standard output is one JSON object; the last lines of
standard error are the compared numbers beside their limits. On any
platform but a TPU, or with fewer chips than the cell asks for, the run
exits nonzero before it prints a result. JAX's compilation cache is kept
in `$JAX_COMPILATION_CACHE_DIR`, or else at `<checkout>/.jax_cache`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def use_compile_cache() -> str:
    """Keep every compiled program in the persistent cache; return its path.

    Where `JAX_COMPILATION_CACHE_DIR` is set JAX has read it already;
    otherwise the cache sits at a fixed path in the checkout, since the
    path is part of the cache's key."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    """Parse the arguments, check the platform, run the cell."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="a workload name of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True, help="seed of every input of the run")
    ap.add_argument("--seconds", type=float, required=True, help="length of the timed window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report the per-layer metrics")
    args = ap.parse_args(argv)

    use_compile_cache()
    import jax

    import repro.core.sampler_api  # noqa: F401  (the system under test; fail early without it)
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    t_imports = time.perf_counter()
    devices = jax.devices()
    phases = {"imports_s": t_imports - T_START, "backend_s": time.perf_counter() - t_imports}
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, found platform {devices[0].platform!r}; "
              "there is no fallback", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START, phases)
    sys.stdout.flush()
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
