#!/usr/bin/env python3
"""perfbench/calibrate.py — the readings that the limits of ``correct`` are
set from, many seeds in one process (so the chip is paid for once).

    python3 perfbench/calibrate.py --workload <name> --seeds 101,102,... \\
        [--variant-seeds 101,102,103] [--variants control,half_batch,...] \\
        [--out chiprun_out/x.json]

For every seed: set the cell up as a run does, keep what the timed path
produced, free the program, run the plain reference, and record every number
``correct`` could compare, with the table of leaves it was reduced from.
For the variant seeds also, each put in the program's place: the reference in
the nearest lower precision (``control``), with a planted fault
(``half_batch``, ``unchanged``), with its input perturbed (``perturbed``,
image cells).  This only measures.

    python3 perfbench/calibrate.py --workload <name> --seeds 201,202,203 \\
        --through-run-cell control --seconds 5

drives whole runs (``run.run_cell``: set-up, window, reference, ``correct``)
with the named variant standing in the program's place where ``correct``
reads it, and records each result line: the control has to come out
``correct: false`` through the harness's own comparison.

Needs a TPU unless ``--rehearse``.
"""
import argparse
import gc
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def say(msg):
    print("[calibrate] " + msg, flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant-seeds", default="")
    ap.add_argument("--variants",
                    default="control,half_batch,unchanged")
    ap.add_argument("--through-run-cell", default=None, metavar="VARIANT")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from perfbench import common, run
    import jax
    platform = jax.devices()[0].platform
    if (platform != "tpu") != bool(args.rehearse):
        sys.stderr.write("calibrate: needs a TPU, or --rehearse on the CPU\n")
        return 2
    common.enable_compile_cache()
    bench = common.load_json(ROOT, "BENCHMARK.json")
    entry = common.cell_entry(bench, args.workload)
    cell = common.load_json(common.named_file("workloads", entry["name"]))
    config = common.load_json(common.named_file("configs", entry["config"]))
    if args.rehearse:
        cell = common.merged(cell, cell.get("rehearse"))
        config = common.merged(config, config.get("rehearse"))
    seeds = [int(s) for s in args.seeds.split(",") if s]
    with_variants = {int(s) for s in args.variant_seeds.split(",") if s}
    variants = [v for v in args.variants.split(",") if v]
    devices = jax.devices()[:int(entry["chips"])]
    driver_mod = importlib.import_module("perfbench.drivers."
                                         + cell["driver"])
    results = {}
    for seed in seeds:
        t0 = time.perf_counter()
        if args.through_run_cell:
            results[str(seed)] = through_run_cell(
                run, driver_mod, args.through_run_cell, bench, entry, cell,
                config, seed, args.seconds, devices, args.rehearse)
        else:
            env = common.Env(entry["name"], cell, config, seed, devices,
                             args.rehearse, False, say)
            driver = driver_mod.Driver(env)
            driver.setup()
            driver.release()
            gc.collect()
            results[str(seed)] = driver.calibration(
                variants if seed in with_variants else [])
            del driver
        gc.collect()
        say("seed %d done in %.1f s" % (seed, time.perf_counter() - t0))
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f)
    if not args.out:
        print(json.dumps(results))
    return 0


def through_run_cell(run, driver_mod, variant, bench, entry, cell, config,
                     seed, seconds, devices, rehearse):
    """One whole run in which ``variant`` (a name of the driver module's
    ``VARIANTS``) stands in the program's place where ``correct`` reads the
    program: the result line."""
    from perfbench.drivers import train_fit
    real = driver_mod.Driver.program_readings

    def stand_in(self):
        return self.reference_readings(
            **train_fit.variant_args(self, variant))

    driver_mod.Driver.program_readings = stand_in
    try:
        line = run.run_cell(bench, entry, cell, config, seed, seconds, False,
                            devices, None, rehearse)
    finally:
        driver_mod.Driver.program_readings = real
    say("seed %d with %s in the program's place: correct=%s %s"
        % (seed, variant, line["correct"], json.dumps(line["compared"])))
    return line


if __name__ == "__main__":
    sys.exit(main())
