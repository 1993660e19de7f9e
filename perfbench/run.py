#!/usr/bin/env python3
"""perfbench/run.py — one cell of the benchmark, one process, one result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its file under ``perfbench/workloads/``,
its configuration under ``perfbench/configs/`` and its driver under
``perfbench/drivers/`` — all by name, so a later PR adds cells, configurations,
metrics and drivers as new files and new entries only (``perfbench/README.md``).

Without a TPU (or with fewer chips than the cell asks for) it exits 2 and
prints no result line.  ``--rehearse`` runs the cell's tiny ``rehearse`` sizes
on the CPU to find wrong paths and arguments; what it prints is stamped
``"rehearsal": true`` and is never a result.
"""
import time
T_START = time.perf_counter()       # process start, as near as Python gets

import argparse          # noqa: E402
import glob              # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_SECONDS = 4.0      # a traced run measures this long at the most


def log(msg):
    sys.stdout.write("[perfbench +%.1fs] %s\n"
                     % (time.perf_counter() - T_START, msg))
    sys.stdout.flush()


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU; never a result")
    return ap.parse_args(argv)


def metrics_of(bench, kind, cell_name, reported=None):
    """The entries of ``end_to_end`` / ``per_layer`` that this cell reports:
    those that list it under ``workloads``, or list nothing and (per-layer)
    move an end-to-end metric the cell reports."""
    out = []
    for m in bench[kind]:
        cells = m.get("workloads")
        if cells is not None and cell_name not in cells:
            continue
        if kind == "per_layer" and m["moves"] not in reported:
            continue
        out.append(m)
    return out


def _finite(x):
    """A number JSON can carry: not-a-number and infinity become 1e300,
    which no limit admits."""
    x = float(x)
    return x if x == x and abs(x) != float("inf") else 1e300


def device_stamp(jax, devices):
    # On this runtime a compiled program's scratch is held as "reserved"
    # memory, apart from the buffers "in use" (PERF.md section 2): the
    # chip's peak is the two peaks together.
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def run_cell(bench, entry, cell, config, seed, seconds, trace, devices,
             peaks, rehearse):
    """Everything a run does once the chip has been looked for: set-up,
    the window, the metrics, the reference and ``correct``.  Returns the
    result line as a dict (``perfbench/tests/test_correct.py`` drives it
    with the timed path broken underneath)."""
    import jax
    from perfbench import common
    chips = int(entry["chips"])
    compiles = common.Compiles()

    env = common.Env(entry["name"], cell, config, seed, devices,
                     rehearse, trace, log)
    driver_mod = importlib.import_module("perfbench.drivers."
                                         + cell["driver"])
    driver = driver_mod.Driver(env)
    log("cell %s  config %s  driver %s  seed %d  seconds %g  trace %d%s"
        % (entry["name"], entry["config"], cell["driver"], seed,
           seconds, trace, "  REHEARSAL" if rehearse else ""))
    driver.setup()
    setup_compiles = compiles.since((0, 0.0, 0))
    log("set-up: %s" % json.dumps(setup_compiles))

    trace_dir = os.path.join(ROOT, ".perfbench_trace",
                             "%s-%d" % (entry["name"], os.getpid()))
    snap = compiles.snapshot()
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with env.span("window"):
            res = driver.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    setup_s = res["t_first"] - T_START
    in_window = compiles.since(snap)
    log("window: %s  compiled in window: %s"
        % (json.dumps(res["counters"]), json.dumps(in_window)))
    device = device_stamp(jax, devices)
    log("memory: %s" % json.dumps(devices[0].memory_stats() or {}))
    driver.release()

    e2e = dict(res["e2e"])
    e2e["setup_s"] = setup_s
    reported = {m["name"] for m in metrics_of(bench, "end_to_end",
                                              entry["name"])
                if m["name"] in e2e}
    line = {"correct": None, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": {}, "device": device}
    if rehearse:
        line["rehearsal"] = True
    if not trace:
        for m in metrics_of(bench, "end_to_end", entry["name"]):
            if m["name"] in e2e:
                line["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                              "unit": m["unit"]}
    else:
        from perfbench import trace_reduce
        pbs = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
        data = trace_reduce.load(pbs[0])
        summary = trace_reduce.TraceSummary(
            data, trace_reduce.find_window(data))
        del data
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
        log("device time by kind of operation, seconds: %s" % json.dumps(
            trace_reduce.top(trace_reduce.by_stem(trace_reduce.self_times(
                summary.first_chip_ops())), n=12)))
        ctx = {"trace": summary, "counters": res["counters"], "cell": cell,
               "config": config, "peaks": peaks, "chips": chips,
               "elapsed_s": res["elapsed_s"]}
        for m in metrics_of(bench, "per_layer", entry["name"], reported):
            spec = common.load_json(common.named_file("metrics", m["name"]))
            reader = importlib.import_module("perfbench.readers."
                                             + spec["reader"])
            value = reader.read(ctx, **spec.get("args", {}))
            if value is not None:       # nothing to read: left out
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}

    # the reference runs last: the window is closed, the peak is read and
    # the program's state is freed
    t_check = time.perf_counter()
    rows = driver.check()
    rows.append(("compiled_in_window", in_window["executables"], 0))
    rows.append(("failed_requests", res["failed"], 0))
    compared = {name: {"value": _finite(value), "limit": limit}
                for name, value, limit in rows}
    correct = all(v["value"] <= v["limit"] for v in compared.values())
    log("check took %.1f s" % (time.perf_counter() - t_check))
    line["correct"] = bool(correct)
    line["compared"] = compared          # comes last in the line
    return line


def main(argv=None):
    args = parse_args(argv)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from perfbench import common
    bench = common.load_json(ROOT, "BENCHMARK.json")
    entry = common.cell_entry(bench, args.workload)
    cell = common.load_json(common.named_file("workloads", entry["name"]))
    config = common.load_json(common.named_file("configs", entry["config"]))
    if cell["config"] != entry["config"]:
        raise SystemExit("perfbench: %s names configuration %r, "
                         "BENCHMARK.json %r" % (entry["name"], cell["config"],
                                                entry["config"]))
    if args.rehearse:
        cell = common.merged(cell, cell.get("rehearse"))
        config = common.merged(config, config.get("rehearse"))
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])
    if args.trace:
        seconds = min(seconds, float(cell.get("trace_seconds",
                                              TRACE_SECONDS)))

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    chips = int(entry["chips"])
    if args.rehearse:
        if platform != "cpu":
            sys.stderr.write("perfbench: --rehearse is the CPU rehearsal: "
                             "run it under JAX_PLATFORMS=cpu\n")
            return 2
    elif platform != "tpu" or len(devices) < chips:
        sys.stderr.write("perfbench: cell %s needs %d TPU chip(s); "
                         "jax.devices() is %s; nothing was run\n"
                         % (entry["name"], chips, devices))
        return 2
    devices = devices[:chips]
    if not args.rehearse:
        from perfbench import flops
        peaks = flops.load_peaks(devices[0].device_kind)  # unknown: error
    else:
        peaks = None

    log("compile cache: %s" % common.enable_compile_cache())
    line = run_cell(bench, entry, cell, config, args.seed, seconds,
                    bool(args.trace), devices, peaks, args.rehearse)
    tail = "  ".join("%s=%.6g (limit %.6g)" % (n, v["value"], v["limit"])
                     for n, v in line["compared"].items())
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    sys.stderr.write("perfbench %s seed %d correct=%s: %s\n"
                     % (entry["name"], args.seed, line["correct"], tail))
    sys.stderr.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
