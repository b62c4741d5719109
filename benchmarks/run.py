"""python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s>
--trace <0|1|2>

One process: loads the cell's configuration and traffic files, makes the
weights from the seed, warms only that cell's shapes, measures for
``--seconds``, and prints the contract's JSON object as the last line of
standard output. ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics (a profiler trace is taken over a
few seconds of the window). ``--trace 2`` is a ``--trace 0`` run
followed by a short traced phase: it does what ``--trace 0`` does until
the window has closed and its numbers are taken, then traces a few
seconds of the same traffic through the program's own control and
reports both kinds of metric in one line. Without a TPU, with fewer chips than the
cell asks, or on a device kind missing from benchmarks/peaks.json, it
exits non-zero and prints no result. ``--rehearse`` is the only CPU
path: it runs a toy cell of benchmarks/rehearsal/cells/, never a cell
of BENCHMARK.json, and says ``platform: cpu``. What belongs to a model
family comes from benchmarks/families/<family>.py, found by the
configuration file's ``family`` key.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()      # set-up counts from here

import argparse                    # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import traceback                   # noqa: E402
import types                       # noqa: E402

from benchmarks import common      # noqa: E402
from benchmarks.common import log  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy cell of rehearsal/cells/")
    ap.add_argument("--rate", type=float, default=None,
                    help="knee sweep only: override an open loop's rate")
    ap.add_argument("--dump-trace", default=None,
                    help="also write the trace's plain form (.json.gz)")
    return ap.parse_args(argv)


def end_to_end(bench, cell_name, run, units):
    wanted = [m["name"] for m in common.metrics_of_cell(
        bench, "end_to_end", cell_name)]
    return {n: {"value": run.e2e[n], "unit": units[n]}
            for n in wanted if n in run.e2e}


def main(argv=None) -> int:
    args = parse(argv)
    common.refuse_selectors()
    bench = common.load_benchmark()
    if args.rehearse:
        cell = common.load_rehearsal_cell(args.workload)
        cfg = common.load_json("rehearsal", cell["config"] + ".json")
        metrics_as = cell["metrics_as"]
    else:
        cell = common.find_named(bench["workloads"], args.workload,
                                 "workload")
        conf = common.find_named(bench["configs"], cell["config"],
                                 "configuration")
        with open(os.path.join(common.ROOT, conf["file"])) as f:
            cfg = json.load(f)
        metrics_as = cell["name"]
    tr_dir = "rehearsal" if args.rehearse else "traffic"
    traffic = common.load_json(tr_dir, cell["traffic"] + ".json")
    if args.seconds is None:
        args.seconds = float(bench["run_seconds"])
    chips = int(cell["chips"])
    if chips != int(cfg["chips"]):
        raise SystemExit(f"benchmarks: cell {cell['name']} asks {chips} "
                         f"chips, its configuration {cfg['chips']}")

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}")
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse:
        if platform != "cpu":
            raise SystemExit("benchmarks: --rehearse is the CPU path")
        peaks = None
    else:
        if platform != "tpu":
            raise SystemExit(f"benchmarks: cells measure the chip; JAX "
                             f"found {devices}")
        peaks = common.peaks_for(devices[0].device_kind)
    if len(devices) < chips:
        raise SystemExit(f"benchmarks: cell needs {chips} chip(s), JAX "
                         f"found {len(devices)}")
    log(f"[device] {platform} {devices[0].device_kind} x{len(devices)}; "
        f"cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}")

    meter = common.CompileMeter().start()
    trace_dir = os.path.join(common.ROOT, ".bench_trace",
                             cell["name"].replace("/", "_"))
    if args.trace == 1:
        # (--trace 2 touches nothing of the trace before its window has
        # closed: the runner makes the directory then)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    ctx = types.SimpleNamespace(
        args=args, cell=cell, cfg=cfg, traffic=traffic, chips=chips,
        meter=meter, t_process=T_PROCESS, peaks=peaks,
        trace_dir=trace_dir, rate=args.rate, rehearse=args.rehearse,
        family=common.load_family(cfg["family"], cfg["kind"]))
    if cfg["kind"] == "serve":
        from benchmarks import serve_runner as runner
    elif cfg["kind"] == "train":
        from benchmarks import train_runner as runner
    else:
        raise SystemExit(f"benchmarks: unknown kind {cfg['kind']!r}")
    run = runner.run(ctx)

    totals = meter.snapshot()
    log(f"[compile] whole run: trace {totals['trace_s']:.1f} lower "
        f"{totals['lower_s']:.1f} backend {totals['compile_s']:.1f} "
        f"cache-read {totals['cache_read_s']:.1f} s; cache hits "
        f"{totals['cache_hits']:.0f} misses {totals['cache_misses']:.0f}"
        f"; programs {totals['programs']:.0f}")
    for name, value in sorted(run.e2e.items()):
        log(f"[host clock] {name} = {value}")

    device = common.device_block(devices, chips)
    log(f"[memory] {devices[0]}: {devices[0].memory_stats()}")
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed}
    if args.trace:
        from benchmarks import trace_reduce
        ir = trace_reduce.load(trace_dir)
        if args.dump_trace:
            os.makedirs(os.path.dirname(os.path.abspath(args.dump_trace)),
                        exist_ok=True)
            trace_reduce.save_json(ir, args.dump_trace)
        run.trace = trace_reduce.reduce(ir, chips)
        if not run.trace or run.trace["busy_s"] <= 0:
            if not args.rehearse:
                raise SystemExit("benchmarks: the trace shows no "
                                 "operation on the device")
            run.trace = {"breakdown": {"device_ops": [], "idle_gaps": []}}
        else:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            log(f"[trace] modules {run.trace['modules']}")
        # --trace 2 reports both kinds side by side; its readers may
        # open the trace itself (run.trace_dir), so it goes only now
        metrics = (end_to_end(bench, metrics_as, run, units)
                   if args.trace == 2 else {})
        for m in common.metrics_of_cell(bench, "per_layer", metrics_as):
            value = common.load_metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = run.trace["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        metrics = end_to_end(bench, metrics_as, run, units)
    result["metrics"] = metrics
    result["device"] = device
    result["compiles_in_window"] = run.compiles_in_window
    try:
        run.shutdown()
    except Exception:                 # noqa: BLE001 — the result stands
        traceback.print_exc()
    common.emit_result(result)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        if isinstance(e.code, str):
            print(e.code, file=sys.stderr)
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:             # noqa: BLE001 — report, then leave
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # engine, serve and client threads must not keep a finished (or a
    # failed) run alive on the chip
    os._exit(rc)
