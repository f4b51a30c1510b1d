"""`python3 -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>`: one cell, one process, one line of JSON at the end.

Set-up (everything up to the start of the window: reaching the chip,
weights from the seed, the engine or the train step, every program the
cell's schedule can reach) is `setup_s`; then the window; then
`memory_peak_bytes` is read, the program's state freed, and the timed
path's output held against the plain reference (`check.py`), which is
not part of `setup_s`.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

from benchmark import manifest  # noqa: E402


class NoChip(RuntimeError):
    pass


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def configure_jax() -> None:
    """The program's own rules (platform pinned, compile cache at its
    fixed path in the checkout), and every program kept in that cache,
    however quickly it compiled, so that only a checkout's first run
    compiles."""
    from deeplearning4j_tpu.utils import jaxenv

    jaxenv.configure()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_report(chips: int, require_chip: bool) -> dict:
    import jax

    devices = jax.devices()
    report = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if require_chip and (report["platform"] != "tpu"
                         or report["count"] < chips):
        raise NoChip(f"the cell asks for {chips} TPU chip(s); JAX has "
                     f"{report}")
    return report


def memory_peak() -> int:
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def read_metrics(entries, ctx: dict, root: str) -> dict:
    """Each metric through its own reader; one that finds nothing to
    read returns None and is left out."""
    out = {}
    for entry in entries:
        value = manifest.load_reader(entry["name"], root)(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def drive_train(cell, seed, seconds, ctx, programs, tracer,
                control_modes) -> dict:
    """Set-up, window and comparison of a training cell."""
    from benchmark import check, train

    loop = train.TrainLoop(cell, seed)
    got = loop.first_steps()
    loop.warm_up()
    ctx["setup_s"] = time.perf_counter() - ctx["process_start"]
    before = programs.count
    ctx.update(train.run_window(loop, seconds, tracer))
    ctx["programs_in_window"] = programs.count - before
    ctx["memory_peak_bytes"] = memory_peak()
    steps = ctx["train"]["steps"]
    say(f"[{cell.name}] steps={steps} elapsed="
        f"{ctx['train']['elapsed']:.3f}s programs_in_window="
        f"{ctx['programs_in_window']}")
    loop.free()
    ctx["check_from"] = time.perf_counter()
    want = check.reference_steps(cell, seed, train.CHECK_STEPS)
    numbers = check.train_numbers(got, want)
    for mode in control_modes:
        # "half": the fault of half the batch left out, planted in the
        # reference put in the program's place
        half = mode == "half"
        low = check.reference_steps(
            cell, seed, train.CHECK_STEPS, mode="f32" if half else mode,
            rows=slice(0, loop.rows // 2) if half else None)
        for k, v in check.train_numbers(low, want).items():
            numbers[f"control_{mode}_{k}"] = v
    loss = ctx["train"]["last_loss"]
    finite = loss == loss and abs(loss) != float("inf")
    return {"numbers": numbers, "detail": check.train_detail(got, want),
            "attempted": steps, "failed": 0 if finite else steps}


def drive_serve(cell, seed, seconds, ctx, programs, tracer,
                control_modes) -> dict:
    """Set-up, window and comparison of a serving cell."""
    import gc

    import jax

    from benchmark import check, serve

    engine, params = serve.build_engine(cell, seed)
    warm_groups = serve.warm(engine, cell, seed, seconds)

    def stamp_setup():
        ctx["setup_s"] = time.perf_counter() - ctx["process_start"]

    ctx.update(serve.run_window(engine, cell, seed, seconds, programs,
                                tracer, on_start=stamp_setup))
    ctx["memory_peak_bytes"] = memory_peak()
    attempted, failed = serve.attempted_failed(ctx)
    say(f"[{cell.name}] warm_groups={warm_groups} "
        + json.dumps(ctx["diagnosis"]))
    sample = check.pick_requests(ctx["requests"], ctx["window"],
                                 int(cell.traffic["check_requests"]), seed)
    # give the device back before the reference runs: the weights are
    # the benchmark's own arrays, the pool goes with the engine
    engine.close()
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    del engine
    gc.collect()
    ctx["check_from"] = time.perf_counter()
    numbers = check.serve_numbers(cell, seed, sample, control_modes)
    return {"numbers": numbers, "detail": ctx["diagnosis"],
            "attempted": attempted, "failed": failed}


DRIVERS = {"train": drive_train, "serve_closed": drive_serve,
           "serve_open": drive_serve}


def execute(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
            require_chip: bool = True, control_modes=(),
            keep_trace_in: Optional[str] = None,
            process_start: Optional[float] = None) -> dict:
    """One run of one cell; returns the result line as a dict."""
    from benchmark import check, serve, tracing

    device = device_report(cell.chips, require_chip)
    peak = manifest.load_peak(device["kind"], cell.root) \
        if device["platform"] == "tpu" else None
    programs = serve.ProgramCounter()
    tracer = tracing.Tracer(keep_trace_in) if trace else None
    ctx = {"cell": cell.name, "config": cell.config,
           "traffic": cell.traffic, "family": cell.family,
           "itemsize": manifest.itemsize_of(cell.config), "peak": peak,
           "seconds": float(seconds), "device": device,
           "process_start": time.perf_counter() if process_start is None
           else process_start}
    try:
        out = DRIVERS[cell.traffic["driver"]](
            cell, seed, seconds, ctx, programs, tracer, control_modes)
        if tracer is not None:
            reduced = tracer.reduce()
            if ctx.get("trace") is not None and reduced is not None:
                ctx["trace"].update(reduced)
            else:
                ctx["trace"] = None
    finally:
        if tracer is not None:
            tracer.close()
    now = time.perf_counter()
    say(f"[{cell.name}] setup_s={ctx['setup_s']:.2f} whole_run_s="
        f"{now - ctx['process_start']:.2f} check_s="
        f"{now - ctx['check_from']:.2f}")
    result = check.verdict(out["numbers"], cell.limits)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end,
                           ctx, cell.root)
    device = dict(device, memory_peak_bytes=ctx["memory_peak_bytes"])
    line = {"correct": result["correct"] and out["failed"] == 0,
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": device}
    if trace and ctx.get("trace"):
        device["busy_s"] = ctx["trace"]["busy_s"]
        device["window_s"] = ctx["trace"]["window_s"]
        line["breakdown"] = {"device_ops": ctx["trace"]["device_ops"],
                             "idle_gaps": ctx["trace"]["idle_gaps"]}
    line["detail"] = out["detail"]
    line["numbers"] = {k: v for k, v in out["numbers"].items()
                       if k not in result["compared"]}
    line["compared"] = result["compared"]
    for name, c in result["compared"].items():
        say(f"compared {name} = {c['value']} limit {c['limit']}")
    return line


def main(argv=None, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="",
                    help="calibration only: comma-separated lower "
                         "precisions to put in the program's place")
    ap.add_argument("--rate", type=float, default=None,
                    help="builder only: another rate_per_s than the "
                         "traffic file's, for the sweep that finds the "
                         "knee")
    ap.add_argument("--keep-trace-in", default=None,
                    help="builder only: copy the raw trace here")
    args = ap.parse_args(argv)
    root = manifest.ROOT
    if not os.path.isdir(os.path.join(root, "deeplearning4j_tpu")):
        say("the program (deeplearning4j_tpu/) is not in this checkout")
        return 2
    cell = manifest.load_cell(args.workload, root)
    configure_jax()
    if args.rate is not None:
        cell = cell._replace(traffic=dict(cell.traffic,
                                          rate_per_s=args.rate))
    try:
        line = execute(cell, args.seed, args.seconds, bool(args.trace),
                       require_chip=require_chip,
                       control_modes=tuple(m for m in
                                           args.control.split(",") if m),
                       keep_trace_in=args.keep_trace_in,
                       process_start=_PROCESS_START)
    except NoChip as e:
        say(f"no accelerator: {e}")
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
