"""Builder's tool, not part of a run: find the knee of an open-loop
cell by one sweep on the chip. One process, one set-up, then a window at
each rate; prints, for each, the queue at both ends, the time to first
token in each half, and the tails. The knee is the highest rate at which
the queue at the end is no longer than at the start and the second half
is no worse than the first; the cell's rate (0.8 of it) is then written
into its traffic file by hand.

    python3 -m benchmark.sweep --workload <cell> --seed <n> \\
        --seconds <s> --rates 1.5,2,2.5,3,3.5,4
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import manifest, run, serve


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    cell = manifest.load_cell(args.workload)
    run.configure_jax()
    run.device_report(cell.chips, require_chip=True)
    programs = serve.ProgramCounter()
    engine, _params = serve.build_engine(cell, args.seed)
    # warm for the densest schedule of the sweep
    top = cell._replace(traffic=dict(cell.traffic, rate_per_s=max(rates)))
    serve.warm(engine, top, args.seed, args.seconds)
    for rate in rates:
        at = cell._replace(traffic=dict(cell.traffic, rate_per_s=rate))
        ctx = serve.run_window(engine, at, args.seed, args.seconds,
                               programs)
        ctx["seconds"] = args.seconds
        attempted, failed = serve.attempted_failed(ctx)
        read = {name: manifest.load_reader(name)(ctx) for name in
                ("ttft_p50_ms", "ttft_p90_ms", "itl_p50_ms", "itl_p98_ms",
                 "out_tok_s", "gen_late_p95_ms")}
        print(json.dumps({"rate_per_s": rate, "attempted": attempted,
                          "failed": failed, **read,
                          **ctx["diagnosis"]}), flush=True)
    engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
