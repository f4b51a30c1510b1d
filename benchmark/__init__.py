"""The benchmark: cells, traffic, metrics and the comparison that decides
`correct`, for `BENCHMARK.json` at the root of the repo.

Everything that measures lives here (traffic generation, percentile and
rate arithmetic, FLOP and byte counts, the table of peaks, the trace
reduction, the plain reference); from the program the benchmark takes
the system under test, `snapshot()`/`plan_fragment()` counts and the
kernels' names. `PERF.md` at the root says why each piece is as it is.
"""
