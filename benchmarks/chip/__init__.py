"""The on-chip benchmark: one command runs one cell of BENCHMARK.json once
(``python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``); see PERF.md for what each cell and metric is for."""
