"""The benchmark: one cell, once, in a fresh process.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that decides a number lives here, where a PR that changes the
program cannot move it: traffic generation, the plain float32 reference, the
comparison that decides `correct`, the reduction from traces, spans and
counters to metrics, the table of peaks and the functions that count a
kernel's operations and bytes. From the program the benchmark takes only
the system under test (through `benchmark/programs/`) and its counters.

A cell is data: `BENCHMARK.json` names a configuration
(`configs/<name>.json`), a traffic mix (`traffic/<name>.json`) and per-layer
metrics (`metrics/<name>.json`, each naming its reader's module, kept in
`readers/`).
"""
