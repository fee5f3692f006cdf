"""On-chip benchmark of the EAT serving stack.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON result line.
Everything that measures lives here: the traffic generator, the peaks
table, the operation and byte counts, the trace reduction, the plain
float32 reference and the comparison that decides ``correct``.  From the
program under test (``src/repro``) the harness takes only the serving
engine, built by the launcher's own construction.
"""
