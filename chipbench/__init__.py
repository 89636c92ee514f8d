"""chipbench — the benchmark: everything the yardstick owns lives here.

``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the chip it is
started on and prints the result line.  Later PRs add cells, configurations,
traffic mixes and layer metrics as new files found by name (``spec.py``);
from the program the benchmark takes only the system under test, its spans
and its counters.
"""
