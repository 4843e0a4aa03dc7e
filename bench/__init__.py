"""On-chip benchmark of the sparse LU pipeline: one harness driven by data.

``python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once.  Configurations
(``configs/``), traffic mixes (``traffic/``), per-layer metric readers
(``layer_metrics/``), pattern generators (``generators/``) and traffic kinds
(``kinds/``) are files found by name; ``lib/`` is the yardstick: value and
right-hand-side generators, the plain references, the peaks table, the work
counts and the trace reduction.  Nothing under ``lib/`` imports ``repro``.
"""
