"""The chip benchmark of the MiniConv split-policy system.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip and
prints one JSON result line last.  Everything that measures lives here:
the traffic generators (``kinds/``) and their data (``traffic/``), the
configurations (``configs/``) with their plain reference
(``reference/``), the operation and byte counts, the peaks table, the
trace reduction and one reader per per-layer metric (``metrics/``).
From the program it takes only the system under test.
"""
