"""Chip benchmark of the DGRO ``/v1`` membership control plane.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``.  Everything a cell is
made of is found by name: its configuration in ``configs/<name>.json``, its
traffic mix in ``traffic/<name>.json`` and each per-layer metric's reader
in ``metrics/<name>.py``.  The yardstick (traffic generation, latency
models, the plain reference, the trace reduction) lives here and imports
nothing of the program under test.
"""
