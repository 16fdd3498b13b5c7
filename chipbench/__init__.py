"""Chip benchmark for PATSMA's serving path.

Run one cell with ``python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Everything a cell needs is found by name:
configurations in ``configs/``, traffic mixes in ``traffic/``, cells in
``workloads/``, engines in ``engines/``, metric readers in ``metrics/`` and
plain references in ``reference/``.  From the program under ``src/`` the
benchmark takes only the system under test, its counters and its program
names.
"""
