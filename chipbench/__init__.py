"""Chip benchmark of the AIF fleet router: cells, traffic, reference, trace
reduction.  Run one cell with ``python chipbench/run.py --workload <cell>``."""
