"""On-chip benchmark of the GRNND index: `python bench/run.py --help`.

Cells, configurations, traffic mixes and per-layer metrics are named in
BENCHMARK.json at the repository root and found by name under this
directory: `configs/<config>.json`, `traffic/<mix>.json`,
`metrics/<metric>.py`.  Nothing here is imported by the program.
"""
