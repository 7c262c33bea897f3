"""The on-chip benchmark: `python3 benchmarks/run.py --workload <cell>`.

Everything that decides a number lives here, where a PR that claims a
gain cannot edit it: traffic generation (`traffic.py`), percentile and
spread arithmetic (`stats.py`), analytic FLOP counts (`flops.py`), the
table of peaks (`peaks.json`), the reduction from a profiler capture to
metrics (`xplane.py`) and the checks that decide `correct` (in the
runners).  From the program it takes only the system under test and its
spans, counters and series.

A cell is found by name: `BENCHMARK.json` names its configuration and
traffic mix; `configs/<config>.json`, `traffic/<mix>.json`,
`runners/<runner>.py` and `metrics/<metric>.py` are looked up by those
names, so a later PR adds a cell, a mix, a runner or a per-layer metric
by adding files and one entry.
"""
