"""The benchmark: cells of `BENCHMARK.json`, run one at a time.

Everything here is found by name, so a later PR adds a cell, a traffic
mix or a metric by adding files only:

  configs/<config>.json   a deployment: a `ScenarioSpec` (the request
                          plane's own config object) plus its source
  traffic/<mix>.json      a traffic kind (`kinds/<kind>.py`) and its
                          parameters
  metrics/<metric>.py     one reader per metric: ``read(record)`` gives
                          the number, or None where the run has nothing
                          to read
  reference/              the plain reference the runs are compared with
                          (numpy, written apart from the program; imports
                          nothing of it)

`run.py` is the entry; `harness.py` holds the lookups, the device
checks and the result line; `trace.py` reduces a profiler trace;
`check.py` decides `correct`.
"""
