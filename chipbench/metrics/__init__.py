"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each ``<name>.py`` defines ``read(run)``: the metric's value from what
one run recorded (``chipbench.run.Run``), or None where this run has
nothing to read it from; a None metric is left out of the result line.
Readers of shares never return 0 for want of data.
"""
