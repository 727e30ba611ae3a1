"""On-chip benchmark of the CNN serving path (see ``bench/run.py``).

Everything the benchmark measures with lives here: the traffic generator
and its mixes, the model configurations, the plain references, the
per-kernel operation and byte counts, the peaks table, the trace
reduction and the comparison that decides ``correct``.  From the
program under ``src/`` it takes only the served path (``CNNApi.serve``)
and the compiled programs' kernel names.
"""
