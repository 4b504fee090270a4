"""The on-chip benchmark of the importance-sampling trainer.

``run.py`` (one directory up) is the command; this package holds the
yardstick: traffic generation (``traffic``), the plain float32 reference
of each model (``reference`` + one file per block kind under ``refs``),
the comparison that decides ``correct`` (``compare``), the reduction from
a profiler trace to device metrics (``tracefile``), the model-FLOP count
(``flops``) and the table of device peaks (``peaks.json``).
"""
