"""The yardstick: stream generator, NumPy reference, pacing and rate
arithmetic, host spans, trace reduction, peaks. Nothing here imports
``clonos_tpu`` except ``job.py``, which builds the system under test."""
