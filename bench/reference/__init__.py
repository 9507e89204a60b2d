"""Plain references the benchmark compares the served answers with.

Each module is independent of the program under test: numpy (and
``ml_dtypes`` for the lower-precision control) only. ``<problem>.py``
computes optimal values in a stated dtype (float64 for the comparison);
``solutions`` recomputes a decoded solution's cost from the instance alone.
"""
