"""The benchmark's own yardstick: cell files, data from the seed, the plain
NumPy reference, the trace reduction, the peaks and cost functions, the
result line.  Nothing here imports the program; ``kinds/`` and a few
reducers do, and take from it only the system under test, its spans and
counters, and its kernel names."""
