"""The benchmark's yardstick: traffic generation, percentile and lateness
arithmetic, trace reduction, FLOP and byte functions, the table of peaks,
the float32 reference and the comparison that decides `correct`. From the
program it takes the system under test and its counters, nothing else."""
