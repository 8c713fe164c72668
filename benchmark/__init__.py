"""The on-chip benchmark of tpu-ft: the yardstick later PRs are held to.

Everything here is the benchmark's own.  From the program it takes the system
under test (`torchft_tpu`), its spans, counters and kernel names; traffic,
weights, the plain reference, the comparison that decides `correct`, the
operation counts, the peaks and the reduction from trace to metrics live here.
"""
