"""`device.memory_stats()["peak_bytes_in_use"]` at the window's end, before the
reference comparison runs in the process: the peak of set-up and the train
steps alone.  Known to read under what a program in flight holds on this chip
(PERF.md section 6): the compiler's own count for the gradient program is in
each config file."""

LAYER = "train step"
UNIT = "bytes"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    return ctx["alloc_peak_bytes"]
