"""Device milliseconds per epoch of the extract stage (``parsir.extract``:
draining and sorting the epoch's calendar bucket), from the profiler
trace."""
from bench import stages


def compute(rec):
    return stages.epoch_ms(rec, "parsir.extract")
