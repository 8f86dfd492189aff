"""Device milliseconds per epoch of the process stage (``parsir.process``:
the steal policy and the scheduler running the events), from the profiler
trace."""
from bench import stages


def compute(rec):
    return stages.epoch_ms(rec, "parsir.process")
