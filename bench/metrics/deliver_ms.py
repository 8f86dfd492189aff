"""Device milliseconds per epoch of the deliver stage (``parsir.deliver``:
inserting arrivals into calendar buckets and the fallback list), from the
profiler trace."""
from bench import stages


def compute(rec):
    return stages.epoch_ms(rec, "parsir.deliver")
