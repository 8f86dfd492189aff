"""Device milliseconds per epoch of routing: producer triage, route-buffer
selection and fallback compaction (``parsir.route``) and the router's
collective (``parsir.exchange``), from the profiler trace."""
from bench import stages


def compute(rec):
    return stages.epoch_ms(rec, "parsir.route", "parsir.exchange")
