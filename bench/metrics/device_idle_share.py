"""Share of the traced window in which the device ran no operation:
1 - busy / window, both from the profiler trace."""


def compute(rec):
    if "busy_s" not in rec:
        return None
    return 1.0 - rec["busy_s"] / rec["trace_window_s"]
