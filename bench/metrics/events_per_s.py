"""Committed events per second of a steady simulation: every event the
window committed over the window's whole time, to the end of its last
dispatch (host clock)."""


def compute(rec):
    return rec["committed"] / rec["window_s"]
