"""Device milliseconds per epoch of a steady simulation: trace busy time
over the epochs the window advanced (an exact count)."""


def compute(rec):
    if "busy_s" not in rec or not rec["epochs"]:
        return None
    return rec["busy_s"] * 1e3 / rec["epochs"]
