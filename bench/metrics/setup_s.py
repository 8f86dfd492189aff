"""Set-up seconds: process start to window start (imports, device check,
compile cache, engine build, ``init`` and the warm-up of every dispatched
program)."""


def compute(rec):
    return rec["setup_s"]
