"""The least HBM traffic of the committed events' handler work, as a share
of the device's peak bandwidth over its busy time, in percent.

The least bytes of one PHOLD event follow from its parameters alone, so the
share reads the same work whatever implements it (rounds, packed or the
event-apply kernel):

- the touch window, read and written: ``S // 32`` nodes x lanes x 4 B x 2;
- the reallocation of ``ceil(P * S)`` nodes: their payload written
  (lanes x 4 B each), their addresses freed and allocated (4 B + 4 B each),
  and the stack top read and written (8 B);
- the event record: read from the calendar (ts, seed, payload: 12 B),
  emitted (dst, ts, seed, payload: 16 B) and inserted (12 B).
"""
import math

EVENT_RECORD_BYTES = 12 + 16 + 12


def event_bytes(model_kw: dict) -> int:
    S, lanes = model_kw["state_nodes"], model_kw["lanes"]
    touch = max(1, S // 32) * lanes * 4 * 2
    kr = max(1, math.ceil(model_kw["realloc_fraction"] * S))
    realloc = kr * (lanes * 4 + 4 + 4) + 8
    return touch + realloc + EVENT_RECORD_BYTES


def compute(rec):
    if "busy_s" not in rec or not rec.get("peaks") or not rec["busy_s"]:
        return None
    least_s = (rec["committed"] * event_bytes(rec["model_kw"])
               / rec["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / rec["busy_s"]
