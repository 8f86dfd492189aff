"""A steady simulation: ``run(state, epochs_per_dispatch)`` over and over,
one dispatch kept queued ahead of the one waited on.

Each simulation (segment) runs ``horizon_epochs`` epochs, then the next seed
drawn from ``--seed`` starts with ``init``.  Counters are read only at the
end of a segment and of the window.  The comparison covers the segment the
window ends in, from its ``init`` to the window's last epoch; a segment
completed inside the window is checked for clean counters only (its
reference would take minutes).
"""
from __future__ import annotations

import time

import jax
from jax.profiler import TraceAnnotation as span

from bench import check
from bench.drivers import object_state, pending


@jax.jit
def _token(state):
    # a fresh scalar that is ready when ``state`` is: something to wait on
    # that the next dispatch does not donate.
    return state.epoch.sum()


class Driver:
    def __init__(self, eng, traffic: dict, seeds):
        self.eng, self.seeds = eng, seeds
        self.per = int(traffic["epochs_per_dispatch"])
        self.horizon = int(traffic["horizon_epochs"])
        if self.horizon % self.per:
            raise ValueError("horizon_epochs must be a multiple of "
                             "epochs_per_dispatch")

    def _start(self):
        self.seed = next(self.seeds)
        with span("bench.init"):
            self.st = self.eng.init(self.seed)
        self.epoch = 0

    def warm(self):
        self._start()
        self.st = self.eng.run(self.st, 0)
        _token(self.st).block_until_ready()

    def window(self, seconds: float) -> dict:
        eng, done = self.eng, []          # totals of whole segments
        epochs = 0
        t0 = time.perf_counter()
        with span("bench.window"):
            prev = None
            while True:
                if self.epoch == self.horizon:
                    with span("bench.readback"):
                        done.append(eng.totals(self.st))
                    self._start()
                    prev = None
                with span("bench.dispatch"):
                    self.st = eng.run(self.st, self.per)
                    tok = _token(self.st)
                self.epoch += self.per
                epochs += self.per
                if prev is not None:
                    with span("bench.wait"):
                        prev.block_until_ready()
                prev = tok
                if time.perf_counter() - t0 >= seconds:
                    break
            with span("bench.wait"):
                tok.block_until_ready()
            window_s = time.perf_counter() - t0
            with span("bench.readback"):
                self.totals = eng.totals(self.st)
        segs = done + [self.totals]
        return {"window_s": window_s, "attempted": len(segs),
                "failed": sum(check.unclean(t) for t in segs),
                "committed": sum(t["processed"] for t in segs),
                "epochs": epochs}

    def sims(self, rec: dict) -> list[check.Sim]:
        eng, st = self.eng, self.st
        return [check.Sim(self.seed, self.epoch, self.totals["processed"],
                          object_state(eng, st.obj, st.bounds),
                          pending(eng, st))]
