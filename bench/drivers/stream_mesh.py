"""Stream partitioning across a worker mesh (Alg 4): the ``stream``
driver's cell, which also records each feed's ``StreamUpdate.counters``
(the merge's ``merge_rounds``, ``merge_bytes`` and ``pushed_words``
beside the packed blocks' counts) and, for a trace's readers, the mesh:
the device kind, the workers, k and the packed width of the sets.

Traffic parameters are the ``stream`` driver's.
"""
from __future__ import annotations

from bench.drivers import stream

__all__ = ["Cell"]


class Cell(stream.Cell):
    def __init__(self, ctx):
        super().__init__(ctx)
        self.counters: list = []   # every feed's counters, set-up's too

    def _feed(self):
        upd = super()._feed()
        self.counters.append(dict(upd.counters))
        return upd

    def window(self, seconds: float) -> None:
        first = len(self.counters)
        super().window(seconds)
        for f, c in zip(self.feeds, self.counters[first:]):
            f["counters"] = c

    def record(self, summary) -> dict:
        import jax

        p = self.cfg["parsa"]
        run = super().record(summary)
        run["mesh"] = {"device_kind": jax.devices()[0].device_kind,
                       "workers": p["workers"], "k": self.cfg["k"],
                       "words": (self.num_v + 31) // 32}
        return run
