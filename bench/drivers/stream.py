"""Stream partitioning: fresh chunks fed into one ``StreamSession`` back
to back, as arriving rows are placed in a deployment that keeps growing.

Traffic parameters (``bench/traffic/<mix>.json``):

* ``rows_per_feed`` — rows of each chunk;
* ``pool_feeds`` — chunks made at set-up, each a function of (seed,
  index); a window that outruns them wraps round, and says so;
* ``warmup_feeds`` — chunks fed during set-up (they compile the scan);
* ``trace_seconds`` — the traced window's length.

``partition_rate`` is the rows of every feed in the window over the
window's time, from its start to the end of its last feed.  The check
replays every chunk fed, set-up's included, through the plain greedy
(``bench.reference.StreamReference``) and compares every row's part,
the final server sets bit for bit and the part sizes.  With Alg 4
(``parallel_device``) the reference follows the same merge rounds.
"""
from __future__ import annotations

import time

import numpy as np

from bench import gen
from bench.reference import StreamReference

__all__ = ["Cell"]


def _unpack(words: np.ndarray, num_v: int) -> np.ndarray:
    """(k, W) int32 words → (k, num_v) bool; bit j of word w is column
    32·w + j."""
    w = np.ascontiguousarray(words).view(np.uint32)
    bits = np.unpackbits(w.view(np.uint8).reshape(w.shape[0], -1), axis=-1,
                         bitorder="little")
    return bits[:, :num_v].astype(bool)


class Cell:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.traffic = ctx.traffic
        self.num_v = gen.num_columns(self.cfg)
        self.feeds: list = []       # window feeds: rows, timings, end time
        self.fed: list = []         # pool index of every chunk fed
        self.window_s = 0.0
        self.bad_feeds = 0

    def _session(self):
        from repro.api import ParsaConfig
        from repro.stream import ParsaStreamConfig, StreamSession

        p, s = self.cfg["parsa"], self.cfg["stream"]
        base = ParsaConfig(k=self.cfg["k"], backend=p["backend"],
                           block_size=p["block_size"], cap=p["cap"],
                           use_kernel=p["use_kernel"],
                           workers=p.get("workers", 4),
                           merge_every=p.get("merge_every", 1),
                           seed=self.ctx.seed)
        return StreamSession(
            ParsaStreamConfig(base=base, repartition=s["repartition"],
                              tb_pad=s["tb_pad"]), num_v=self.num_v)

    def setup(self) -> None:
        t = self.traffic
        t0 = time.perf_counter()
        self.pool = gen.chunk_pool(self.cfg, self.ctx.seed,
                                   t["rows_per_feed"], t["pool_feeds"])
        self.graphs = [gen.as_graph(c, self.num_v) for c in self.pool]
        self.ctx.log(f"data: {len(self.pool)} chunks of "
                     f"{t['rows_per_feed']} rows, "
                     f"{sum(int(c[1].shape[0]) for c in self.pool)} "
                     f"nonzeros, made in {time.perf_counter() - t0:.3f} s")
        self.session = self._session()
        for _ in range(t["warmup_feeds"]):
            self._feed()
        W = (self.num_v + 31) // 32
        blocks = -(-t["rows_per_feed"] // self.cfg["parsa"]["block_size"])
        tb = self.cfg["stream"]["tb_pad"]
        self.ctx.log(f"feed: {t['rows_per_feed']} rows in {blocks} blocks; "
                     f"truncation bucket {tb}, channel "
                     f"{blocks * tb * W * 4} bytes per feed")

    def _feed(self):
        i = len(self.fed) % len(self.graphs)
        with self.ctx.annotate("feed"):
            upd = self.session.feed(self.graphs[i])
        self.fed.append(i)
        return upd

    def window(self, seconds: float) -> None:
        start = time.perf_counter()
        end = start
        while end - start < seconds:
            upd = self._feed()
            end = time.perf_counter()
            self.feeds.append({"rows": upd.u_stop - upd.u_start,
                               "timings": dict(upd.timings), "end": end})
        self.window_s = end - start
        wraps = (len(self.fed) - 1) // len(self.graphs)
        self.ctx.log(f"window: {len(self.feeds)} feeds, "
                     f"{sum(f['rows'] for f in self.feeds)} rows in "
                     f"{self.window_s:.3f} s; pool of {len(self.graphs)} "
                     f"chunks wrapped {wraps} times")

    def end_to_end(self) -> dict:
        rows = sum(f["rows"] for f in self.feeds)
        return {"partition_rate": rows / self.window_s}

    def release(self) -> None:
        """Keep the answers on the host and free the device state."""
        s = self.session
        self.parts = s.parts.copy()
        self.words = s.arena.masks_np()
        self.sizes = np.asarray(s.arena.sizes).astype(np.int64)
        self.session = None
        del s

    def check(self) -> list:
        t0 = time.perf_counter()
        cfg, p = self.cfg, self.cfg["parsa"]
        workers = (p["workers"] if p["backend"] == "parallel_device" else 1)
        ref = StreamReference(cfg["k"], self.num_v, p["block_size"],
                              p["cap"], self.ctx.seed, workers=workers)
        parts = np.concatenate([ref.feed(*self.pool[i]) for i in self.fed])
        misplaced = int((parts != self.parts).sum()) + abs(
            parts.shape[0] - self.parts.shape[0])
        n = self.traffic["rows_per_feed"]
        for j in range(len(self.fed)):
            if (parts[j * n:(j + 1) * n] != self.parts[j * n:(j + 1) * n]
                    ).any():
                self.bad_feeds += 1
        bits = int((_unpack(self.words, self.num_v) != ref.sets).sum())
        sizes = int(np.abs(self.sizes - ref.sizes).sum())
        self.ctx.log(f"reference: {parts.shape[0]} rows replayed in "
                     f"{time.perf_counter() - t0:.3f} s; most truncated rows "
                     f"in one block {ref.max_tb} (bucket "
                     f"{cfg['stream']['tb_pad']})")
        return [{"name": "rows_misplaced", "value": misplaced, "limit": 0},
                {"name": "set_bits_differ", "value": bits, "limit": 0},
                {"name": "sizes_differ", "value": sizes, "limit": 0}]

    def counts(self) -> tuple[int, int]:
        return len(self.fed), self.bad_feeds

    def record(self, summary) -> dict:
        return {"kind": "stream", "window_s": self.window_s,
                "feeds": self.feeds, "trace": summary}
