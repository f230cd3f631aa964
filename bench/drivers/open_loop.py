"""Open-loop serving: independent users, each request sent at its due time
on the seed's Poisson schedule at the mix's fixed rate, whatever the
engine is doing. Latency counts from the due time, so a stall shows in
every request that waited behind it."""
from __future__ import annotations

from bench import generate, timeline


class Source:
    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.stream = generate.Stream(traffic, seed, vocab)
        self.i = 0

    def start(self, rec, T0: float):
        self.rec, self.T0 = rec, T0

    def pump(self, now: float):
        while self.T0 + self.stream[self.i].due <= now:
            r = self.stream[self.i]
            self.rec.submit(r, self.T0 + r.due)
            self.i += 1

    def next_due(self) -> float:
        return self.T0 + self.stream[self.i].due


def end_to_end(rec, W0: float, W1: float) -> dict:
    ttft = timeline.ttfts(rec.due, {r: ts[0] for r, ts in
                                    rec.token_times.items() if ts}, W0, W1)
    tpots = [x for x in (timeline.tpot(ts, W0, W1)
                         for ts in rec.token_times.values()) if x is not None]
    out = {"output_tok_s": timeline.rate(
        timeline.tokens_in_window(rec.token_times, W0, W1), W0, W1)}
    if ttft:
        out["ttft_ms_p90"] = 1e3 * timeline.percentile(ttft.values(), 0.9)
    if tpots:
        out["tpot_ms_p90"] = 1e3 * timeline.percentile(tpots, 0.9)
    return out

