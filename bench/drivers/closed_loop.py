"""Closed-loop serving: a fixed number of clients, each sending its next
request the moment its previous one finishes, so the queue never empties
and the engine runs above its knee. Judged on tokens completed per
second."""
from __future__ import annotations

from bench import generate, timeline


class Source:
    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.stream = generate.Stream(traffic, seed, vocab)
        self.clients = int(traffic["clients"])
        self.i = 0

    def start(self, rec, T0: float):
        self.rec = rec
        self.free = [T0] * self.clients        # when each free client sends
        self.seen = 0

    def pump(self, now: float):
        fins = self.rec.finished_rids
        while self.seen < len(fins):           # a finished request frees
            self.free.append(self.rec.finish[fins[self.seen]][0])
            self.seen += 1
        while self.free:
            due = self.free.pop()
            self.rec.submit(self.stream[self.i], due)
            self.i += 1

    def next_due(self) -> None:
        return None


def end_to_end(rec, W0: float, W1: float) -> dict:
    return {"output_tok_s": timeline.rate(
        timeline.tokens_in_window(rec.token_times, W0, W1), W0, W1)}

