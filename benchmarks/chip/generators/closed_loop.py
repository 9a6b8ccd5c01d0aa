"""Closed-loop clients: each sends its next request the moment its last one
finished.

Parameters (the traffic file): ``clients``, ``prompt_len`` (tokens drawn
uniformly over the vocabulary), ``max_new_tokens``.  Request ``i`` of
client ``c`` depends only on (seed, stream, c, i), so the same seed gives
the same requests whatever the timing.
"""
from __future__ import annotations

import numpy as np

STREAMS = {"window": 0, "warmup": 1}


class Generator:
    def __init__(self, mix: dict, seed: int, vocab: int,
                 stream: str = "window"):
        self.clients = int(mix["clients"])
        self.prompt_len = int(mix["prompt_len"])
        self.max_new_tokens = int(mix["max_new_tokens"])
        self.vocab = int(vocab)
        self.seed = int(seed) % (1 << 64)
        self.stream = STREAMS[stream]
        self.sent = [0] * self.clients

    def _prompt(self, client: int, index: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, self.stream, client, index])
        return rng.integers(0, self.vocab, self.prompt_len, dtype=np.int32)

    def _request(self, client: int) -> dict:
        i = self.sent[client]
        self.sent[client] += 1
        return {"rid": (self.stream << 40) | (client << 24) | i,
                "client": client,
                "prompt": self._prompt(client, i),
                "max_new_tokens": self.max_new_tokens}

    def start(self) -> list:
        """The requests sent when the window opens: one per client."""
        return [self._request(c) for c in range(self.clients)]

    def due(self, elapsed: float) -> list:
        """Requests due by ``elapsed`` seconds regardless of replies (an
        open loop's arrivals): none here."""
        return []

    def on_finish(self, request: dict) -> list:
        """The finished request's client sends its next one."""
        return [self._request(request["client"])]
