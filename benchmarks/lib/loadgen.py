"""The closed-loop load generator and the arithmetic on what it saw.

``clients`` threads share one queue of requests (the traffic file's
schedule, ``lib.traffic.requests``) and each waits for its reply before
taking the next item. The threads only wait on sockets. Every token gets
the client's clock at the moment it was received; rates and tails are
computed from those stamps afterwards, over the window alone.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import numpy as np


@dataclasses.dataclass
class Record:
    client: int
    prompt: np.ndarray
    want: int                       # output tokens asked for
    t_free: float                   # when the client's last reply ended
    t_send: float = 0.0
    stamps: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False              # every token asked for arrived
    error: str | None = None


class ClosedLoop:
    """``send(prompt, n)`` returns an iterator of token ids (a streaming
    client call). ``first_outputs[c]`` caps client ``c``'s FIRST request,
    which staggers the streams so that they do not march in lock-step."""

    def __init__(self, make_sender, source, clients: int, first_outputs):
        self._make_sender, self._source = make_sender, source
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.records: list[Record] = []
        self._threads = [threading.Thread(
            target=self._client, args=(c, first_outputs[c]), daemon=True)
            for c in range(clients)]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def _next(self, client: int, t_free: float, cap) -> Record:
        with self._lock:
            prompt, want = next(self._source)
            rec = Record(client, prompt, want if cap is None
                         else min(want, cap), t_free)
            self.records.append(rec)
        return rec

    def _client(self, c: int, first_cap: int) -> None:
        with self._make_sender() as send:
            cap, t_free = first_cap, time.perf_counter()
            while not self._stop.is_set():
                rec = self._next(c, t_free, cap)
                cap = None
                stream = None
                try:
                    rec.t_send = time.perf_counter()
                    stream = send(rec.prompt, rec.want)
                    for tok in stream:
                        rec.stamps.append(time.perf_counter())
                        rec.tokens.append(int(tok))
                        # after the close only a first token is waited for
                        if self._stop.is_set():
                            break
                    rec.done = len(rec.tokens) == rec.want
                except Exception as e:          # counted as a failure
                    rec.error = f"{type(e).__name__}: {e}"
                finally:
                    if stream is not None:
                        stream.close()          # cancels what is left
                t_free = time.perf_counter()

    def completed(self) -> list[int]:
        """Requests finished so far, by client."""
        n = [0] * len(self._threads)
        with self._lock:
            for r in self.records:
                n[r.client] += r.done
        return n

    def close(self, timeout: float = 60.0) -> bool:
        self._stop.set()
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(deadline - time.monotonic(), 0.0))
        return not any(t.is_alive() for t in self._threads)


# -- arithmetic on the records -------------------------------------------------

def tokens_in_window(records, t0: float, t1: float) -> int:
    """Every output token stamped inside ``[t0, t1]``, whichever request
    it belongs to: requests in flight at either edge count for the tokens
    that fell inside."""
    return sum(1 for r in records for s in r.stamps if t0 <= s <= t1)


def inter_token_gaps(records, t0: float, t1: float) -> list[float]:
    """Gaps between consecutive tokens of one stream, every stream, whose
    later token fell inside the window."""
    return [b - a for r in records
            for a, b in zip(r.stamps, r.stamps[1:]) if t0 <= b <= t1]


def first_token_times(records, t0: float, t1: float) -> list[float]:
    """Send -> first token of every request sent inside the window."""
    return [r.stamps[0] - r.t_send for r in records
            if t0 <= r.t_send <= t1 and r.stamps]


def lateness(records, t0: float, t1: float) -> list[float]:
    """How long after its previous reply ended a client sent its next
    request (a closed loop's due time)."""
    return [r.t_send - r.t_free for r in records if t0 <= r.t_send <= t1]


def percentile(values, q: float):
    return float(np.percentile(values, q)) if len(values) else None
