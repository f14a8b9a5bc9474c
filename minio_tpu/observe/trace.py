"""The internal/pubsub equivalent: subscribers attach and detach
dynamically, publish fans an item out to whoever is attached.  The one
trace plane that publishes through it is observe/span.py (a request's
root span carries what cmd/http-tracer.go's TraceInfo did: status,
sizes, source address); bucket/notify.py streams events through its
own instance."""

from __future__ import annotations

import threading
from collections import deque


class PubSub:
    def __init__(self):
        self._mu = threading.Lock()
        self._subs: list[deque] = []

    def subscribe(self, maxlen: int = 1000) -> deque:
        q: deque = deque(maxlen=maxlen)
        with self._mu:
            self._subs.append(q)
        return q

    def unsubscribe(self, q: deque) -> None:
        with self._mu:
            try:
                self._subs.remove(q)
            except ValueError:
                pass

    def publish(self, item) -> None:
        with self._mu:
            subs = list(self._subs)
        for q in subs:
            q.append(item)

    @property
    def num_subscribers(self) -> int:
        with self._mu:
            return len(self._subs)
