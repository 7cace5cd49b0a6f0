"""In-process messaging bus (the paper's Kafka analogue).

Topic-based pub/sub with the same role Kafka plays in DynIMS: decouple
monitoring agents, the stream processor, and the memory controller.  Two
consumption styles, matching Kafka's consumer groups:

* callback subscription (``subscribe``) -- push, used by the aggregator,
  one call per message or, with ``batch=True``, one call per published
  batch,
* bounded per-topic retention + cursors (``poll``) -- pull, used by tests
  and by slow consumers.

``publish_many`` publishes a list of messages under one lock: the log,
offsets, retention and every ``poll`` cursor end as if each message had
been published in order, and per-message subscribers still get every
message, one call each, in order.

Thread-safe; publishing never blocks on slow subscribers (exceptions in a
callback are recorded, not propagated -- a monitoring plane must not take
down the data plane).
"""

from __future__ import annotations

import threading
from collections import defaultdict, deque
from typing import Any, Callable, Dict, List, Sequence, Tuple


class MessageBus:
    def __init__(self, retention: int = 4096):
        self._lock = threading.RLock()
        self._retention = retention
        self._log: Dict[str, deque] = defaultdict(   # guarded-by: _lock
            lambda: deque(maxlen=retention))
        self._offsets: Dict[str, int] = defaultdict(int)  # guarded-by: _lock
        self._subs: Dict[str, List[Tuple[Callable[[Any], None], bool]]] = \
            defaultdict(list)                        # guarded-by: _lock
        self._cursors: Dict[Tuple[str, str], int] = {}  # guarded-by: _lock
        self.errors: List[Tuple[str, Exception]] = []   # guarded-by: _lock

    # -- producer side ---------------------------------------------------
    def publish(self, topic: str, message: Any) -> None:
        self.publish_many(topic, [message])

    def publish_many(self, topic: str, messages: Sequence[Any]) -> None:
        """Publish ``messages`` in order, under one lock.

        A per-message subscriber gets each message in its own call, a
        batch subscriber the whole list in one call; subscribers are
        called in the order they subscribed."""
        messages = list(messages)
        if not messages:
            return
        with self._lock:
            self._log[topic].extend(messages)
            self._offsets[topic] += len(messages)
            subs = list(self._subs[topic])
        for fn, batch in subs:
            for arg in ([messages] if batch else messages):
                try:
                    fn(arg)
                except Exception as exc:  # monitoring must not crash data plane
                    with self._lock:
                        self.errors.append((topic, exc))

    # -- push consumers ----------------------------------------------------
    def subscribe(self, topic: str, fn: Callable[[Any], None],
                  batch: bool = False) -> Callable[[], None]:
        """Register a callback; returns an unsubscribe handle.

        With ``batch=True`` the callback gets a list of the messages of
        one ``publish`` or ``publish_many`` call instead of one message."""
        entry = (fn, batch)
        with self._lock:
            self._subs[topic].append(entry)

        def unsubscribe() -> None:
            with self._lock:
                try:
                    self._subs[topic].remove(entry)
                except ValueError:
                    pass
        return unsubscribe

    # -- pull consumers ----------------------------------------------------
    def poll(self, topic: str, group: str = "default", max_items: int = 256) -> List[Any]:
        """Return messages this consumer group has not seen yet."""
        with self._lock:
            log = self._log[topic]
            total = self._offsets[topic]
            first_retained = total - len(log)
            cursor = self._cursors.get((topic, group), 0)
            cursor = max(cursor, first_retained)
            start = cursor - first_retained
            out = list(log)[start:start + max_items]
            self._cursors[(topic, group)] = cursor + len(out)
            return out

    def depth(self, topic: str) -> int:
        with self._lock:
            return len(self._log[topic])
