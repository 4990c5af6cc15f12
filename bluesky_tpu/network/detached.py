"""Detached node: the Node interface with no networking
(parity: bluesky/network/detached.py:7-50).

For embedding the TPU sim in other Python programs (tests, notebooks,
batch scripts): events are delivered by direct calls, streams collected in
a buffer the host program may drain.
"""
import time

from ..utils.timer import Timer
from .common import make_id


class Node:
    def __init__(self, *args, **kwargs):
        self.node_id = make_id()
        self.host_id = make_id()
        self.running = False
        self.streams = []         # [(name, data)] drained by the embedder

    def connect(self):
        pass

    def close(self):
        pass

    def quit(self):
        self.running = False

    def send_event(self, name: bytes, data=None, route=None):
        # loop server-bound events straight back into the handler
        self.event(name, data, [self.node_id])

    def send_stream(self, name: bytes, data):
        self.streams.append((name, data))

    def event(self, name: bytes, data, sender_route):
        pass

    def step(self):
        pass

    def event_wait_ms(self) -> int:
        """How long the coming turn of ``run`` waits: not at all for a
        node with work of its own.  SimNode answers
        ``common.IDLE_WAIT_MS`` while its sim is not stepping."""
        return 0

    def poll(self, timeout_ms: int) -> int:
        """There is no socket and nothing can end the wait: sleep it
        out, and no event has come."""
        if timeout_ms:
            time.sleep(timeout_ms * 1e-3)
        return 0

    def process_events(self, timeout_ms: int = 0) -> int:
        return 0

    def run(self):
        self.running = True
        while self.running:
            self.poll(self.event_wait_ms())
            self.step()
            Timer.update_timers()
