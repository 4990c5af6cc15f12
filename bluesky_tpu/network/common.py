"""Shared network helpers (parity: bluesky/network/common.py:4-15).

Endpoint ids are 5 random bytes with a leading zero byte so they can never
collide with single-character control tokens like ``b'*'``.
"""
import os
import socket

# Reference defaults (network/server.py:20-23): client event/stream ports,
# worker event/stream ports, UDP discovery port.
DEFAULT_PORTS = dict(event=9000, stream=9001,
                     wevent=10000, wstream=10001, discovery=11000)

# The idle loop's pace: a worker that is not stepping waits this long for
# an event (Node: on its socket, and an event ends the wait; detached.Node:
# asleep) before it turns its loop again, so stack commands queued by a
# timer or a plugin, wall-clock timers, the watchdog's beat and the failover
# check run some 50 times a second and the processor is given up between.
IDLE_WAIT_MS = 20


def make_id() -> bytes:
    """A 5-byte endpoint id: zero byte + 4 random bytes (node.py:15)."""
    return b"\x00" + os.urandom(4)


def get_ownip() -> str:
    """Best-effort non-loopback IPv4 of this host."""
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("10.255.255.255", 1))
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"
