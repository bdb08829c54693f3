"""Shim for the one optional dependency the launchers share.

``pick_unused_port``: portpicker when installed, else a socket-based
fallback (bind port 0, read back the assignment). The fallback has a
marginally wider race window than portpicker's reservation protocol,
which is acceptable for the local-runner/test uses it serves. The
module stays jax-free: runners and the forked test harness import it
from a parent that must not initialise a JAX backend.
"""

from __future__ import annotations


def pick_unused_port() -> int:
    try:
        import portpicker

        return portpicker.pick_unused_port()
    except ImportError:
        import socket

        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as sock:
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind(("127.0.0.1", 0))
            return sock.getsockname()[1]
