"""Requests first: background work of this process stands back while a
request is being served.

One GIL serves every Python thread. A numpy call that lets it go costs
the caller 35-130 us whenever another thread waits for it (PERF.md
section 7, PR 38), and a served read makes a thousand such calls: a
background thread that is merely runnable beside it — the block cache's
fill decoding whole blocks (storage/block_cache.py) — cost a 12-hour
panel 40 ms of its 30 (PERF.md section 6, PR 39). So the HTTP front
marks a request's whole stretch (`serving`), and background work asks
for a quiet moment before each piece (`wait_quiet`): at most `timeout_s`,
so that a server that is never quiet still gets its background work
done, slowly."""

from __future__ import annotations

import threading

_cv = threading.Condition()
_serving = 0


class _Serving:
    __slots__ = ()

    def __enter__(self):
        global _serving
        with _cv:
            _serving += 1

    def __exit__(self, *exc):
        global _serving
        with _cv:
            _serving -= 1
            if not _serving:
                _cv.notify_all()
        return False


serving = _Serving()    # `with foreground.serving:` around a request


def wait_quiet(timeout_s: float) -> bool:
    """Block until no request is being served, `timeout_s` at most;
    whether it was quiet."""
    with _cv:
        return _cv.wait_for(lambda: not _serving, timeout_s)
