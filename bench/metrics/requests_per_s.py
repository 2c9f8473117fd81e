"""Requests completed per second: every request of the batches that
started within the window, over the time from the first of them to start
to the last of them to end."""


def read(run):
    bs = run.window.counted
    if not bs:
        return None
    span = max(b.end for b in bs) - min(b.start for b in bs)
    return sum(len(b.requests) for b in bs) / span
