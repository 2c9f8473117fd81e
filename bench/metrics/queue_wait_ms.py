"""Queue and batcher: mean over requests of their batch's wall-clock
start minus their due time."""


def read(run):
    w = run.window
    waits = [b.start - w.due[rid] for b in w.counted
             for rid, _, _ in b.requests]
    return 1e3 * sum(waits) / len(waits) if waits else None
