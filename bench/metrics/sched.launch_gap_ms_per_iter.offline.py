"""Host time per scheduler iteration during which no engine program is
queued on the device, in ms, from the engine's hand-off spans: for each
`chunk.dispatch` and `step.dispatch` of an iteration wholly inside the
traced window, if the last `chunk.wait` or `step.fetch` before it ended
after the previous dispatch had returned, the time from that sync's end
to the dispatch's return; summed, over the iterations."""
from bench import hostspans

DISPATCH = ("chunk.dispatch", "step.dispatch")
SYNC = ("chunk.wait", "step.fetch")


def read(obs):
    spans = hostspans.scheduler(obs)
    iters = hostspans.iterations(spans, obs.window)
    if not iters or not any(s.name in DISPATCH for s in spans):
        return None
    total, synced, prev = 0.0, None, None
    for s in spans:
        if s.name in SYNC:
            synced = s.end
        elif s.name in DISPATCH:
            if synced is not None and (prev is None or synced > prev) \
                    and hostspans.within(s, iters):
                total += s.end - synced
            prev = s.end
    return 1e3 * total / len(iters)
