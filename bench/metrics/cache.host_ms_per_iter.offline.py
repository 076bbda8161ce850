"""Host time per scheduler iteration in the paged cache's bookkeeping,
in ms: the engine's `pages.grow` (page growth, evictions, preemptions),
`pages.table` (block-table uploads) and `pages.check` (page accounting)
spans of the iterations wholly inside the traced window, over their
number."""
from bench import hostspans

PAGES = ("pages.grow", "pages.table", "pages.check")


def read(obs):
    spans = hostspans.scheduler(obs)
    iters = hostspans.iterations(spans, obs.window)
    pages = [s for s in spans if s.name in PAGES]
    if not iters or not pages:
        return None
    return 1e3 * sum(s.end - s.start for s in pages
                     if hostspans.within(s, iters)) / len(iters)
