"""Host milliseconds in the ranker per request scored (arrival scoring and
re-ranking refreshes together)."""


def read(run):
    p = run.probe
    return p.scorer_s * 1e3 / p.scored if p.scored else None
