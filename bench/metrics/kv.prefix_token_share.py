"""Share of prompt tokens served from the prefix cache instead of being
computed, in percent, over the requests due in the window."""


def read(run):
    prompt = sum(r.prompt_len for r in run.requests)
    cached = sum(r.cached_prefix_tokens or 0 for r in run.requests)
    return 100.0 * cached / prompt if prompt else None
