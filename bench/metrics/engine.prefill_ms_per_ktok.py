"""Host milliseconds in prefill calls per thousand prompt tokens the
engine computed (padding included: it is what the engine computes)."""


def read(run):
    p = run.probe
    return p.prefill_s * 1e6 / p.prefill_tokens if p.prefill_tokens else None
