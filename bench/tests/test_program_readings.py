"""Readers of what the program records itself, on synthetic runs."""
import types

import pytest

from bench.harness import read_metric


def _run(admitted, end=10.0):
    """Requests due at 0; ``admitted`` their first admissions (None: never
    admitted); the probe saw the same admissions."""
    reqs = [types.SimpleNamespace(req_id=i, arrival_time=0.0,
                                  first_admit_time=t)
            for i, t in enumerate(admitted)]
    probe = types.SimpleNamespace(admitted={
        i: t for i, t in enumerate(admitted) if t is not None})
    return types.SimpleNamespace(requests=reqs, end=end, probe=probe)


@pytest.mark.parametrize("admitted, want", [
    ([0.5] * 20, 0.5),
    ([float(i) for i in range(20)], 18.05),
    # the one never admitted waits to the end, 10 s: 0.05 of the way there
    ([0.0] * 19 + [None], 0.5),
])
def test_admit_wait_p95_reads_first_admission_like_the_probe(admitted, want):
    run = _run(admitted)
    got = read_metric("sched.admit_wait_p95_s", run)
    assert got == pytest.approx(want)
    assert got == pytest.approx(read_metric("sched.queue_wait_p95_s", run))


def test_admit_wait_p95_is_absent_without_first_admission_times():
    run = _run([0.5] * 4)
    for r in run.requests:
        del r.first_admit_time
    assert read_metric("sched.admit_wait_p95_s", run) is None
