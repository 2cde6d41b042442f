"""Window accounting: straddling steps, requests in flight at the close,
failed against unfinished."""
import pytest

from gfbench import window


def _run(steps=(), requests=None, stop=12.0):
    return {"window": {"w0": 1.0, "w1": 11.0, "seconds": 10.0},
            "stop": stop, "steps": list(steps),
            "requests": requests or {}, "worker_errors": 0,
            "collective_timeouts": 0}


def _step(start, finish, tokens=100, kind="denoise", failed=False):
    return {"kind": kind, "start": start, "finish": finish,
            "duration": finish - start, "tokens": tokens, "rows": 1,
            "failed": failed}


def _req(due, done=None, failed=False):
    return {"due": due, "done": done, "failed": failed}


@pytest.mark.parametrize("start,finish,share", [
    (0.0, 2.0, 0.5), (2.0, 4.0, 1.0), (10.0, 14.0, 0.25),
    (11.5, 12.0, 0.0), (0.0, 12.0, 10 / 12)])
def test_bench_straddling_steps_count_by_share(start, finish, share):
    assert window.inside(start, finish, 1.0, 11.0) == pytest.approx(share)


def test_bench_rate_counts_shares_and_skips_failed_and_other_kinds():
    run = _run([_step(0.0, 2.0), _step(2.0, 4.0), _step(10.0, 14.0),
                _step(3.0, 4.0, kind="decode"), _step(4, 5, failed=True)])
    steps = window.denoise_steps(run)
    assert sum(s["tokens"] * s["share"] for s in steps) == \
        pytest.approx(100 * (0.5 + 1 + 0.25))
    assert window.ms_per_ktoken(run) == pytest.approx(
        1000 * (2 * 0.5 + 2 + 4 * 0.25) / (0.175))


def test_bench_in_flight_at_close_is_not_failed_but_late():
    reqs = {"a": _req(2.0, done=5.0),
            "b": _req(9.0, done=None),                    # in flight
            "c": _req(3.0, failed=True),
            "d": _req(11.5, done=12.0)}                   # due after close
    run = _run(requests=reqs, stop=20.0)
    assert window.attempted(run) == 3
    assert window.failed(run) == 1
    assert window.unfinished(run) == 1


def test_bench_failed_counts_worker_errors_and_timeouts():
    run = _run(requests={"a": _req(2.0, failed=True)})
    run["worker_errors"], run["collective_timeouts"] = 2, 1
    assert window.failed(run) == 4
