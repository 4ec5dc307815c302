"""The limit ``tests/conftest.py`` puts on every case."""

import signal
import time

import pytest

import conftest


def test_a_case_that_sleeps_past_its_limit_fails_and_the_next_is_clean(
    monkeypatch, request
):
    """Past the limit the sleeper gets ``TimeoutError`` with its own name;
    afterwards no alarm is pending, the handler is the one that was there,
    and the next arming counts from the whole limit again."""
    mine = signal.getsignal(signal.SIGALRM)   # the autouse fixture's
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.CASE_LIMIT_S
    monkeypatch.setattr(conftest, "CASE_LIMIT_S", 0.2)
    with pytest.raises(TimeoutError, match="sleeper waited past 0.2 s"):
        with conftest.limited("sleeper"):
            time.sleep(5)
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is mine
    time.sleep(0.3)     # nothing is left to go off
    monkeypatch.undo()
    with conftest.limited(request.node.nodeid):
        left, _ = signal.getitimer(signal.ITIMER_REAL)
        assert conftest.CASE_LIMIT_S - 1 < left <= conftest.CASE_LIMIT_S
        assert signal.getsignal(signal.SIGALRM) is not mine
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
