"""The ``timeout`` mark is enforced by ``conftest.time_limit``: a case
that outlasts its seconds fails with a stack; one that does not is left
alone, and the enclosing limit (the hook's own, around these cases) and
its handler are put back either way."""

import signal
import time

import pytest
from conftest import DEFAULT_TIMEOUT_S, time_limit


def test_a_block_that_outlasts_its_limit_fails(capfd):
    usual = signal.getsignal(signal.SIGALRM)
    with pytest.raises(pytest.fail.Exception, match="ran past its 0.05 s"):
        with time_limit(0.05, "a sleeper"):
            time.sleep(5)
    assert "most recent call first" in capfd.readouterr().err
    assert signal.getsignal(signal.SIGALRM) is usual
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= DEFAULT_TIMEOUT_S


def test_a_block_inside_its_limit_is_left_alone():
    usual = signal.getsignal(signal.SIGALRM)
    with time_limit(5):
        time.sleep(0.01)
    assert signal.getsignal(signal.SIGALRM) is usual
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= DEFAULT_TIMEOUT_S
