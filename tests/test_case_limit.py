"""The limit every case runs under (``tests/conftest.py::case_limit``)."""

import signal
import time

import pytest

from conftest import case_limit


def test_a_case_over_its_limit_fails_by_name_and_the_alarm_goes_back():
    handler, (outer, _) = signal.getsignal(signal.SIGALRM), signal.getitimer(signal.ITIMER_REAL)
    assert outer > 500                      # this case runs under the limit of every case
    with pytest.raises(pytest.fail.Exception, match="a sleeper was still running .* 0.2 s"):
        with case_limit(0.2, "a sleeper"):
            time.sleep(1)
    with case_limit(5):
        assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= 5
    # cleared: the limit around this case has the alarm again, and its time
    assert signal.getsignal(signal.SIGALRM) is handler
    assert outer - 2 < signal.getitimer(signal.ITIMER_REAL)[0] <= outer
