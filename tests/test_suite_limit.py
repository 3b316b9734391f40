"""The suite's own limit on a test (``tests/conftest.py``)."""
import signal
import time

import pytest

from conftest import TEST_LIMIT_S, time_limit


@pytest.mark.skipif(not hasattr(signal, "SIGALRM"), reason="no SIGALRM")
def test_a_body_past_its_limit_fails_by_name():
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"tests/x\.py::test_y ran past its limit of "
                             r"0\.2 s"):
        with time_limit(0.2, "tests/x.py::test_y"):
            time.sleep(30)
    assert time.monotonic() - began < 5
    # this test's own limit (the autouse fixture's) is armed again, and a
    # body inside its limit is left alone
    left, _every = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= TEST_LIMIT_S
    with time_limit(5, "tests/x.py::test_y"):
        time.sleep(0.01)
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 5
