"""The suite's own shared pieces (``harness.py``): the clock every test
runs under, the shared model and the kept oracle."""

import signal
import time

import numpy as np
import pytest

import paddle_tpu as paddle

from harness import (fresh_model, per_test_clock, shared_model,
                     solo_generate)


def test_clock_fails_the_test_by_name_and_disarms():
    """Past its limit a block fails with the test's name, and the timer
    and handler found on entry — here the ones ``conftest.py`` armed for
    this test — are back afterwards."""
    outer_handler = signal.getsignal(signal.SIGALRM)
    outer_left = signal.getitimer(signal.ITIMER_REAL)[0]
    assert outer_left > 0                      # conftest's clock is armed
    with pytest.raises(pytest.fail.Exception,
                       match=r"tests/x\.py::test_sleeps ran past"):
        with per_test_clock("tests/x.py::test_sleeps", 0.05):
            time.sleep(5)
    assert signal.getsignal(signal.SIGALRM) is outer_handler
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= outer_left
    time.sleep(0.1)                            # the short timer is gone


def test_clock_is_silent_inside_the_limit():
    with per_test_clock("tests/x.py::test_quick", 5):
        pass
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 5   # conftest's


def test_shared_model_leaves_the_generator_as_a_fresh_build_does():
    a = shared_model()
    x = np.asarray(paddle.randn([4])._value)
    b = shared_model()
    y = np.asarray(paddle.randn([4])._value)
    assert a is b
    np.testing.assert_array_equal(x, y)
    fresh = fresh_model()
    z = np.asarray(paddle.randn([4])._value)
    assert fresh is not a
    np.testing.assert_array_equal(x, z)
    for (n, p), (_, q) in zip(a.named_parameters(),
                              fresh.named_parameters()):
        np.testing.assert_array_equal(np.asarray(p._value),
                                      np.asarray(q._value), err_msg=n)


def test_kept_oracle_matches_generate_and_hands_out_copies():
    m = shared_model()
    p = np.arange(1, 8, dtype=np.int32)
    want = np.asarray(m.generate(paddle.to_tensor(p[None, :]),
                                 max_new_tokens=3,
                                 temperature=0.0)._value)[0]
    got = solo_generate(m, p, 3)
    np.testing.assert_array_equal(got, want)
    got[:] = 0                                  # a caller's scribble
    np.testing.assert_array_equal(solo_generate(m, p, 3), want)
    # same bytes, other dtype: not the same prompt
    np.testing.assert_array_equal(
        solo_generate(m, p.astype(np.int64), 3)[:7], p)
