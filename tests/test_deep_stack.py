"""utils/deep_stack.py: the serving thread's frames get one roomy chunk of
CPython's frame stack (the reason is in the module's docstring)."""

import sys
import threading

import pytest

from flexflow_tpu.utils.deep_stack import SLOTS, with_deep_stack


def _roomy_frames():
    f, out = sys._getframe(1), []
    while f is not None:
        if f.f_code.co_stacksize >= SLOTS:
            out.append(f.f_code.co_name)
        f = f.f_back
    return out


def test_calls_through_a_frame_with_the_room_and_hands_the_result_back():
    assert with_deep_stack(lambda: (_roomy_frames(), 7)) == (["_call"], 7)
    assert _roomy_frames() == []


def test_an_exception_passes_through():
    def boom():
        raise KeyError("x")

    with pytest.raises(KeyError):
        with_deep_stack(boom)


def test_deep_recursion_beneath_it_still_works():
    def depth(n):
        return 0 if n == 0 else 1 + depth(n - 1)

    assert with_deep_stack(lambda: depth(600)) == 600


def test_the_serving_thread_runs_beneath_it(monkeypatch):
    from flexflow_tpu.serve import api

    seen = {}

    def fake_run(self):
        seen["frames"] = _roomy_frames()
        seen["thread"] = threading.current_thread().name

    monkeypatch.setattr(api._BackgroundServer, "_run", fake_run)
    srv = api._BackgroundServer(llm=None)
    srv.start()
    srv._thread.join(10)
    assert seen == {"frames": ["_call"], "thread": "flexflow-serve"}
