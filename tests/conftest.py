import faulthandler
import os
import shutil
import signal
import sys
import tempfile

import pytest

# Tests run on the real device set (1 CPU device) — the 512-device
# XLA_FLAGS override belongs to launch/dryrun.py ONLY.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.compile_cache import use_classic_cpu_emitters  # noqa: E402

use_classic_cpu_emitters()

# Every phase of a test (setup, call, teardown) gets DEFAULT_TIME_LIMIT
# seconds unless it carries @pytest.mark.time_limit(seconds, reason=...).
# At the limit SIGALRM fails the test with its traceback.  A Python
# handler only runs between bytecodes, so a test stuck in native code
# (one long XLA compile, a hung runtime) is stopped HARD_STOP_AFTER
# seconds later: faulthandler prints every thread's stack and ends the
# process.  Under xdist the worker is replaced, and the test is failed
# rather than run again when its file is handed to the new worker.
DEFAULT_TIME_LIMIT = 120.0
HARD_STOP_AFTER = 60.0
COMPILE_HEAVY = pytest.mark.time_limit(
    300, reason="compiles several large programs: 50-150 s a phase in a "
    "full tier-1 run on 8 CPU cores")
_RUNNING_ENV = "HOBFLOPS_TEST_RUNNING_DIR"
_state = {}


def count_primitives(jx, name):
    """Occurrences of primitive ``name`` in a jaxpr, recursing into
    nested jaxprs (pjit bodies, scan/fori carriers).  Shared by the
    one-encode/one-decode invariant tests."""
    n = 0
    for e in jx.eqns:
        if str(e.primitive) == name:
            n += 1
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    n += count_primitives(getattr(inner, "jaxpr", inner),
                                          name)
    return n


def pytest_configure(config):
    # faulthandler needs a file that outlives pytest's capture of fd 2.
    capman = config.pluginmanager.getplugin("capturemanager")
    if capman is not None:
        with capman.global_and_fixture_disabled():
            fd = os.dup(2)
    else:
        fd = os.dup(2)
    _state["stderr"] = os.fdopen(fd, "w")
    _state["old_alarm"] = signal.signal(signal.SIGALRM, _on_alarm)
    workerinput = getattr(config, "workerinput", None)
    if workerinput is not None:
        _state["running"] = os.path.join(
            workerinput[_RUNNING_ENV], workerinput["workerid"])


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    """xdist (controller side): one directory where each worker notes
    the test it is running, read back when a worker dies."""
    if "running_dir" not in _state:
        _state["running_dir"] = tempfile.mkdtemp(prefix="pytest-running-")
    node.workerinput[_RUNNING_ENV] = _state["running_dir"]


def pytest_unconfigure(config):
    signal.signal(signal.SIGALRM, _state.pop("old_alarm", signal.SIG_DFL))
    if "running_dir" in _state:
        shutil.rmtree(_state.pop("running_dir"), ignore_errors=True)


def _expired():
    return (f"{_state['phase']} ran past its time limit of "
            f"{_state['limit']:g} s")


def _on_alarm(signum, frame):
    _state["expired"] = True
    pytest.fail(_expired())


def _time_limit(item):
    mark = item.get_closest_marker("time_limit")
    if mark is None:
        return DEFAULT_TIME_LIMIT
    assert mark.kwargs.get("reason"), \
        f"{item.nodeid}: time_limit needs a reason="
    return float(mark.args[0])


def _left_running_by_dead_worker(nodeid):
    running = _state.get("running")
    if running is None:
        return False
    folder = os.path.dirname(running)
    for name in os.listdir(folder):
        path = os.path.join(folder, name)
        if path == running:
            continue
        try:
            with open(path) as f:
                if f.read() == nodeid:
                    return True
        except FileNotFoundError:   # that worker ended its phase meanwhile
            pass
    return False


def _limited(item, phase):
    limit = _time_limit(item)
    _state["phase"], _state["limit"] = f"{item.nodeid} ({phase})", limit
    if "running" in _state:
        with open(_state["running"], "w") as f:
            f.write(item.nodeid)
    faulthandler.dump_traceback_later(limit + HARD_STOP_AFTER, exit=True,
                                      file=_state["stderr"])
    _state["expired"] = False
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        # The alarm fires at most once; if it lands here, the inner
        # finally still disarms the hard stop.
        try:
            signal.setitimer(signal.ITIMER_REAL, 0)
        finally:
            faulthandler.cancel_dump_traceback_later()
            if "running" in _state:
                os.remove(_state["running"])
    if _state["expired"]:
        # The alarm landed where exceptions are only reported (a gc or
        # weakref callback), so the phase itself did not fail.
        pytest.fail(_expired())


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    if _left_running_by_dead_worker(item.nodeid):
        pytest.fail(f"{item.nodeid} ran past its hard time limit in a "
                    "worker that was stopped (its stacks are printed "
                    "above); not run again")
    yield from _limited(item, "setup")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    yield from _limited(item, "call")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    yield from _limited(item, "teardown")
