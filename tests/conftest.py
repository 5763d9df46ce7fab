import faulthandler
import hashlib
import os
import shutil
import sys
import tempfile

import pytest

# Tests run single-device (the dry-run sets its own device count in
# subprocesses); keep CPU determinism.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Hard limit on one test's call, in seconds: several times the slowest
# test under a loaded machine.  A test stuck past it (say, inside an XLA
# run that cannot be interrupted) dumps every thread's stack and ends
# its process, which fails the test instead of stalling the whole run.
TEST_TIME_LIMIT_S = 300

_stderr_fd_key = pytest.StashKey[int]()
_started_dir_key = pytest.StashKey[str]()


def pytest_configure(config):
    # a copy of stderr taken while output capture is off, so that the
    # stack dump of a test over its limit reaches the terminal
    config.stash[_stderr_fd_key] = os.dup(2)


def pytest_unconfigure(config):
    os.close(config.stash[_stderr_fd_key])
    if _started_dir_key in config.stash:
        shutil.rmtree(config.stash[_started_dir_key], ignore_errors=True)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    """xdist, on the controller: every worker of the run shares one
    directory of started-test markers."""
    stash = node.config.stash
    if _started_dir_key not in stash:
        stash[_started_dir_key] = tempfile.mkdtemp(prefix="pytest-started-")
    node.workerinput["started_dir"] = stash[_started_dir_key]


def _started_marker(item):
    """A file that exists while ``item`` runs, shared by the xdist
    workers of one run (None outside xdist)."""
    d = getattr(item.config, "workerinput", {}).get("started_dir")
    if d is None:
        return None
    return os.path.join(d, hashlib.sha1(item.nodeid.encode()).hexdigest())


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    marker = _started_marker(item)
    if marker is not None:
        # `--dist loadfile` hands a crashed worker's file, the test that
        # ended it included, to its replacement: fail that test there
        # instead of running it into the limit again
        if os.path.exists(marker):
            pytest.fail(f"an earlier worker ended in this test (over "
                        f"{TEST_TIME_LIMIT_S} s, or a crash)", pytrace=False)
        open(marker, "w").close()
    faulthandler.dump_traceback_later(
        TEST_TIME_LIMIT_S, exit=True, file=item.config.stash[_stderr_fd_key])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        if marker is not None:
            os.remove(marker)
