"""The limit ``tests/conftest.py`` puts on every test, held to its word on a
one-file session of its own: a test that hangs fails by name with every
thread's stack, and the session goes on to the next test and ends."""

import sys
import textwrap
from pathlib import Path

import pytest

from tests.helpers import run_child

CONFTEST = Path(__file__).with_name("conftest.py")

SHORTENED = f"""
import importlib.util
import sys

spec = importlib.util.spec_from_file_location("repo_conftest", {str(CONFTEST)!r})
repo_conftest = sys.modules["repo_conftest"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo_conftest)
repo_conftest.TEST_LIMIT_S, repo_conftest.HARD_LIMIT_S = 1.0, 4.0
from repo_conftest import pytest_configure, pytest_runtest_protocol  # noqa
"""

HANGS = {
    # where Python can be interrupted the handler fails the test itself
    "python": "time.sleep(600)",
    # where it cannot (native code; here the signal is blocked) the process
    # exits at the hard limit and xdist reports the worker down by the test
    "native": ("signal.pthread_sigmask(signal.SIG_BLOCK, [signal.SIGALRM])\n"
               "time.sleep(600)"),
}


@pytest.mark.parametrize("where,flags,said", [
    ("python", [], ["over the limit of 1 s", "1 failed, 1 passed"]),
    ("native", ["-p", "xdist", "-n", "1"],
     ["node down", "crashed while running", "1 failed, 1 passed"])],
    ids=["python", "native"])
def test_a_hung_test_fails_by_name_and_the_session_goes_on(
        where, flags, said, tmp_path):
    (tmp_path / "conftest.py").write_text(SHORTENED)
    (tmp_path / "test_probe.py").write_text(
        "import signal\nimport time\n\n\ndef test_hangs():\n"
        + textwrap.indent(HANGS[where], "    ")
        + "\n\n\ndef test_comes_after():\n    pass\n")
    run = run_child(
        [sys.executable, "-m", "pytest", str(tmp_path), "-q", "-p",
         "no:cacheprovider", "--rootdir", str(tmp_path), *flags],
        timeout=120, cwd=tmp_path)
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    for line in [*said, "test_probe.py::test_hangs",
                 # the stacks: the frame that hangs, under the thread's header
                 "most recent call first", "in test_hangs"]:
        assert line in out, out
