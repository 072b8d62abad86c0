"""The time limit of `tests/conftest.py`, tested on itself: an inner pytest run
loads the same hooks with the limit cut to a fraction of a second."""

import pathlib
import re
import subprocess
import sys

import pytest

CONFTEST = pathlib.Path(__file__).with_name("conftest.py")

INNER_CONFTEST = f"""
import importlib.util
spec = importlib.util.spec_from_file_location("repo_conftest", {str(CONFTEST)!r})
repo_conftest = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo_conftest)
repo_conftest.TEST_TIME_LIMIT_S = 0.4
pytest_runtest_setup = repo_conftest.pytest_runtest_setup
pytest_runtest_call = repo_conftest.pytest_runtest_call
pytest_runtest_teardown = repo_conftest.pytest_runtest_teardown
"""

# Each of the first four never ends by itself; the marker comments are what the
# reported stack must show.
INNER_TESTS = """
import asyncio
import time


def test_busy_loop():
    while True: pass  # noqa: E701  stood-here: busy_loop


def test_spinning_coroutine():
    # the shape of the hang this limit was written for: an awaitable that
    # returns at once, so the coroutine never hands control back to the loop
    async def closed():
        return None

    async def spin():
        while True:
            await closed()  # stood-here: spinning_coroutine

    asyncio.new_event_loop().run_until_complete(spin())


def test_yielding_coroutine():
    async def spin():
        while True:
            await asyncio.sleep(0)  # stood-here: yielding_coroutine

    asyncio.new_event_loop().run_until_complete(spin())


def test_blocking_sleep():
    time.sleep(3600)  # stood-here: blocking_sleep


def test_next_one_runs():
    assert True
"""

HANGS = ["busy_loop", "spinning_coroutine", "yielding_coroutine", "blocking_sleep"]


@pytest.fixture(scope="module")
def inner_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("time_limit")
    (root / "conftest.py").write_text(INNER_CONFTEST)
    (root / "test_inner.py").write_text(INNER_TESTS)
    done = subprocess.run(
        [sys.executable, "-m", "pytest", str(root), "-v", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "--rootdir", str(root)],
        capture_output=True, text=True, timeout=120, cwd=root)
    return done.stdout + done.stderr


@pytest.mark.parametrize("hang", HANGS)
def test_hang_fails_as_a_time_out_with_its_stack(inner_run, hang):
    assert re.search(rf"test_inner\.py::test_{hang} FAILED", inner_run), inner_run
    # the failure's own section names the limit and the line the test stood on
    section = inner_run.split(f"_ test_{hang} _", 1)[1].split("\n____", 1)[0]
    assert "call passed the time limit of 0.4 s" in section, section
    assert f"stood-here: {hang}" in section, section


def test_the_next_test_in_the_same_process_runs(inner_run):
    assert "test_inner.py::test_next_one_runs PASSED" in inner_run, inner_run
    assert f"{len(HANGS)} failed, 1 passed" in inner_run, inner_run
