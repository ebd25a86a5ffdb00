"""The limit tests/conftest.py gives every test (`TEST_LIMIT_S`): a test
that runs past it fails alone, and the run goes on."""

import os
import subprocess
import sys
import textwrap

CONFTEST = os.path.join(os.path.dirname(__file__), "conftest.py")


def test_a_test_past_its_limit_fails_alone_and_the_next_one_runs(tmp_path):
    """A run of two tests under this repo's hook with the limit patched to
    0.2 s: the one that sleeps fails with its node id in the message, the
    one after it passes."""
    (tmp_path / "conftest.py").write_text(textwrap.dedent(f"""
        import importlib.util

        spec = importlib.util.spec_from_file_location("the_conftest",
                                                      {CONFTEST!r})
        the_conftest = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(the_conftest)
        the_conftest.TEST_LIMIT_S = 0.2
        pytest_runtest_call = the_conftest.pytest_runtest_call
    """))
    (tmp_path / "test_two.py").write_text(textwrap.dedent("""
        import time

        def test_sleeps():
            time.sleep(30)

        def test_the_next_one():
            pass
    """))
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", "--rootdir", str(tmp_path),
         "-c", os.devnull, str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=tmp_path)
    assert run.returncode == 1, run.stdout + run.stderr
    assert "1 failed, 1 passed" in run.stdout, run.stdout
    assert "test_two.py::test_sleeps ran past 0.2 s" in run.stdout, run.stdout
