"""The per-test wall-clock limit of tests/conftest.py, which needs no plugin."""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

CONFTEST = Path(__file__).resolve().with_name("conftest.py")


def test_limit_fails_the_slow_test_and_the_run_goes_on(tmp_path):
    (tmp_path / "conftest.py").write_text(CONFTEST.read_text())
    (tmp_path / "test_sleepy.py").write_text(
        "import time\n"
        "import pytest\n"
        "\n"
        "@pytest.mark.timeout(1)\n"
        "def test_sleeps_past_its_limit():\n"
        "    time.sleep(30)\n"
        "\n"
        "def test_after_it():\n"
        "    pass\n"
    )
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist", "test_sleepy.py"],
        cwd=tmp_path, capture_output=True, text=True, timeout=25,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "test exceeded its 1 s time limit" in out.stdout
    assert "test_sleeps_past_its_limit FAILED" in out.stdout
    assert "test_after_it PASSED" in out.stdout
    assert "1 failed, 1 passed" in out.stdout
