"""The benchmark's own tests of ``benchmarks/compare.py``, collected by the
tier-1 command as well: every verdict on a PR rests on that code."""

import pytest

pytest.register_assert_rewrite("benchmarks.tests.test_compare")

from benchmarks.tests.test_compare import *  # noqa: E402,F401,F403
