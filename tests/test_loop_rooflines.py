"""`benchmark/tests/test_shapes_loop.py`'s and `test_readers_loop.py`'s
cases (what a looped stack's roofline counts, PR 53; seconds), counted in
tier-1."""
from benchmark.tests.test_readers_loop import *  # noqa: F401,F403
from benchmark.tests.test_shapes_loop import *  # noqa: F401,F403
