"""`benchmark/tests/test_readers_setup.py`'s cases (the six readers of a
replica's set-up account on canned files, PR 56; seconds), counted in
tier-1."""
from benchmark.tests.test_readers_setup import *  # noqa: F401,F403
