"""`benchmark/tests/test_expert_rooflines.py`'s 42 cases (what the expert
rooflines count, PR 45; no JAX, a second), counted in tier-1."""
from benchmark.tests.test_expert_rooflines import *  # noqa: F401,F403
