"""The benchmark harness's three seams, counted in tier-1.

``cellbench/tests/test_seam.py`` holds the checks (a configuration finds
its adapter by ``model_type``, a metric finds its reduction by name, a
cell's engine settings come from its files, and GPT-2's numbers behind the
adapter are the ones they were). They need no window and no model, so they
run here too: a PR that breaks the seam by which an architecture comes in
fails tier-1, not only the benchmark's own suite.
"""

from cellbench.tests.test_seam import *  # noqa: F401,F403
