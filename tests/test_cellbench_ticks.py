"""The benchmark's split of the serving tick, counted in tier-1.

``cellbench/tests/test_readers_ticks.py`` holds the checks: the four
reductions of ``cellbench/readers_ticks.py`` on hand-built tuples with
known answers, on a trace recorded on a v5e, and on a tiny engine traced
on the CPU. They need no chip, so they run here too: a PR that renames
``tick.prefill`` or drops its ``tokens`` attribute fails tier-1, not only
the benchmark's own suite.
"""

from cellbench.tests.test_readers_ticks import *  # noqa: F401,F403
