"""Warm routing of ``BatchedSWAG.stream`` for the baseline algorithms
(recalc, two-stacks, two-stacks lite): chunked ≡ per-element from live
windows across ragged chunk splits.  The paper's worst-case O(1)
algorithms and the routing edge cases are in test_warm_stream.py."""

import pytest

from repro.core import CONSTANT_TIME_ALGORITHMS, GENERAL_ALGORITHMS

from warm_cases import MONOID_CASES, check_warm_stream_matches_per_element

BASELINES = sorted(set(GENERAL_ALGORITHMS) - set(CONSTANT_TIME_ALGORITHMS))


@pytest.mark.parametrize("mname", sorted(MONOID_CASES))
@pytest.mark.parametrize("algo_name", BASELINES)
def test_warm_stream_chunked_matches_per_element(algo_name, mname):
    check_warm_stream_matches_per_element(algo_name, mname)
