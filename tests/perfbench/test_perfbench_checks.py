"""Check 3's arithmetic: the differences it reads and its verdict."""

import glob
import json
import os

import pytest

from perfbench import checks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_ranked_diff_compares_kth_largest_whatever_the_token():
    a = {"x": -3.0, "y": -3.5, "z": -4.0}
    b = {"z": -3.02, "q": -3.4, "x": -4.05}
    assert checks.ranked_diff(a, b) == pytest.approx(0.1)
    assert checks.ranked_diff({}, {}) == float("inf")


@pytest.mark.parametrize("diffs,far", [
    ([0.01, 0.02, 0.03, 0.50, 0.90], False),   # two of five in the tail
    ([0.01, 0.02, 0.13, 0.50, 0.90], True),    # three of five over it
    ([0.12] * 5, False),                        # on the tolerance
    ([0.01, 0.02, float("inf"), float("inf"), float("inf")], True),
    ([0.01, 0.02, float("nan"), float("nan"), float("nan")], True),
])
def test_verdict_is_the_median_of_the_prompts(diffs, far):
    assert checks.too_far(sorted(diffs), 0.12) is far


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(ROOT, "perfbench", "configs", "*", "deployment.json"))
    + [os.path.join(ROOT, "perfbench", "rehearsal", "deployment.json")]
))
def test_every_deployment_states_its_tolerance(path):
    with open(path) as f:
        tol = json.load(f)["prefill_vs_cache_tol"]
    # under the least a wrong answer's median of five read on the chip
    assert 0 < tol <= 0.12
