"""Runs of a cell with the timed path broken underneath read not correct:
a token altered where it is produced, and a step that leaves the page
pools unchanged.  The harness's look for a chip is skipped; the rest of
the run (engine thread, clients, reference) is the benchmark's own."""

import pytest

import tiny
from test_bench_reference import LIMIT


@pytest.mark.parametrize("n_kv_heads", [2, 1])
@pytest.mark.parametrize("fault", sorted(tiny.FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, n_kv_heads, tmp_path):
    res = tiny.run("bert-cam.decode", tmp_path, fault=fault, limit=LIMIT,
                   n_kv_heads=n_kv_heads)
    assert res["correct"] is False
    assert res["checks"]["mean_logit_gap"]["value"] > LIMIT, res["checks"]
