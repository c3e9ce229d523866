"""Op and byte counts from shapes, against hand-worked numbers."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.counts import (ota_round, ota_shard_tx, round_linreg_pop,  # noqa
                          round_mlp)


def test_ota_round_small():
    # U = 2, D = 3: candidates 4*6 = 24; per candidate 3*6 + 12*3 = 54,
    # twice = 108; transmit 18*6 = 108; descale 9 -> 249 FLOPs.
    # bytes: w 6 + |w|, eta, noise 9 + per-worker 10 + outputs 15 = 40 f32
    assert ota_round.counts(2, 3) == {"flops": 249.0, "bytes": 160.0}


def test_ota_round_at_the_mlp_cell():
    # U = 20, D = 50,890: 1,017,800 + 8 * 50,890 + 100 = 1,425,020 f32
    assert ota_round.counts(20, 50890)["bytes"] == 4 * 1425020


def test_ota_shard_tx_at_the_population_cell():
    # U_b = 1,000 workers, D = 3: 18 * 3,000 FLOPs; w 3,000 + 7,000
    # per-worker + 2 * 3 in + 4 * 3 out = 10,018 f32
    assert ota_shard_tx.counts(1000, 3) == {"flops": 54000.0,
                                            "bytes": 40072.0}


@pytest.mark.parametrize("policy,flops", [("perfect", 34.0),
                                          ("random", 114.0)])
def test_round_mlp_small(policy, flops):
    # U = k_b = n_test = 1, 2-1-1 net: P = 3 weights, D = 5 params.
    # local 6 P = 18, eval 2 P = 6; aggregate: perfect 2 U D = 10,
    # random 18 U D = 90.  Bytes: sample 3 + test 3 + 4 D = 26 f32.
    got = round_mlp.counts(1, 1, 2, 1, 1, 1, 5, policy)
    assert got == {"flops": flops, "bytes": 104.0}


def test_round_mlp_inflota_adds_the_search():
    base = round_mlp.counts(20, 16, 784, 64, 10, 2000, 50890, "perfect")
    inf = round_mlp.counts(20, 16, 784, 64, 10, 2000, 50890, "inflota")
    assert inf["flops"] - base["flops"] == (
        ota_round.counts(20, 50890)["flops"] - 2 * 20 * 50890)
    assert inf["bytes"] == base["bytes"]


def test_round_linreg_pop_small():
    # U = 2, one real sample each, k_max = 2, D = 1: local 15 * 2 = 30;
    # search 2 U log2 U + U + 9 U D = 4 + 2 + 18 = 24; transmit 36;
    # descale 3 -> 93.  Bytes: 3 * U * k_max + U + 4 D = 18 f32.
    assert round_linreg_pop.counts(2, 1.0, 2, 1) == {"flops": 93.0,
                                                     "bytes": 72.0}
