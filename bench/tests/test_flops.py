"""FLOP and byte counts against counts made by hand at the two
configurations (batch 1024, fanouts (25, 10): frontiers of 1,024, 26,624
and 292,864 rows)."""
from bench import flops


def test_frontier_sizes():
    assert flops.frontier_sizes(1024, (25, 10)) == [1024, 26624, 292864]


def test_sage_products_by_hand():
    # layer 1 reads hop 1: 26,624 nodes x 10 neighbours, f 100 -> 256
    agg1 = 26624 * 10 * 100
    mm1 = 2 * 26624 * 200 * 256
    bias1 = 26624 * 256
    # layer 2 reads hop 0: 1,024 targets x 25 neighbours, f 256 -> 47
    agg2 = 1024 * 25 * 256
    mm2 = 2 * 1024 * 512 * 47
    bias2 = 1024 * 47
    forward = agg1 + mm1 + bias1 + agg2 + mm2 + bias2
    backward = (mm1 + bias1) + (mm2 + bias2) + (mm2 + agg2)
    assert flops.train_flops("sage", (100, 256, 47), (25, 10), 1024) \
        == forward + backward == 5_653_903_360


def test_gcn_papers100m_by_hand():
    agg1 = 2 * 26624 * 10 * 128 + 2 * 26624 * 128
    mm1 = 2 * 26624 * 128 * 256
    bias1 = 26624 * 256
    agg2 = 2 * 1024 * 25 * 256 + 2 * 1024 * 256
    mm2 = 2 * 1024 * 256 * 172
    bias2 = 1024 * 172
    forward = agg1 + mm1 + bias1 + agg2 + mm2 + bias2
    backward = (mm1 + bias1) + (mm2 + bias2) + (mm2 + agg2)
    assert flops.train_flops("gcn", (128, 256, 172), (25, 10), 1024) \
        == forward + backward == 3_876_413_440


def test_combine_bytes():
    # 512 accelerator targets: 146,432 positions of 100 float32 features,
    # each written once and read once
    assert flops.combine_bytes(512 * 286, 100) == 2 * 146432 * 100 * 4
