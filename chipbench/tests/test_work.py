"""Byte counts of the GF operations, from logical shapes."""
from chipbench import work


def test_encode_reads_and_writes_n_blocks():
    assert work.encode(8, 4096) == 2 * 8 * 4096


def test_regenerate_reads_k_plus_one_writes_a_pair():
    # r_{i-1} and k data blocks in, the node's (a, r) pair out
    assert work.regenerate(4, 1000) == 5 * 1000 + 2 * 1000
