"""The plain reference: exact, independent of the program, and strict."""
import numpy as np
import pytest

from chipbench import reference

C = (218, 164, 131, 70)     # CodeSpec.make(4, 257).c
P = 257


def stripe(rng, w=512):
    a = rng.integers(0, 256, (8, w)).astype(np.int64)
    return a, reference.encode(C, a, P)


def test_reference_matches_the_program_encode():
    from repro.core.circulant import CodeSpec
    from repro.core.msr import DoubleCirculantMSR

    assert tuple(CodeSpec.make(4, P).c) == C
    rng = np.random.default_rng(0)
    a, r = stripe(rng)
    prog = np.asarray(DoubleCirculantMSR(CodeSpec.make(4, P)).encode(
        a.astype(np.int32)))
    assert np.array_equal(prog, r)


def test_clean_stripe_has_no_mismatch():
    rng = np.random.default_rng(1)
    a, r = stripe(rng)
    assert reference.stripe_mismatches(C, a, r, P, rng) == 0


@pytest.mark.parametrize("block", ["data", "redundancy"])
def test_corrupted_stripe_is_rejected(block):
    rng = np.random.default_rng(2)
    a, r = stripe(rng)
    (a if block == "data" else r)[3, 17] ^= 1
    assert reference.stripe_mismatches(C, a, r, P, rng) > 0


def test_byte_symbols_read_as_wrong():
    # the control: the field's value 256 stored in one byte becomes 0
    rng = np.random.default_rng(3)
    a, r = stripe(rng, w=4096)
    assert (r == 256).any()
    assert reference.stripe_mismatches(C, a, r, P, rng, symbol_bits=8) > 0


def test_any_k_decode_inverts_encode():
    rng = np.random.default_rng(4)
    a, r = stripe(rng)
    nodes = [0, 3, 5, 6]
    assert np.array_equal(reference.decode(C, nodes, a[nodes], r[nodes], P), a)


def test_block_layout_pads_the_tail():
    blocks = reference.bytes_to_blocks(np.arange(10, dtype=np.uint8), 4)
    assert blocks.shape == (4, 3)
    assert blocks.reshape(-1).tolist() == list(range(10)) + [0, 0]
