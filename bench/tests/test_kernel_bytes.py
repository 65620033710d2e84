"""The byte count behind ``msbfs_roofline``, against a hand count."""
from kernel_bytes import index_bytes, msbfs_sweep_bytes


def test_sweep_bytes_by_hand():
    # n=10 vertices, m=20 valid arcs, 40 sources -> W=2 words, 3 hops:
    # ELL entries 20*4=80, frontier words 20*2*4=160, visited and new
    # words 3*10*2*4=240, count table 2*64*10=1280: 1760 bytes a hop
    assert msbfs_sweep_bytes(10, 20, 40, 3) == 3 * 1760


def test_one_word_up_to_32_sources():
    assert msbfs_sweep_bytes(10, 20, 1, 1) == msbfs_sweep_bytes(10, 20, 32, 1)
    assert msbfs_sweep_bytes(10, 20, 33, 1) > msbfs_sweep_bytes(10, 20, 32, 1)


def test_index_is_both_sweeps():
    assert index_bytes(10, 20, 40, 5, 3) == (msbfs_sweep_bytes(10, 20, 40, 3)
                                             + msbfs_sweep_bytes(10, 20, 5, 3))
