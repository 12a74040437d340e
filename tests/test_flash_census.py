"""``tile_census`` against a brute-force count over the causal mask."""

import numpy as np
import pytest

CENSUS_CASES = [
    # sq, sk, block_q, block_k, q_offset, kv_offset
    (1024, 1024, 512, 512, 0, 0),
    (4096, 4096, 512, 512, 0, 0),
    (512, 512, 128, 128, 64, 0),
    (512, 512, 128, 128, 0, 64),
    (512, 512, 128, 128, 512, 0),
    (512, 512, 128, 128, 0, 512),
    (128, 512, 128, 128, 0, 0),
    (512, 128, 64, 128, 0, 0),
    (384, 768, 128, 256, 100, 37),
    (768, 384, 256, 128, 37, 100),
    (192, 192, 128, 128, 0, 0),       # blocks fitted to 96
    (256, 1024, 64, 512, 300, 0),
    (2048, 2048, 512, 512, 2048, 4096),
]


@pytest.mark.parametrize("sq,sk,bq,bk,q_off,kv_off", CENSUS_CASES)
def test_tile_census_matches_the_mask(sq, sk, bq, bk, q_off, kv_off):
    from horovod_tpu.ops import flash_attention as fa

    seen = ((q_off + np.arange(sq))[:, None]
            >= (kv_off + np.arange(sk))[None, :])
    fq, fk = fa._check_blocks(sq, sk, bq, bk, True)  # as the kernels fit
    tiles = seen.reshape(sq // fq, fq, sk // fk, fk)
    every, some = tiles.all(axis=(1, 3)), tiles.any(axis=(1, 3))
    assert fa.tile_census(sq, sk, bq, bk, True, q_off, kv_off) == {
        "skipped": int((~some).sum()), "full": int(every.sum()),
        "crossed": int((some & ~every).sum())}
    fq, fk = fa._check_blocks(sq, sk, bq, bk)
    assert fa.tile_census(sq, sk, bq, bk, False, q_off, kv_off) == {
        "skipped": 0, "full": (sq // fq) * (sk // fk), "crossed": 0}


def test_tile_census_of_the_benchmark_cells():
    from horovod_tpu.ops.flash_attention import tile_census

    assert tile_census(16384, 16384, 512, 512, True) == {
        "skipped": 496, "full": 496, "crossed": 32}
    assert tile_census(1024, 1024, 512, 512, True) == {
        "skipped": 1, "full": 1, "crossed": 2}
