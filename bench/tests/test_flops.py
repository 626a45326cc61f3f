"""bench/flops.py against counts made by hand."""
from bench import flops, manifest
from bench.tests.conftest import ROOT


def test_causal_pairs():
    assert flops.causal_pairs(4, 0) == 1 + 2 + 3 + 4
    assert flops.causal_pairs(6, 2) == 1 + 2 + 2 + 2 + 2 + 2
    assert flops.causal_pairs(4096, 4096) == 4096 * 4097 // 2


def test_starcoder2_by_hand():
    cell = manifest.load_cell(ROOT, "starcoder2-3b-L1.h2.1chip")
    got = flops.per_example(cell.conf, cell.traffic)
    # wq 3072x3072, wk and wv 3072x256, wo 3072x3072, wi 3072x12288,
    # wo 12288x3072, and the tied head 3072x49152
    layer = 9_437_184 + 786_432 + 786_432 + 9_437_184 + 37_748_736 * 2
    head = 150_994_944
    assert got["matmul_params"] == layer + head == 246_939_648
    # 24 heads of 128; a causal 4096-token sequence has 8,390,656 pairs
    attn = 12 * 24 * 128 * 8_390_656
    assert got["flops"] == 6 * 246_939_648 * 4096 + attn
    assert got["tokens"] == 4096


def test_vit_by_hand():
    cell = manifest.load_cell(ROOT, "vit-b16.h4.1chip")
    got = flops.per_example(cell.conf, cell.traffic)
    patch = 768 * 768                         # 16*16*3 -> 768
    layer = 4 * 768 * 768 + 2 * 768 * 3072    # q, k, v, o and the MLP
    body = patch + 12 * layer
    assert body == 85_524_480
    head = 768 * 1000
    attn = 12 * 12 * 768 * 196 * 196          # 196 patches see all 196
    assert got["flops"] == 6 * body * 196 + 6 * head + attn
    assert got["tokens"] == 196
    assert abs(got["flops"] - 1.0484e11) / 1.0484e11 < 1e-3
