"""The yardstick's operation and byte counts against hand counts."""
from bench import flops


def test_mlp_train_flops_hand_count():
    # layers 2x3 and 3x4: 18 multiply-adds; forward 36, weight grads 36,
    # input grads only for the second layer: 24
    assert flops.mlp_train_flops([2, 3, 4], batch=1) == 96.0
    assert flops.mlp_train_flops([2, 3, 4], batch=8) == 768.0


def test_mlp_train_flops_cell_size():
    dims = [512, 2048, 2304, 2048, 10]
    macs = 512 * 2048 + 2048 * 2304 + 2304 * 2048 + 2048 * 10
    assert macs == 10_506_240
    want = 8 * (4 * macs + 2 * (macs - 512 * 2048))
    assert flops.mlp_train_flops(dims, batch=8) == want
    assert sum(flops.mlp_tensor_sizes(dims)) == 10_512_650


def test_commit_apply_bytes_sparse_and_dense():
    sizes = [1000, 10]
    # density 0.01: k = 10 + 1; commit and apply each read index and
    # value and read and write the target: 16 bytes an entry, twice
    assert flops.commit_apply_bytes(sizes, 0.01, 3) == 3 * 2 * 11 * 16
    # dense: commit reads M, writes v; apply reads theta and G, writes
    assert flops.commit_apply_bytes(sizes, None, 2) == 2 * 1010 * 4 * 5


def test_mamba2_780m_flops_per_token_hand_count():
    cfg = {"d_model": 1536, "n_layers": 48, "vocab_size": 50280,
           "ssm": {"d_state": 128, "d_conv": 4, "expand": 2,
                   "head_dim": 64, "n_groups": 1, "chunk": 256}}
    d_in, H, N, P, Q = 3072, 48, 128, 64, 256
    proj = 1536 * (2 * 3072 + 2 * 128 + 48) + 3072 * 1536
    conv = 4 * (3072 + 256)
    ssd = 2 * Q * N + 2 * Q * P * H + 4 * N * P * H
    want = 48 * (6 * proj + 6 * conv + 3 * ssd) + 6 * 1536 * 50280
    assert flops.mamba2_train_flops_per_token(cfg, seq=1024) == want
    # about 4.9 GFLOP a token: 6 x 780M parameters plus the scan
    assert 4.5e9 < want < 5.5e9
    # a sequence shorter than the chunk shrinks the intra-chunk terms
    assert flops.mamba2_train_flops_per_token(cfg, seq=128) < want
