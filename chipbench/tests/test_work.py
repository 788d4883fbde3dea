"""work.py against operations and bytes counted by hand for one small
shape: hidden 8, 2 query heads / 1 kv head of 4, ffn 16, vocab 32, 1 layer."""

from chipbench import work

CFG = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 1,
       "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
       "vocab_size": 32}


def test_layer_and_head_parameters():
    # wq 8x8, wk 8x4, wv 8x4, wo 8x8, gate/up/down 3 x 8x16
    assert work.layer_matmul_params(CFG) == 64 + 32 + 32 + 64 + 384
    assert work.head_params(CFG) == 256
    assert work.weight_bytes(CFG) == 2 * (576 + 256)


def test_causal_attention_operations():
    # 3 positions see 1 + 2 + 3 = 6 keys; q.K^T and p.V are 2*4 flops a key
    # a head each, 2 heads: 6 * 2 * (2*4 + 2*4) = 192
    assert work.attn_flops_causal(CFG, 3) == 192
    # with 1 position cached: positions 1, 2 see 2 + 3 = 5 keys
    assert work.attn_flops_causal(CFG, 3, cached=1) == 5 * 32
    assert work.attn_flops_token(CFG, 5) == 5 * 32


def test_train_step():
    # batch 2 x seq 3: matmuls 2 * 6 tokens * (576 + 256) = 9984, attention
    # 2 rows * 192 = 384; forward + backward = 3 x
    assert work.train_step_flops(CFG, 2, 3) == 3 * (9984 + 384)
    flops, nbytes = work.flash_train_work(CFG, 2, 3)
    assert flops == 3 * 2 * 192
    q, kv = 2 * 3 * 2 * 4 * 2, 2 * 3 * 1 * 4 * 2        # bytes of q, of k
    assert nbytes == (2 * q + 2 * kv) + (4 * q + 4 * kv)


def test_request():
    # prompt 3, 2 generated: 4 tokens through the layer, the head twice,
    # attention over positions 0..3 = 10 keys
    assert work.request_flops(CFG, 3, 0, 2) == (2 * 4 * 576 + 2 * 2 * 256
                                                + 10 * 32)
    flops, nbytes = work.request_attn_work(CFG, 3, 0, 2)
    kv, qo = 2 * 1 * 4 * 2, 2 * 2 * 4 * 2
    assert flops == 10 * 32
    # prefill reads 3 tokens' k,v once and its q/o; the one decode step
    # reads 4 tokens' k,v and its own q/o
    assert nbytes == 3 * kv + 3 * qo + 4 * kv + qo
    assert work.decode_kv_bytes(CFG, 3, 2) == 4 * kv
    assert work.request_flops(CFG, 3, 0, 1) == 2 * 3 * 576 + 2 * 256 + 6 * 32


def test_least_seconds_names_the_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000, 10, peaks) == (10.0, "compute")
    assert work.least_seconds(10, 1000, peaks) == (100.0, "memory")
