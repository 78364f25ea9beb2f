"""Operation and byte counts against hand counts."""
import bench_tiny  # noqa: F401  (puts the repository root on the path)
from bench.flops import mips_topk as M
from bench.flops import qwen2 as Q

TINY = {"hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 512}


def test_qwen2_tiny_by_hand():
    # q and o: 2*64*64 each; k and v: 2*64*32 each; biases (4+2+2)*16;
    # gate, up, down: 3 * 2*64*128
    assert Q.dense_per_token(TINY) == 8192 + 8192 + 8192 + 128 + 49152
    assert Q.attention_per_token(TINY, 5) == 2 * 2 * 4 * 16 * 5
    assert Q.head_per_token(TINY) == 2 * 64 * 512
    # a 3-token prompt: keys 1, 2, 3; the head once
    assert Q.prompt_flops(TINY, 3) == 2 * (3 * 73856 + 256 * 6) + 65536
    # the token at position 3 attends 4 keys
    assert Q.decode_flops(TINY, 3) == 2 * (73856 + 256 * 4) + 65536
    # two output tokens: the prompt's forward gives the first
    assert Q.request_flops(TINY, 3, 2) == 511744 + 215296
    assert Q.request_flops(TINY, 3, 1) == 511744
    assert Q.total_flops(TINY, [(3, 2), (3, 1)]) == 727040 + 511744


def test_qwen2_7b_layer_is_twice_its_parameters():
    cfg = {"hidden_size": 3584, "intermediate_size": 18944,
           "num_attention_heads": 28, "num_key_value_heads": 4,
           "head_dim": 128, "num_hidden_layers": 16,
           "vocab_size": 152064}
    params = (3584 * 3584 * 2 + 3584 * 512 * 2 + (28 + 8) * 128
              + 3 * 3584 * 18944)
    assert Q.dense_per_token(cfg) == 2 * params - (28 + 8) * 128
    assert Q.head_per_token(cfg) == 2 * 3584 * 152064


def test_mips_topk_by_hand():
    # the committed index: 131,072-row buffer of 768 + 3 flag columns
    assert M.ops(64, 131072, 771) == 2 * 64 * 131072 * 771
    assert M.bytes_moved(64, 131072, 771, 8) == \
        404_226_048 + 4 * 64 * 771 + 8 * 64 * 8
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = M.least_seconds(64, 131072, 771, 8, peaks)
    assert bound == "bytes"
    assert abs(t - 404_427_520 / 819e9) < 1e-12
    # a compute-heavy shape flips the bound
    assert M.least_seconds(4096, 100_000, 64, 8, peaks)[1] == "ops"
