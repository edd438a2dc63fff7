"""Operation and byte counts against hand counts at a toy size."""
import flops

TOY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 16, "vocab_size": 32,
       "tie_word_embeddings": True, "qkv_bias": True, "param_dtype": "float32"}


def test_dense_decode_step_tied_head():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 576 matmul weights
    # step: 2 layers + head 8*32 = 1408; ops 2*3*1408 + attention 2*(4*3*2*4*5) = 9408
    # weights: layers 2*(576 + norms 16 + biases 16) + final norm 8 + head 256 = 1480
    # K/V: 2 layers * (K and V) * 3 seqs * 1 head * 4 * (5 live + 1 new) * 2 B = 576
    ops, nbytes = flops.dense_decode_step(TOY, batch=3, live=5)
    assert ops == 9408
    assert nbytes == 1480 * 4 + 576


def test_dense_decode_step_separate_head_reads_the_looked_up_rows():
    untied = dict(TOY, tie_word_embeddings=False, qkv_bias=False)
    ops, nbytes = flops.dense_decode_step(untied, batch=3, live=5)
    assert ops == 9408
    assert nbytes == (2 * (576 + 16) + 8 + 256 + 3 * 8) * 4 + 576
