"""Reference graph of ``repro.core.presets.attention_block``."""

from harness.reference import matmul


def build(d_model, head_dim, n_heads, n_kv_heads, seq):
    """QKV projection -> QK^T -> scores x V -> output projection, one head
    wide for the score matmuls; edges carry each output into the next
    matmul's left operand."""
    qkv = (n_heads + 2 * n_kv_heads) * head_dim
    nests = [matmul(seq, qkv, d_model), matmul(seq, seq, head_dim),
             matmul(seq, head_dim, seq), matmul(seq, d_model,
                                                n_heads * head_dim)]
    return nests, [(0, 1, "C", "A"), (1, 2, "C", "A"), (2, 3, "C", "A")]
