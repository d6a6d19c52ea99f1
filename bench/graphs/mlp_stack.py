"""Reference graph of ``repro.core.presets.mlp_stack``: one dense gated
MLP of width ``d_ff``."""

from harness.reference import matmul


def build(d_model, d_ff, seq):
    """Gate and up projections feeding the down projection."""
    nests = [matmul(seq, d_ff, d_model), matmul(seq, d_ff, d_model),
             matmul(seq, d_model, d_ff)]
    return nests, [(0, 2, "C", "A"), (1, 2, "C", "B")]


def program_cfg(widths):
    """The library's builder takes an expert width where ``n_experts`` is
    set; the configuration names one dense expert of width ``d_ff``."""
    return dict(d_model=widths["d_model"], d_ff=widths["d_ff"], n_experts=0,
                expert_ff=widths["d_ff"])
