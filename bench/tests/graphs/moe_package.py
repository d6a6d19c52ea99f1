"""A test graph of the shape of one expert-parallel package of a
DeepSeek-V2 expert layer (arXiv:2405.04434; the published
``config.json`` widths below): the router, the shared experts as one
gated MLP of width ``n_shared_experts * moe_intermediate_size``, and
gate, up and down of each routed expert the package holds, each routed
expert over the tokens a seeded group-limited top-k draw sends it.  It
sizes the reference's check for a graph of this many workloads; it is
not a configuration's graph."""

import numpy as np

from harness.reference import matmul


def routed_tokens(seq, n_routed_experts, num_experts_per_tok, n_group,
                  topk_group, seed):
    """Tokens per routed expert under ``group_limited_greedy``: each
    token keeps its ``topk_group`` groups of highest expert score and
    its ``num_experts_per_tok`` best experts within them.  Scores are
    drawn from ``seed``; the ranking of softmax scores is that of the
    logits, so the logits rank."""
    logits = np.random.default_rng(seed).standard_normal(
        (seq, n_routed_experts))
    per_group = n_routed_experts // n_group
    group_best = logits.reshape(seq, n_group, per_group).max(axis=-1)
    groups = np.argsort(-group_best, axis=-1, kind="stable")[:, :topk_group]
    keep = np.zeros((seq, n_group), bool)
    np.put_along_axis(keep, groups, True, axis=-1)
    masked = np.where(np.repeat(keep, per_group, axis=-1), logits, -np.inf)
    top = np.argsort(-masked, axis=-1, kind="stable")[:, :num_experts_per_tok]
    return np.bincount(top.ravel(), minlength=n_routed_experts)


def build(hidden_size, moe_intermediate_size, n_routed_experts,
          num_experts_per_tok, n_shared_experts, n_group, topk_group,
          ep_ranks, seq, seed=0):
    """Router, shared gate/up/down, then gate/up/down of each of the
    package's ``n_routed_experts // ep_ranks`` routed experts; the
    router feeds every routed gate and up, which feed their down."""
    d, ff = hidden_size, moe_intermediate_size
    tokens = routed_tokens(seq, n_routed_experts, num_experts_per_tok,
                           n_group, topk_group, seed)
    shared = n_shared_experts * ff
    nests = [matmul(seq, n_routed_experts, d),
             matmul(seq, shared, d), matmul(seq, shared, d),
             matmul(seq, d, shared)]
    edges = [(1, 3, "C", "A"), (2, 3, "C", "B")]
    for e in range(n_routed_experts // ep_ranks):
        t = max(int(tokens[e]), 1)
        g = len(nests)
        nests += [matmul(t, ff, d), matmul(t, ff, d), matmul(t, d, ff)]
        edges += [(0, g, "C", "A"), (0, g + 1, "C", "A"),
                  (g, g + 2, "C", "A"), (g + 1, g + 2, "C", "B")]
    return nests, edges
