"""Reference graph of ``repro.core.presets.moe_layer``: one
expert-parallel rank of a DeepSeek-V3 expert layer (router, shared
expert, and gate, up and down of each routed expert the rank holds)."""

import numpy as np

from harness.reference import matmul


def route_counts(n_tokens, n_routed_experts, num_experts_per_tok, n_group,
                 topk_group, route_seed):
    """Tokens per routed expert among the first ``n_tokens`` tokens of the
    seeded stream under ``noaux_tc`` with a zero correction bias.  Logits
    are the rows of ``default_rng(route_seed).standard_normal((n_tokens,
    n_routed_experts))``; scores are their sigmoids; a group's score is
    the sum of its two best scores; each token keeps its ``topk_group``
    best groups and picks its ``num_experts_per_tok`` best experts within
    them; ties go to the lower index (stable sorts)."""
    logits = np.random.default_rng(route_seed).standard_normal(
        (n_tokens, n_routed_experts))
    scores = 1.0 / (1.0 + np.exp(-logits))
    per_group = n_routed_experts // n_group
    grouped = scores.reshape(n_tokens, n_group, per_group)
    best_two = np.sort(grouped, axis=-1)[..., -2:]
    group_score = best_two[..., 0] + best_two[..., 1]
    groups = np.argsort(-group_score, axis=-1, kind="stable")[:, :topk_group]
    in_kept = np.zeros((n_tokens, n_group), bool)
    np.put_along_axis(in_kept, groups, True, axis=-1)
    masked = np.where(np.repeat(in_kept, per_group, axis=-1), scores,
                      -np.inf)
    top = np.argsort(-masked, axis=-1,
                     kind="stable")[:, :num_experts_per_tok]
    return np.bincount(top.ravel(), minlength=n_routed_experts)


def build(hidden_size, moe_intermediate_size, n_routed_experts,
          num_experts_per_tok, n_shared_experts, n_group, topk_group,
          topk_method, scoring_func, ep_size, ep_rank, route_seed, seq):
    """Router and shared gate/up/down over the rank's ``seq`` tokens, then
    gate/up/down of experts ``ep_rank * k .. (ep_rank + 1) * k - 1`` (``k
    = n_routed_experts // ep_size``), each over the tokens among the first
    ``ep_size * seq`` that pick it (at least one).  The router feeds every
    routed gate and up, which feed their down; the shared gate and up
    feed the shared down."""
    if (topk_method, scoring_func) != ("noaux_tc", "sigmoid"):
        raise ValueError(f"no reference for {topk_method} routing over "
                         f"{scoring_func} scores")
    d, ff = hidden_size, moe_intermediate_size
    k = n_routed_experts // ep_size
    counts = route_counts(ep_size * seq, n_routed_experts,
                          num_experts_per_tok, n_group, topk_group,
                          route_seed)[ep_rank * k:(ep_rank + 1) * k]
    shared = n_shared_experts * ff
    nests = [matmul(seq, n_routed_experts, d),
             matmul(seq, shared, d), matmul(seq, shared, d),
             matmul(seq, d, shared)]
    edges = [(1, 3, "C", "A"), (2, 3, "C", "B")]
    for t in counts:
        g, t = len(nests), max(int(t), 1)
        nests += [matmul(t, ff, d), matmul(t, ff, d), matmul(t, d, ff)]
        edges += [(0, g, "C", "A"), (0, g + 1, "C", "A"),
                  (g, g + 2, "C", "A"), (g + 1, g + 2, "C", "B")]
    return nests, edges
