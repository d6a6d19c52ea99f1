"""Workload suites used by the paper's experiments.

* res[2-5]  — the ``res{2,3,4,5}b_branch2b`` 3x3 convolutions of ResNet-50
  (paper Fig. 7, batch 1).
* att[1-4]  — four matrix-multiply shapes from BERT-large (seq 512, hidden
  1024, heads 16, FFN 4096): QKV projection, QK^T scores, scores x V, FFN.
* transformer_block — Fig. 4a: 2 heads = 5 matmuls with the 0->2, 1->3,
  2->4, 3->4 dependency structure, pipelineable across chiplets.
* tt_chain  — Fig. 10: tensor-train contraction chain C23 -> C33 -> C43 -> C52.
* workload_library — ~8 workload graphs *derived from the registered
  ``repro.configs`` architectures* (attention blocks, MLP stacks, conv
  chains, scan-style contraction chains): the scenario-diverse library the
  cross-spec transfer subsystem is exercised on.
* graph builders of one layer of a model configuration, read by attribute
  from ``cfg``: ``attention_block``, ``mlp_stack``, ``conv_frontend``,
  ``scan_chain``, ``hybrid_block``, and ``moe_layer`` (one expert-parallel
  rank of a group-limited mixture-of-experts layer).
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Tuple

import numpy as np

from repro import obs

from .constants import DEFAULT_TECH, TechConstants
from .workload import (Edge, Workload, WorkloadGraph, contraction, conv2d,
                       matmul, mttkrp)


def resnet_convs() -> Dict[str, WorkloadGraph]:
    """res{2-5}b_branch2b: 3x3 stride-1 convs at each ResNet-50 stage."""
    shapes = {
        "res2": dict(N=1, K=64, C=64, P=56, Q=56, R=3, S=3),
        "res3": dict(N=1, K=128, C=128, P=28, Q=28, R=3, S=3),
        "res4": dict(N=1, K=256, C=256, P=14, Q=14, R=3, S=3),
        "res5": dict(N=1, K=512, C=512, P=7, Q=7, R=3, S=3),
    }
    return {k: WorkloadGraph([conv2d(k, **v)], []) for k, v in shapes.items()}


def bert_mms() -> Dict[str, WorkloadGraph]:
    """Four matmul shapes from BERT-large."""
    shapes = {
        "att1": (512, 1024, 1024),   # QKV projection
        "att2": (512, 512, 64),      # per-head Q K^T
        "att3": (512, 64, 512),      # per-head scores x V
        "att4": (512, 4096, 1024),   # FFN up-projection
    }
    return {k: WorkloadGraph([matmul(k, *v)], []) for k, v in shapes.items()}


def fig7_suite() -> Dict[str, WorkloadGraph]:
    out = dict(resnet_convs())
    out.update(bert_mms())
    return out


def transformer_block(seq: int = 512, d: int = 512,
                      heads: int = 2) -> WorkloadGraph:
    """Paper Fig. 4a: 2 heads / 5 matmuls with cross-head concat into MM4."""
    dh = d // heads
    wls = [
        matmul("mm0_qk_h0", seq, seq, dh),
        matmul("mm1_qk_h1", seq, seq, dh),
        matmul("mm2_av_h0", seq, dh, seq),
        matmul("mm3_av_h1", seq, dh, seq),
        matmul("mm4_out", seq, d, d),
    ]
    edges = [
        Edge(0, 2, "C", "A"),
        Edge(1, 3, "C", "A"),
        Edge(2, 4, "C", "A"),
        Edge(3, 4, "C", "A"),
    ]
    return WorkloadGraph(wls, edges)


def tt_chain(s: int = 32, r: int = 32) -> WorkloadGraph:
    """Fig. 10: TT reconstruction by sequential contraction.  The result
    tensor grows: C23 (O(n^4)) -> C33 (O(n^5)) -> C43/C52 (O(n^6))."""
    c23 = contraction("c23", {"s1": s}, {"s2": s, "a2": r}, {"a1": r})
    c33 = contraction("c33", {"m": s * s}, {"s3": s, "a3": r}, {"a2": r})
    c43 = contraction("c43", {"m": s * s * s}, {"s4": s, "a4": r}, {"a3": r})
    c52 = contraction("c52", {"m": s * s * s * s}, {"s5": s}, {"a4": r})
    edges = [
        Edge(0, 1, "O", "A"),
        Edge(1, 2, "O", "A"),
        Edge(2, 3, "O", "A"),
    ]
    return WorkloadGraph([c23, c33, c43, c52], edges)


def validation_suite() -> Dict[str, WorkloadGraph]:
    """Small matmuls for the Sec. V-A model-vs-simulator validation (the
    paper uses a four-chip transformer with 8x8 PE arrays per chip)."""
    out = {}
    for m, n, k in [(64, 64, 64), (128, 128, 128), (128, 512, 256),
                    (256, 256, 256), (512, 512, 128)]:
        out[f"mm{m}x{n}x{k}"] = WorkloadGraph([matmul("mm", m, n, k)], [])
    return out


def mttkrp_example(i: int = 256, j: int = 64, k: int = 128,
                   l: int = 128) -> WorkloadGraph:
    return WorkloadGraph([mttkrp("mttkrp", i, j, k, l)], [])


# ---------------------------------------------------------------------------
# model-derived workload library (cross-workload transfer scenarios)
# ---------------------------------------------------------------------------
def attention_block(cfg, seq: int = 256) -> WorkloadGraph:
    """One self-attention block of a registered architecture: QKV
    projection -> per-head QK^T -> scores x V -> output projection, chained
    producer->consumer (per-head matmuls at head_dim width)."""
    d, hd = cfg.d_model, cfg.head_dim
    qkv_cols = (cfg.n_heads + 2 * cfg.n_kv_heads) * hd
    wls = [
        matmul("qkv_proj", seq, qkv_cols, d),
        matmul("qk_scores", seq, seq, hd),
        matmul("av", seq, hd, seq),
        matmul("out_proj", seq, d, cfg.n_heads * hd),
    ]
    edges = [Edge(0, 1, "C", "A"), Edge(1, 2, "C", "A"), Edge(2, 3, "C", "A")]
    return WorkloadGraph(wls, edges)


def mlp_stack(cfg, seq: int = 256) -> WorkloadGraph:
    """Gated MLP of a registered architecture (MoE archs use the per-expert
    width): gate and up projections feeding the down projection."""
    d = cfg.d_model
    ff = cfg.expert_ff if cfg.n_experts > 0 else cfg.d_ff
    wls = [
        matmul("gate_proj", seq, ff, d),
        matmul("up_proj", seq, ff, d),
        matmul("down_proj", seq, d, ff),
    ]
    edges = [Edge(0, 2, "C", "A"), Edge(1, 2, "C", "B")]
    return WorkloadGraph(wls, edges)


def conv_frontend(cfg, frames: int = 1500, mel: int = 80) -> WorkloadGraph:
    """Whisper-style audio conv frontend: two stride-adjacent k=3 conv1d
    layers (encoded as 7-loop conv2d with a unit Q axis)."""
    d = cfg.d_model
    c1 = conv2d("conv1", N=1, K=d, C=mel, P=frames, Q=1, R=3, S=1)
    c2 = conv2d("conv2", N=1, K=d, C=d, P=frames // 2, Q=1, R=3, S=1)
    return WorkloadGraph([c1, c2], [Edge(0, 1, "O", "I")])


def scan_chain(cfg, seq: int = 512) -> WorkloadGraph:
    """Mamba-style selective-scan dataflow as a contraction chain:
    in-projection -> state contraction -> output projection (the tensor
    sizes flow (t, d_inner) -> (t, n_state) -> (t, d_model))."""
    d, di, n = cfg.d_model, cfg.d_inner, max(cfg.ssm_state, 1)
    c_in = contraction("in_proj", {"t": seq}, {"di": di}, {"d": d})
    c_h = contraction("state", {"t": seq}, {"n": n}, {"di": di})
    c_out = contraction("out_proj", {"t": seq}, {"dm": d}, {"n": n})
    return WorkloadGraph([c_in, c_h, c_out],
                         [Edge(0, 1, "O", "A"), Edge(1, 2, "O", "A")])


def hybrid_block(cfg, seq: int = 256) -> WorkloadGraph:
    """Hymba-style parallel heads: sliding-window attention (scores over a
    ``window`` span) beside an SSM state contraction, both feeding one
    output projection."""
    d, hd = cfg.d_model, cfg.head_dim
    w = cfg.window or seq
    di, n = cfg.d_inner, max(cfg.ssm_state, 1)
    wls = [
        matmul("win_scores", seq, min(w, seq), hd),
        matmul("win_av", seq, hd, min(w, seq)),
        contraction("ssm", {"t": seq}, {"n": n}, {"di": di}),
        matmul("out_proj", seq, d, d),
    ]
    edges = [Edge(0, 1, "C", "A"), Edge(1, 3, "C", "A"),
             Edge(2, 3, "O", "B")]
    return WorkloadGraph(wls, edges)


ROUTE_BLOCK = 4096      # tokens per rank the routing stream holds at least


def noaux_tc_route(logits: np.ndarray, top_k: int, n_group: int,
                   topk_group: int) -> np.ndarray:
    """Expert choice of each token (row) under DeepSeek-V3's ``noaux_tc``
    rule with a zero correction bias: sigmoid scores; a group's score is
    the sum of its two best; the ``topk_group`` best groups are kept and
    the ``top_k`` best experts within them chosen; ties go to the lower
    index.  A (tokens, experts) bool mask with ``top_k`` per row."""
    n, n_exp = logits.shape
    scores = 1.0 / (1.0 + np.exp(-logits))
    grouped = scores.reshape(n, n_group, n_exp // n_group)
    group_score = np.partition(grouped, -2, axis=-1)[..., -2:].sum(-1)
    kept = np.argsort(-group_score, axis=-1, kind="stable")[:, :topk_group]
    keep = np.zeros((n, n_group), bool)
    np.put_along_axis(keep, kept, True, axis=-1)
    masked = np.where(np.repeat(keep, n_exp // n_group, axis=-1), scores,
                      -np.inf)
    kth = -np.partition(-masked, top_k - 1, axis=-1)[:, top_k - 1:top_k]
    above = masked > kth
    tied = masked == kth
    room = top_k - above.sum(-1, keepdims=True)
    return above | (tied & (np.cumsum(tied, axis=-1) <= room))


class _RouteStream:
    """The routing choices of the seeded token stream of one layer: token
    ``t``'s logits are row ``t`` of ``default_rng(seed).standard_normal((N,
    n_experts))`` for any ``N > t``; the stream grows by drawing further
    rows from the same generator."""

    def __init__(self, seed: int, n_experts: int, top_k: int, n_group: int,
                 topk_group: int):
        self.rng = np.random.default_rng(seed)
        self.route = (top_k, n_group, topk_group)
        self.n = 0                              # tokens drawn
        # per expert, the tokens that pick it, ascending
        self.tokens = [np.empty(0, np.int64)] * n_experts

    def extend(self, n: int) -> None:
        with obs.span("presets.moe_route_draw", tokens=n - self.n):
            obs.inc("presets.moe_layer.stream_draws")
            picks = noaux_tc_route(self.rng.standard_normal(
                (n - self.n, len(self.tokens))), *self.route)
            expert, token = np.nonzero(picks.T)
            cut = np.searchsorted(expert, np.arange(len(self.tokens) + 1))
            self.tokens = [np.concatenate([t, token[a:b] + self.n])
                           for t, a, b in zip(self.tokens, cut, cut[1:])]
            self.n = n

    def counts(self, experts: range, n: int) -> np.ndarray:
        """Tokens among the first ``n`` that pick each of ``experts``."""
        return np.asarray([np.searchsorted(self.tokens[e], n)
                           for e in experts], np.int64)


_ROUTE_STREAMS: Dict[Tuple, _RouteStream] = {}
_ROUTE_LOCK = threading.Lock()


def routed_tokens(cfg, seq: int) -> np.ndarray:
    """Tokens each expert ``cfg`` holds gets from the first
    ``ep_size * seq`` tokens of the layer's routing stream.  The stream is
    drawn once per widths and seed and kept, at least ``ROUTE_BLOCK``
    tokens a rank, doubling when a longer ``seq`` needs more."""
    if cfg.topk_method != "noaux_tc" or cfg.scoring_func != "sigmoid":
        raise ValueError(f"moe_layer routes by noaux_tc over sigmoid scores,"
                         f" not {cfg.topk_method} over {cfg.scoring_func}")
    held = cfg.n_routed_experts // cfg.ep_size
    key = (cfg.route_seed, cfg.n_routed_experts, cfg.num_experts_per_tok,
           cfg.n_group, cfg.topk_group)
    n = cfg.ep_size * seq
    with _ROUTE_LOCK:
        stream = _ROUTE_STREAMS.get(key)
        if stream is None:
            stream = _ROUTE_STREAMS[key] = _RouteStream(*key)
        if stream.n < n:
            per_rank = ROUTE_BLOCK
            while per_rank < seq:
                per_rank *= 2
            stream.extend(cfg.ep_size * per_rank)
        return stream.counts(range(cfg.ep_rank * held,
                                   (cfg.ep_rank + 1) * held), n)


def moe_layer(cfg, seq: int = 256) -> WorkloadGraph:
    """One expert-parallel rank of a DeepSeek-V3-style MoE layer: the
    router (``hidden_size -> n_routed_experts``) and the shared experts,
    one gated MLP of width ``n_shared_experts * moe_intermediate_size``,
    over the rank's ``seq`` tokens; then gate, up and down of each of the
    ``n_routed_experts // ep_size`` experts the rank ``ep_rank`` holds,
    each over the tokens the group-limited routing (``noaux_tc_route``)
    sends it from all ``ep_size`` ranks' tokens (``routed_tokens``; an
    expert no token picks gets one).  The router feeds every routed gate
    and up, the dispatch; gate and up feed their down as in
    ``mlp_stack``.  Nothing stands in for the other ranks."""
    with obs.span("presets.moe_layer", seq=seq) as sp:
        obs.inc("presets.moe_layer.calls")
        d, ff = cfg.hidden_size, cfg.moe_intermediate_size
        shared = cfg.n_shared_experts * ff
        tokens = routed_tokens(cfg, seq)
        wls = [matmul("router", seq, cfg.n_routed_experts, d),
               matmul("shared_gate", seq, shared, d),
               matmul("shared_up", seq, shared, d),
               matmul("shared_down", seq, d, shared)]
        edges = [Edge(1, 3, "C", "A"), Edge(2, 3, "C", "B")]
        first = cfg.ep_rank * len(tokens)
        for e, t in enumerate(tokens, start=first):
            g, t = len(wls), max(int(t), 1)
            wls += [matmul(f"expert{e}_gate", t, ff, d),
                    matmul(f"expert{e}_up", t, ff, d),
                    matmul(f"expert{e}_down", t, d, ff)]
            edges += [Edge(0, g, "C", "A"), Edge(0, g + 1, "C", "A"),
                      Edge(g, g + 2, "C", "A"), Edge(g + 1, g + 2, "C", "B")]
        sp.set(workloads=len(wls), edges=len(edges),
               tokens_here=int(tokens.sum()), tokens_max=int(tokens.max()),
               tokens_min=int(tokens.min()))
        return WorkloadGraph(wls, edges)


def workload_library() -> Dict[str, WorkloadGraph]:
    """Scenario-diverse workload graphs derived from the registered
    ``repro.configs`` architectures — attention blocks, MLP stacks, a conv
    chain, scan-style contraction chains.  Genuinely different graphs (not
    toy variants), so cross-spec transfer is exercised for real: similar
    pairs exist (the three attention blocks; the two MLPs) alongside
    structurally alien ones (conv vs. scan vs. attention)."""
    from repro.configs import get_config      # lazy: keep repro.core light
    qwen72 = get_config("qwen2_72b")
    qwen32 = get_config("qwen2_5_32b")
    intern = get_config("internlm2_1_8b")
    deepseek = get_config("deepseek_v2_236b")
    whisper = get_config("whisper_tiny")
    mamba = get_config("falcon_mamba_7b")
    hymba = get_config("hymba_1_5b")
    return {
        "attn_qwen2_72b": attention_block(qwen72),
        "attn_qwen2_5_32b": attention_block(qwen32),
        "attn_internlm2": attention_block(intern),
        "mlp_qwen2_72b": mlp_stack(qwen72),
        "mlp_deepseek_v2": mlp_stack(deepseek),
        "conv_whisper": conv_frontend(whisper),
        "scan_falcon_mamba": scan_chain(mamba),
        "hybrid_hymba": hybrid_block(hymba),
    }


# ---------------------------------------------------------------------------
# Technology presets — named TechConstants variants, including calibrated
# artifacts produced by ``repro.calib`` (see README "Calibration").
# ---------------------------------------------------------------------------
_TECH_PRESETS: Dict[str, TechConstants] = {"default": DEFAULT_TECH}


def register_tech(name: str, tech: TechConstants) -> None:
    """Register a named TechConstants preset for this process.  Re-registering
    the same name with different constants is an error (preset identity must
    stay stable within a process); re-registering identical constants is a
    no-op."""
    prev = _TECH_PRESETS.get(name)
    if prev is not None and prev != tech:
        raise ValueError(f"tech preset {name!r} already registered with "
                         "different constants")
    _TECH_PRESETS[name] = tech


def tech_preset_names() -> tuple:
    return tuple(sorted(_TECH_PRESETS))


def _load_tech_file(path: str) -> "tuple[str, TechConstants]":
    """Load a tech preset from a JSON file: either a bare tech dict or a
    ``repro.calib`` CalibratedTech artifact ({"name": ..., "tech": {...}})."""
    import json

    from .constants import tech_from_dict
    with open(path) as f:
        doc = json.load(f)
    if "tech" in doc and isinstance(doc["tech"], dict):
        name = doc.get("name") or os.path.splitext(os.path.basename(path))[0]
        return str(name), tech_from_dict(doc["tech"])
    name = os.path.splitext(os.path.basename(path))[0]
    return name, tech_from_dict(doc)


def tech_preset(name: str) -> TechConstants:
    """Resolve a tech preset by name.

    Resolution order: in-process registry (``register_tech``), then
    ``$REPRO_CALIB_DIR/<name>.json``, then ``name`` interpreted as a path to
    a JSON artifact.  File-resolved presets are cached in the registry so a
    name always maps to one set of constants per process.
    """
    if name in _TECH_PRESETS:
        return _TECH_PRESETS[name]
    cal_dir = os.environ.get("REPRO_CALIB_DIR", "")
    candidates = []
    if cal_dir:
        candidates.append(os.path.join(cal_dir, f"{name}.json"))
    if name.endswith(".json") or os.sep in name:
        candidates.append(name)
    for path in candidates:
        if os.path.exists(path):
            _, tech = _load_tech_file(path)
            register_tech(name, tech)
            return tech
    raise KeyError(
        f"unknown tech preset {name!r}; known: {tech_preset_names()} "
        "(set REPRO_CALIB_DIR or pass a JSON artifact path)")


def resolve_tech(tech) -> "tuple[str, TechConstants]":
    """Normalize any accepted tech designator to ``(name, TechConstants)``.

    Accepts ``None`` (default constants), a preset name or artifact path
    (str), a :class:`TechConstants`, or a ``repro.calib`` CalibratedTech
    (duck-typed: ``.name`` + ``.tech`` attributes).
    """
    if tech is None:
        return "default", DEFAULT_TECH
    if isinstance(tech, str):
        return tech, tech_preset(tech)
    if isinstance(tech, TechConstants):
        if tech == DEFAULT_TECH:
            return "default", tech
        for name, t in _TECH_PRESETS.items():
            if t == tech:
                return name, tech
        return "custom", tech
    name = getattr(tech, "name", None)
    inner = getattr(tech, "tech", None)
    if isinstance(inner, TechConstants) and name:
        register_tech(str(name), inner)
        return str(name), inner
    raise TypeError(f"cannot resolve tech designator of type {type(tech)!r}")


def tech_label(tech) -> str:
    """Human-readable tech identity ``name@digest12`` carried in provenance
    and job payloads; plain ``"default"`` for the uncalibrated constants."""
    from .constants import tech_key
    name, t = resolve_tech(tech)
    if t == DEFAULT_TECH:
        return "default"
    return f"{name}@{tech_key(t)[:12]}"
