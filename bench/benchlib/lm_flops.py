"""Operations and bytes of the GRPO step on the latent-attention MoE
policy (``bench/drivers/lm_grpo.py``), from the widths (``reference/
moonlight.py::widths``) and the step's own counts.

FLOPs count matrix products at 2 per multiply-add, over real tokens only
(padding is not work the algorithm needs): latent attention's
projections per token, its scores and value products per attended
(query, key) pair (a causal row of L tokens attends L(L+1)/2), the dense
MLP, router and shared experts per token, the routed experts per
assignment to a held expert (the step's counter), and the head per
trained position.  A backward pass is twice its forward; nothing
recomputed is counted.

The grouped expert kernel's least bytes: bf16 operands read once, the
f32 result written once.
"""
from __future__ import annotations


def trunk_forward(w: dict, tokens: int, pairs: int) -> int:
    """Every layer's attention (projections per token, scores and value
    products per pair), the dense layers' MLP, the MoE layers' router and
    shared experts."""
    D, H, r = w["D"], w["H"], w["r"]
    dn, dr, dv = w["dn"], w["dr"], w["dv"]
    layers = w["dense"] + w["moe"]
    proj = 2 * (D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv)
                + H * dv * D)
    return (layers * (tokens * proj + pairs * 2 * H * (dn + dr + dv))
            + w["dense"] * tokens * 2 * 3 * D * w["Fd"]
            + w["moe"] * tokens * 2 * (D * w["E"] + 3 * D * w["Fs"]))


def routed_forward(w: dict, assignments: int) -> int:
    """The held experts' SiLU-gated FFNs, per assignment."""
    return assignments * 2 * 3 * w["D"] * w["F"]


def head_forward(w: dict, positions: int) -> int:
    return positions * 2 * w["D"] * w["V"]


def step_flops(w: dict, lengths, responses, assignments: float) -> float:
    """One GRPO step: the old log-probabilities' forward pass, the
    training forward pass and its backward (twice a forward).  lengths:
    each row's prompt + response; responses: each row's trained tokens;
    assignments: held-expert assignments of one pass over the rows."""
    fwd = (trunk_forward(w, sum(lengths),
                         sum(n * (n + 1) // 2 for n in lengths))
           + routed_forward(w, assignments)
           + head_forward(w, sum(responses)))
    return 4 * fwd


def gmm_calls(w: dict):
    """(kind, K, N) of the grouped kernel's calls for one MoE layer and one
    pass over a minibatch: forward of the old log-probabilities, training
    forward, its recompute under remat (3 products each: wi, wg D->F, wo
    F->D), the input gradients (wo's D->F, wi's and wg's F->D) and the
    weight gradients (``tgmm``: wo's F x D, wi's and wg's D x F)."""
    D, F = w["D"], w["F"]
    fwd = [("gmm", D, F), ("gmm", D, F), ("gmm", F, D)]
    return (3 * fwd + [("gmm", D, F), ("gmm", F, D), ("gmm", F, D)]
            + [("tgmm", F, D), ("tgmm", D, F), ("tgmm", D, F)])


def gmm_call_cost(kind: str, rows: float, K: int, N: int, G: int):
    """(FLOPs, least bytes) of one call over ``rows`` assignment rows and
    ``G`` experts: ``gmm`` reads rows x K and G x K x N, writes rows x N;
    ``tgmm`` reads rows x K and rows x N, writes G x K x N (f32)."""
    flops = 2 * rows * K * N
    if kind == "gmm":
        return flops, 2 * rows * K + 2 * G * K * N + 4 * rows * N
    return flops, 2 * rows * K + 2 * rows * N + 4 * G * K * N
