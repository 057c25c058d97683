"""A mixture-of-experts FFN layer on IMAC crossbars: router and dispatch.

The layer is DeepSeek-V3's (arXiv:2412.19437 §2.1.2; the published
`config.json`, `topk_method` "noaux_tc"): a sigmoid router over the routed
experts, a per-expert correction bias that steers selection only, groups
ranked by the sum of their two best biased scores, the best `topk_group`
groups kept, the best `top_k` experts taken among them, and the selected
(unbiased) scores normalised to sum 1 and scaled by
`routed_scaling_factor`. Every expert, the shared one included, is a SwiGLU
FFN with no biases: down(silu(gate(x)) * up(x)).

Under expert parallelism one chip holds a share of the routed experts and
the shared expert. The router runs in full on every chip; the chip then
computes, for each token, the shared expert plus the weighted outputs of
the held experts that the token selected. What the other chips' experts
add is not part of this chip's result.

The router is digital (float32, `Precision.HIGHEST`): a routing decision
must not hang on the IR drop of a crossbar. The experts' matrices go
through the circuit solve (`core.evaluate.evaluate_moe`). Each held
expert runs on the tokens routed to it, padded to a power-of-two bucket
of slots, so that any routing reuses a few compiled programs.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: Expert id of the shared expert in dispatch lists and results.
SHARED = -1


@dataclasses.dataclass(frozen=True)
class MoESpec:
    """Shape and router of one MoE FFN layer (published values)."""

    d_model: int
    d_expert: int            # moe_intermediate_size
    n_experts: int           # routed experts, all chips together
    n_shared: int            # shared experts (each runs on every token)
    top_k: int               # routed experts per token
    n_group: int = 1
    topk_group: int = 1
    scaling: float = 1.0     # routed_scaling_factor
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"

    def __post_init__(self):
        if self.scoring_func != "sigmoid":
            raise ValueError(
                f"only the sigmoid (noaux_tc) router is implemented, got "
                f"{self.scoring_func!r}"
            )
        if self.n_experts % self.n_group:
            raise ValueError(
                f"{self.n_experts} experts do not split into {self.n_group} groups"
            )
        if self.n_experts // self.n_group < 2:
            raise ValueError("a group needs at least two experts (top-2 score)")
        if not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"topk_group {self.topk_group} not in 1..{self.n_group}")
        if not 1 <= self.top_k <= self.topk_group * self.n_experts // self.n_group:
            raise ValueError(f"top_k {self.top_k} exceeds the kept groups' experts")

    @classmethod
    def from_model_config(cls, cfg) -> "MoESpec":
        """The MoE layer of a `repro.configs.base.ModelConfig`."""
        return cls(
            d_model=cfg.d_model,
            d_expert=cfg.moe_d_ff,
            n_experts=cfg.n_experts,
            n_shared=cfg.n_shared_experts,
            top_k=cfg.experts_per_token,
            n_group=cfg.n_group,
            topk_group=cfg.topk_group,
            scaling=cfg.routed_scaling_factor,
            norm_topk_prob=cfg.norm_topk_prob,
            scoring_func=cfg.scoring_func,
        )


def route(
    x: jax.Array, w_router: jax.Array, bias: jax.Array, spec: MoESpec
) -> "tuple[jax.Array, jax.Array]":
    """Group-limited top-k routing of a batch of tokens.

    Args:
      x: (B, d_model) hidden states.
      w_router: (n_experts, d_model) router weights.
      bias: (n_experts,) selection correction bias.

    Returns:
      (idx, weight): (B, top_k) selected expert ids, best first, and their
      routing weights.
    """
    highest = jax.lax.Precision.HIGHEST
    x = x.astype(jnp.float32)
    logits = jnp.matmul(x, w_router.astype(jnp.float32).T, precision=highest)
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias.astype(jnp.float32)
    b = x.shape[0]
    per_group = spec.n_experts // spec.n_group
    grouped = choice.reshape(b, spec.n_group, per_group)
    group_scores = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    _, group_idx = jax.lax.top_k(group_scores, spec.topk_group)
    kept = jnp.sum(jax.nn.one_hot(group_idx, spec.n_group), axis=-2) > 0
    kept = jnp.repeat(kept, per_group, axis=-1)
    _, idx = jax.lax.top_k(jnp.where(kept, choice, 0.0), spec.top_k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if spec.norm_topk_prob:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), weight * spec.scaling


def bucket(n: int) -> int:
    """Slots given to an expert with `n` tokens: the next power of two."""
    if n < 1:
        raise ValueError(f"an expert with {n} tokens runs no program")
    return 1 << (n - 1).bit_length()


def dispatch(
    idx: np.ndarray, weight: np.ndarray, held: "tuple[int, ...]"
) -> "list[tuple[int, np.ndarray, np.ndarray]]":
    """(expert, tokens, weights) of each held routed expert that a token
    selected, in `held` order; tokens ascending (host arrays)."""
    out = []
    for e in held:
        tokens, slot = np.nonzero(idx == e)
        if tokens.size:
            out.append((e, tokens, weight[tokens, slot]))
    return out
