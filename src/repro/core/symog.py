"""SYMOG orchestration over arbitrary parameter pytrees (paper Alg. 1).

Usage (see ``repro.train.trainer`` for the integrated loop):

    cfg   = SymogConfig(n_bits=2, total_steps=total)
    state = symog_init(params, cfg)                  # Alg.1 l.2-5: Δ_l search
    ...
    lam   = lambda_at(cfg, step)                     # Alg.1 l.8
    g     = jax.grad(loss)(params) ⊕ lam·reg_grad(params, state, cfg)  # l.15
    params = optimizer(params, g)                    # l.16
    params = clip_tree(params, state, cfg)           # l.17
    ...
    qparams = quantize_tree(params, state, cfg)      # l.21-23 (finalize)
    packed  = pack_tree(params, state, cfg)          # serving artifact

Which leaves are quantized is decided once at init by a path/shape predicate
(default: every rank ≥ 2 kernel except norms/routers/positional tables — see
DESIGN.md §Arch-applicability).  MoE expert stacks (path matching
``per_expert_pattern``, rank ≥ 3) get one Δ per expert — each expert is a
"layer" in the paper's sense.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import metrics as _metrics
from repro.core.quantizer import (
    clip_to_range,
    delta_from_f,
    quantize,
)
from repro.core.regularizer import layer_reg_grad, layer_reg_value
from repro.core.stepsize import F_MAX, F_MIN, optimal_f
from repro.core.packing import pack
from repro.nn.tree import tree_map_with_path, flatten_with_paths

DEFAULT_EXCLUDES: Tuple[str, ...] = (
    "norm",
    "scale",
    "router",
    "pos_embed",
    "a_log",
    "dt_bias",
    "rg_lru/a_param",
    "bias",  # whisper biases are rank-2 (H, hd) — still additive, stay float
    "ssm_d",  # mamba2 skip scale: rank-1 per layer, rank-2 once scan-stacked
)


def default_quant_filter(path: str, leaf: Any) -> bool:
    """Paper quantizes all weight matrices; norms/bias/router stay float."""
    if getattr(leaf, "ndim", 0) < 2:
        return False
    low = path.lower()
    return not any(pat in low for pat in DEFAULT_EXCLUDES)


@dataclasses.dataclass(frozen=True)
class SymogConfig:
    n_bits: int = 2
    lambda0: float = 10.0
    alpha: float = 9.0  # α_E·E with the paper's α_E = 9/E
    total_steps: int = 1000
    clip: bool = True
    f_min: int = F_MIN
    f_max: int = F_MAX
    per_expert_pattern: str = r"experts/"
    quant_filter: Callable[[str, Any], bool] = default_quant_filter


class SymogState:
    """Per-leaf integer exponents f (Δ_l = 2^{-f_l}) + static quantize mask."""

    def __init__(self, f: Any, mask: Dict[str, bool]):
        self.f = f
        self.mask = mask

    def tree_flatten(self):
        return (self.f,), tuple(sorted(self.mask.items()))

    @classmethod
    def tree_unflatten(cls, aux, children):
        (f,) = children
        return cls(f=f, mask=dict(aux))


jax.tree_util.register_pytree_node(
    SymogState, SymogState.tree_flatten, SymogState.tree_unflatten
)


def _delta_for(w: jax.Array, f: jax.Array) -> jax.Array:
    """Δ = 2^{-f}, broadcast per-expert f over trailing weight dims."""
    d = delta_from_f(f)
    while jnp.ndim(d) < jnp.ndim(w):
        d = d[..., None]
    return d


def symog_init(params: Any, cfg: SymogConfig) -> SymogState:
    """Alg. 1 lines 2–5: per-layer (or per-expert) integer grid search for Δ."""
    mask = {p: bool(cfg.quant_filter(p, v)) for p, v in flatten_with_paths(params)}

    def per_leaf(path: str, w):
        if not mask[path]:
            return jnp.zeros((), jnp.int32)
        if re.search(cfg.per_expert_pattern, path) and w.ndim >= 3:
            # one Δ per expert, over EVERY leading dim: an unstacked stack
            # (E,D,F) gets f (E,); a scan-stacked stack (L,E,D,F) gets
            # (L,E) so each layer's experts keep their own exponent.
            # (vmap per leading dim rather than flattening them: a reshape
            # across a sharded expert axis would gather the whole stack)
            search = lambda e: optimal_f(e, cfg.n_bits, cfg.f_min, cfg.f_max)[0]  # noqa: E731
            for _ in range(w.ndim - 2):
                search = jax.vmap(search)
            return search(w).astype(jnp.int32)
        f, _ = optimal_f(w, cfg.n_bits, cfg.f_min, cfg.f_max)
        return jnp.asarray(f, jnp.int32)

    f_tree = tree_map_with_path(per_leaf, params)
    return SymogState(f=f_tree, mask=mask)


def lambda_at(cfg: SymogConfig, step) -> jax.Array:
    """λ(s) = λ_0·exp(α·s/total) — Alg. 1 line 8 in step units."""
    frac = jnp.asarray(step, jnp.float32) / max(cfg.total_steps, 1)
    return cfg.lambda0 * jnp.exp(cfg.alpha * frac)


def reg_value(params: Any, state: SymogState, cfg: SymogConfig) -> jax.Array:
    """R(Θ) over quantizable leaves (paper Eq. 3)."""

    def per_leaf(path, w, f):
        if not state.mask[path]:
            return jnp.zeros((), jnp.float32)
        return layer_reg_value(w, _delta_for(w, f), cfg.n_bits)

    vals = tree_map_with_path(per_leaf, params, state.f)
    return sum(jax.tree_util.tree_leaves(vals))


def reg_grad(params: Any, state: SymogState, cfg: SymogConfig) -> Any:
    """∂R/∂Θ (paper Eq. 4); zeros for non-quantizable leaves."""

    def per_leaf(path, w, f):
        if not state.mask[path]:
            return jnp.zeros_like(w)
        return layer_reg_grad(w, _delta_for(w, f).astype(w.dtype), cfg.n_bits)

    return tree_map_with_path(per_leaf, params, state.f)


def clip_tree(params: Any, state: SymogState, cfg: SymogConfig) -> Any:
    """Paper §3.4 / Alg. 1 line 17 — post-update weight clipping."""
    if not cfg.clip:
        return params

    def per_leaf(path, w, f):
        if not state.mask[path]:
            return w
        return clip_to_range(w, _delta_for(w, f), cfg.n_bits)

    return tree_map_with_path(per_leaf, params, state.f)


def quantize_tree(params: Any, state: SymogState, cfg: SymogConfig) -> Any:
    """Alg. 1 lines 21–23: hard post-quantization (the model stays float-
    represented but every quantizable value is exactly m·2^{-f})."""

    def per_leaf(path, w, f):
        if not state.mask[path]:
            return w
        return quantize(w, _delta_for(w, f), cfg.n_bits)

    return tree_map_with_path(per_leaf, params, state.f)


def pack_tree(params: Any, state: SymogState, cfg: SymogConfig) -> Any:
    """Serving artifact: quantizable leaves → ``Packed`` (int mantissas,
    8/n_bits values per byte); everything else passes through."""

    def per_leaf(path, w, f):
        if not state.mask[path]:
            return w
        return pack(w, f, cfg.n_bits)

    return tree_map_with_path(per_leaf, params, state.f)


def mode_tree(params: Any, state: SymogState, cfg: SymogConfig) -> Any:
    """int8 mode assignment per quantizable leaf (Figure 4 bookkeeping)."""

    def per_leaf(path, w, f):
        if not state.mask[path]:
            return jnp.zeros((1,), jnp.int8)
        return _metrics.mode_assignment(w, _delta_for(w, f), cfg.n_bits)

    return tree_map_with_path(per_leaf, params, state.f)


def quant_error_metrics(params: Any, state: SymogState, cfg: SymogConfig) -> Dict[str, jax.Array]:
    """Aggregate relative quantization error + R(Θ) for logging."""
    sq_err = jnp.zeros(())
    sq_w = jnp.zeros(())
    for path, w in flatten_with_paths(params):
        if not state.mask.get(path, False):
            continue
        f = dict(flatten_with_paths(state.f))[path]
        wf = w.astype(jnp.float32)
        err = wf - quantize(wf, _delta_for(wf, f), cfg.n_bits)
        sq_err = sq_err + jnp.sum(err * err)
        sq_w = sq_w + jnp.sum(wf * wf)
    return {
        "rel_quant_error": jnp.sqrt(sq_err) / (jnp.sqrt(sq_w) + 1e-12),
        "reg_value": reg_value(params, state, cfg),
    }
