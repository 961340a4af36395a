"""Path-based logical-axis sharding rules (MaxText-style, but path-driven).

Every parameter leaf gets a tuple of *logical* axis names derived from its
path + shape (``LOGICAL_RULES``); a *profile* maps logical names to mesh axes.
Resolution is shape-aware: a mapping that does not divide the dimension is
dropped (replicated) rather than erroring, so the same profile works across
all 10 assigned architectures (e.g. gemma3's 8 q-heads cannot shard over a
16-way ``model`` axis — the engine falls back to replication for that leaf).

Profiles
--------
``dp``       batch over (pod, data); params replicated.
``dp_tp``    + tensor parallelism: mlp/heads/vocab/expert over ``model``.
``fsdp_tp``  + ZeRO-3: the ``embed`` axis of params/optimizer over (pod, data).
``fsdp_tp_sp``  + sequence sharding of activations (long-context).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.nn.tree import tree_map_with_path


def make_mesh(shape: Sequence[int], names: Sequence[str], devices=None) -> Mesh:
    """A device mesh whose axes are all ``Auto``: GSPMD propagates shardings
    from the placed params and the ``with_sharding_constraint`` pins, which
    is what every sharding rule here assumes (``jax.make_mesh`` alone makes
    ``Explicit`` axes, whose sharding-in-types rejects that code)."""
    n = int(np.prod(shape))
    devices = jax.devices()[:n] if devices is None else devices
    return jax.make_mesh(tuple(shape), tuple(names), axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def current_mesh():
    """The ambient mesh (entered with ``jax.set_mesh``), or None outside one.

    Returns the abstract mesh — axis names and sizes, all that the sharded
    dispatch reads.  Readable mid-trace: sharded dispatch decisions (moe_ep
    routing, the paged-attention head-slicing wrapper) branch on it while
    jit is tracing — the decision is baked into the trace, which is exactly
    the engine-pins-at-construction contract DESIGN.md §4 already gives the
    packed/attention backends."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_axis_size(mesh: Optional[Mesh], *names: str) -> int:
    """Product of the named mesh axes that exist on ``mesh`` (1 if none)."""
    if mesh is None:
        return 1
    return int(np.prod([mesh.shape[a] for a in names if a in mesh.axis_names] or [1]))

# ---------------------------------------------------------------------------
# Logical rules: (path regex, logical axes per dim).  First match wins.
# Axes tuples shorter than ndim are right-padded with None.  'auto' derives
# a generic (fan_in, fan_out) = ('embed', 'mlp') labelling for 2-D kernels.
# ---------------------------------------------------------------------------
LOGICAL_RULES: List[Tuple[str, Tuple[Optional[str], ...]]] = [
    # embeddings
    (r"(^|/)(tok_)?embed(dings?)?(/embedding)?$", ("vocab", "embed")),
    (r"pos_embed", (None, "embed")),
    (r"lm_head/kernel$", ("embed", "vocab")),
    # attention
    (r"(q_proj|wq)/kernel$", ("embed", "heads", "head_dim")),
    (r"(k_proj|v_proj|wk|wv)/kernel$", ("embed", "kv_heads", "head_dim")),
    (r"(o_proj|wo_attn)/kernel$", ("heads", "head_dim", "embed")),
    (r"(qkv_proj)/kernel$", ("embed", "heads", "head_dim")),
    # MLA (deepseek): low-rank compressions + expansions
    (r"q_a_proj/kernel$", ("embed", None)),
    (r"q_b_proj/kernel$", (None, "heads", "head_dim")),
    (r"kv_a_proj/kernel$", ("embed", None)),
    (r"k_rope_proj/kernel$", ("embed", None)),
    (r"(kv_b_k_proj|kv_b_v_proj)/kernel$", (None, "heads", "head_dim")),
    # MoE experts: leading expert dim (MUST precede the dense-MLP rules —
    # first match wins and 'experts/gate_proj' would match the MLP regex)
    (r"experts/(wi|gate_proj|up_proj)/kernel$", ("expert", "embed", "mlp")),
    (r"experts/(wo|down_proj)/kernel$", ("expert", "mlp", "embed")),
    # shared experts: TP on d_ff only (no fsdp on D — they live inside the
    # EP shard_map whose in_specs are (None,'model') / ('model',None))
    (r"shared/(gate_proj|up_proj)/kernel$", (None, "mlp")),
    (r"shared/down_proj/kernel$", ("mlp", None)),
    (r"router/kernel$", ("embed", None)),
    # MLP (dense)
    (r"(wi|gate_proj|up_proj|fc1|wi_0|wi_1)/kernel$", ("embed", "mlp")),
    (r"(wo|down_proj|fc2)/kernel$", ("mlp", "embed")),
    # recurrent / ssm blocks
    (r"(in_proj\w*|x_proj)/kernel$", ("embed", "mlp")),
    (r"(out_proj)/kernel$", ("mlp", "embed")),
    (r"conv1d/kernel$", (None, "mlp")),          # (width, channels)
    (r"(a_log|A_log|dt_bias|ssm_D|rg_lru/a_param)$", ("mlp",)),
    (r"rg_lru/(input_gate|a_gate)/kernel$", ("heads", None, None)),
    (r"rg_lru/(input_gate|a_gate)/bias$", ("heads", None)),
    # convnets (paper models): (kh, kw, cin, cout)
    (r"conv\d*/kernel$", (None, None, None, "mlp")),
    # norms / scalars / biases: replicate
    (r"(scale|bias|norm|ln|layernorm)", (None,)),
]

# Activation logical axes used with with_sharding_constraint.
ACT_RULES: Dict[str, Tuple[Optional[str], ...]] = {
    "btd": ("batch", "seq_act", "act_embed"),
    "bt": ("batch", "seq_act"),
    "btv": ("batch", "seq_act", "vocab_act"),
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Resolved rules for one (mesh, profile)."""

    mesh: Mesh
    axis_map: Dict[str, Any]  # logical name -> mesh axis | tuple | None

    def _mesh_axes_size(self, mapped) -> int:
        if mapped is None:
            return 1
        axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        size = 1
        for a in axes:
            size *= self.mesh.shape[a]
        return size

    def logical_axes_for(self, path: str, shape: Sequence[int]) -> Tuple[Optional[str], ...]:
        # Packed (quantized) leaves flatten as <param>/0 (int8 words) and
        # <param>/1 (exponent): match the rules against the parent path.
        path = re.sub(r"/[01]$", "", path)
        # Layer stacks produced by scan-over-layers carry a leading L dim:
        # left-pad the matched axes with None so they align to the right.
        stacked = bool(re.search(r"(^|/)(layers|blocks|units)\d*/", path))
        for pat, axes in LOGICAL_RULES:
            if re.search(pat, path):
                ax = tuple(axes)
                if stacked and len(shape) == len(ax) + 1:
                    ax = (None,) + ax
                ax = ax[: len(shape)]
                ax = ax + (None,) * (len(shape) - len(ax))
                return ax
        # default: replicate
        return (None,) * len(shape)

    def pspec_for(self, path: str, shape: Sequence[int]) -> P:
        logical = self.logical_axes_for(path, shape)
        spec: List[Any] = []
        used: set = set()
        for dim, name in zip(shape, logical):
            mapped = self.axis_map.get(name) if name else None
            if mapped is None:
                spec.append(None)
                continue
            axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
            # drop axes already used by an earlier dim of this leaf
            axes = tuple(a for a in axes if a not in used)
            size = int(np.prod([self.mesh.shape[a] for a in axes])) if axes else 1
            if not axes or size <= 1 or dim % size != 0:
                # shape-aware fallback: try progressively shorter prefixes
                ok = ()
                for k in range(len(axes), 0, -1):
                    sz = int(np.prod([self.mesh.shape[a] for a in axes[:k]]))
                    if dim % sz == 0 and sz > 1:
                        ok = axes[:k]
                        break
                axes = ok
            if axes:
                used.update(axes)
                spec.append(axes[0] if len(axes) == 1 else tuple(axes))
            else:
                spec.append(None)
        return P(*spec)

    def act_pspec(self, kind: str) -> P:
        logical = ACT_RULES[kind]
        spec = []
        for name in logical:
            mapped = self.axis_map.get(name) if name else None
            if mapped is None:
                spec.append(None)
            else:
                spec.append(mapped)
        return P(*spec)


def _present(mesh: Mesh, *names: str) -> Tuple[str, ...]:
    return tuple(n for n in names if n in mesh.axis_names)


def _profile_axis_map(profile: str, mesh: Mesh) -> Dict[str, Any]:
    batch = _present(mesh, "pod", "data")
    batch = batch if batch else None
    base: Dict[str, Any] = {
        "batch": batch,
        "vocab": None,
        "embed": None,
        "mlp": None,
        "heads": None,
        "kv_heads": None,
        "head_dim": None,
        "expert": None,
        "seq_act": None,
        "act_embed": None,
        "vocab_act": None,
    }
    if profile == "dp":
        return base
    if profile in ("dp_tp", "fsdp_tp", "fsdp_tp_sp", "tp"):
        base.update(
            {
                "vocab": "model",
                "mlp": "model",
                "heads": "model",
                "kv_heads": "model",
                "expert": "model",
                "vocab_act": "model",
            }
        )
    if profile in ("fsdp_tp", "fsdp_tp_sp"):
        fsdp = _present(mesh, "pod", "data")
        base["embed"] = fsdp if fsdp else None
        # 2-D expert sharding: E over (data × model) puts each expert on as
        # few chips as possible — fully local expert weights for EP
        # (divisibility fallback keeps 1-D sharding when E % (d·m) != 0)
        base["expert"] = _present(mesh, "data", "model") or "model"
    if profile == "fsdp_tp_sp":
        base["seq_act"] = "model"
    if profile == "tp":
        base["batch"] = None
    return base


PROFILES = ("dp", "dp_tp", "fsdp_tp", "fsdp_tp_sp", "tp")


def make_rules(mesh: Mesh, profile: str) -> ShardingRules:
    if profile not in PROFILES:
        raise ValueError(f"unknown sharding profile {profile!r}; options: {PROFILES}")
    return ShardingRules(mesh=mesh, axis_map=_profile_axis_map(profile, mesh))


def logical_to_pspec(rules: ShardingRules, path: str, shape: Sequence[int]) -> P:
    return rules.pspec_for(path, shape)


def pspec_tree_for_params(rules: ShardingRules, params: Any) -> Any:
    """A pytree of PartitionSpec matching ``params``' structure."""
    return tree_map_with_path(lambda p, x: rules.pspec_for(p, x.shape), params)


def shardings_for_tree(rules: ShardingRules, params: Any) -> Any:
    """A pytree of NamedSharding matching ``params``' structure."""
    return tree_map_with_path(
        lambda p, x: NamedSharding(rules.mesh, rules.pspec_for(p, x.shape)), params
    )
