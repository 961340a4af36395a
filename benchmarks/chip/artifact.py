"""The system under test: a ServeEngine over a packed artifact whose codes
come from ``weights`` (drawn on the device, in one jitted call, from the
seed), with the program's configuration built from the configuration file
and the cell's server settings."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from benchmarks.chip import weights

# configuration-file keys -> ModelConfig fields
_MODEL_KEYS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_base",
    "tie_word_embeddings": "tie_lm_head",
    "qk_norm": "qk_norm",
    "num_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "d_ff_expert",
    "moe_capacity_factor": "capacity_factor",
    "moe_impl": "moe_impl",
}


def model_config(spec: dict, server: dict):
    """The program's ModelConfig for a configuration file and the cell's
    server settings (the KV pool's number format is a server choice)."""
    from repro.configs import get_config

    base = get_config(spec["program_config"])
    kw = {field: spec[key] for key, field in _MODEL_KEYS.items() if key in spec}
    if spec.get("intermediate_size"):
        kw["d_ff"] = spec["intermediate_size"]
    kw["qk_norm"] = bool(spec.get("qk_norm"))
    kw["kv_cache_dtype"] = server["kv_pool"]
    return dataclasses.replace(base, **kw)


def _per_layer(fn, layers: int, offset: int):
    return jax.lax.map(lambda i: fn(offset + i), jnp.arange(layers, dtype=jnp.int32))


def build_params(cfg, spec: dict, seed: int):
    """The packed serving artifact of ``cfg``, drawn from ``seed`` by one
    jitted call on the default device.  Leaves that the program quantizes
    become ``Packed`` words of the drawn codes; the others are drawn
    floats.  The tree has exactly the program's own structure."""
    from repro.core import Packed, default_quant_filter, pack_int
    from repro.models import init_lm
    from repro.models.lm import scan_groups
    from repro.nn.tree import flatten_with_paths

    bits = spec["weight_bits"]
    shapes = jax.eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))
    flat = dict(flatten_with_paths(shapes))
    groups, offset = {}, 0
    for g in scan_groups(cfg):
        if len(g.unit) != 1 or not g.stacked:
            raise NotImplementedError(f"group {g.name} {g.unit}: only stacked one-kind groups")
        groups[g.name] = (offset, g.count)
        offset += g.count
    leaves = weights.layer_leaves(spec)

    def packed(c, f):
        return Packed(data=pack_int(c, bits), n_bits=bits, f=jnp.asarray(f, jnp.int32))

    def build(key):
        out = {}
        for path, sds in flat.items():
            quantized = default_quant_filter(path, sds)
            if path == "embed/embedding":
                out[path] = packed(*weights.embedding_codes(key, spec))
            elif path == "lm_head/kernel":
                out[path] = packed(*weights.head_codes(key, spec))
            elif path == "final_norm/scale":
                out[path] = weights.final_norm(key, spec)
            else:
                group, _, name = path.split("/", 2)
                first, count = groups[group]
                kind = leaves[name][0]
                if quantized != (kind == "codes"):
                    raise ValueError(f"{path}: the program quantizes={quantized}, drawn as {kind}")
                if kind == "codes":
                    data, f = _per_layer(
                        lambda i, n=name: (lambda c, e: (pack_int(c, bits), e))(
                            *weights.layer_codes(key, spec, n, i)), count, first)
                    out[path] = Packed(data=data, n_bits=bits, f=f)
                else:
                    out[path] = _per_layer(
                        lambda i, n=name: weights.layer_float(key, spec, n, i), count, first)
            got = out[path].shape
            if tuple(got) != tuple(sds.shape):
                raise ValueError(f"{path}: drawn shape {got}, program shape {sds.shape}")
        return _unflatten(out)

    params = jax.jit(build)(weights.root_key(seed))
    return jax.block_until_ready(params)


def _unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree
