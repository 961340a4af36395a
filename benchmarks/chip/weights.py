"""Random SYMOG weights drawn as integer codes from a seed.

A served SYMOG model is a packed artifact: every quantized weight is an
integer mantissa (code) times a power-of-two step 2^-f, one exponent per
leaf (per expert for expert stacks).  The benchmark draws those codes and
exponents directly, so no float initialisation and no step-size search run
in a benchmark run.  The program's artifact packs these codes; the plain
reference turns the same codes into float32 weights.  Both sides call the
functions here with the same key, leaf name and layer, so they see the same
numbers without sharing any array.

2-bit codes take the values -1, 0, 1 with probabilities 1/4, 1/2, 1/4, so a
weight's variance is 2^-2f / 2; the exponent is chosen per leaf so that this
is about 1 / fan_in, plus 0 or 1 drawn from the seed.

This module imports nothing from the program under test.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any seed below 2**64 (beyond the 31 bits PRNGKey takes)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF), seed >> 63)


def leaf_key(key, name: str, layer) -> jax.Array:
    """Key of one leaf of one layer; ``layer`` may be traced."""
    return jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())), layer)


def base_exponent(fan_in: int) -> int:
    """f with 2^-2f / 2 closest to 1 / fan_in."""
    return int(round(0.5 * math.log2(fan_in / 2)))


def codes(key, shape, n_bits: int = 2) -> jax.Array:
    """2-bit codes -1, 0, 1 as int8 (the only width the cells serve)."""
    if n_bits != 2:
        raise NotImplementedError(f"{n_bits}-bit codes: only 2-bit weights are drawn")
    bits = jax.random.bits(key, shape, jnp.uint8)
    low, high = (bits & 1).astype(jnp.int8), ((bits >> 1) & 1).astype(jnp.int8)
    return low * (1 - 2 * high)


def topic_codes(key, vocab: int, dim: int, n_topics: int, share: float,
                n_bits: int = 2) -> jax.Array:
    """Embedding codes whose rows in one contiguous vocabulary slice share
    a ``share`` of their entries with a code vector of that slice (topic),
    so tokens of one topic route alike in a mixture of experts."""
    own = codes(jax.random.fold_in(key, 0), (vocab, dim), n_bits)
    if not n_topics:
        return own
    width = vocab // n_topics
    topic = jnp.minimum(jnp.arange(vocab) // width, n_topics - 1)
    common = codes(jax.random.fold_in(key, 1), (n_topics, dim), n_bits)[topic]
    pick = jax.random.bernoulli(jax.random.fold_in(key, 2), share, (vocab, dim))
    return jnp.where(pick, common, own)


def exponents(key, lead_shape, fan_in: int) -> jax.Array:
    """Per-leaf (``lead_shape`` ()) or per-expert exponents as int32."""
    jitter = jax.random.randint(jax.random.fold_in(key, 7), lead_shape, 0, 2, jnp.int32)
    return base_exponent(fan_in) + jitter


def norm_scale(key, shape) -> jax.Array:
    """RMSNorm gain offset (the program's norm multiplies by 1 + scale)."""
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


def router_kernel(key, d_model: int, n_experts: int) -> jax.Array:
    return jax.random.normal(key, (d_model, n_experts), jnp.float32) / math.sqrt(d_model)


def dequantize(c, f) -> jax.Array:
    """Float32 weights code * 2^-f; per-expert ``f`` broadcasts over rows."""
    scale = jnp.exp2(-jnp.asarray(f, jnp.float32))
    scale = jnp.reshape(scale, jnp.shape(scale) + (1,) * (c.ndim - jnp.ndim(scale)))
    return c.astype(jnp.float32) * scale


# ---------------------------------------------------------------------------
# the model's leaves: name -> how to draw one layer of it
# ---------------------------------------------------------------------------
def layer_leaves(spec: dict):
    """{name: (kind, per-layer shape, fan_in, per-expert)} of one decoder
    layer, for the configuration file ``spec``.  ``kind`` is 'codes',
    'norm' or 'router'.  Shapes are the (in..., out...) projection layout."""
    D, H, K = spec["hidden_size"], spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    out = {
        "pre_norm/scale": ("norm", (D,), 0, False),
        "attn/q_proj/kernel": ("codes", (D, H, hd), D, False),
        "attn/k_proj/kernel": ("codes", (D, K, hd), D, False),
        "attn/v_proj/kernel": ("codes", (D, K, hd), D, False),
        "attn/o_proj/kernel": ("codes", (H, hd, D), H * hd, False),
        "pre_mlp_norm/scale": ("norm", (D,), 0, False),
    }
    if spec.get("qk_norm"):
        out["attn/q_norm/scale"] = ("norm", (hd,), 0, False)
        out["attn/k_norm/scale"] = ("norm", (hd,), 0, False)
    E = spec.get("num_experts", 0)
    if E:
        Fe = spec["moe_intermediate_size"]
        out["moe/router/kernel"] = ("router", (D, E), D, False)
        out["moe/experts/gate_proj/kernel"] = ("codes", (E, D, Fe), D, True)
        out["moe/experts/up_proj/kernel"] = ("codes", (E, D, Fe), D, True)
        out["moe/experts/down_proj/kernel"] = ("codes", (E, Fe, D), Fe, True)
    else:
        F = spec["intermediate_size"]
        out["mlp/gate_proj/kernel"] = ("codes", (D, F), D, False)
        out["mlp/up_proj/kernel"] = ("codes", (D, F), D, False)
        out["mlp/down_proj/kernel"] = ("codes", (F, D), F, False)
    return out


def layer_codes(key, spec: dict, name: str, layer):
    """(codes, exponents) of a quantized leaf of one layer."""
    _, shape, fan_in, per_expert = layer_leaves(spec)[name]
    k = leaf_key(key, name, layer)
    lead = shape[:1] if per_expert else ()
    return codes(k, shape, spec["weight_bits"]), exponents(k, lead, fan_in)


def layer_float(key, spec: dict, name: str, layer) -> jax.Array:
    """A float leaf (norm gain offset or router) of one layer."""
    kind, shape, _, _ = layer_leaves(spec)[name]
    k = leaf_key(key, name, layer)
    if kind == "router":
        return router_kernel(k, *shape)
    return norm_scale(k, shape)


def embedding_codes(key, spec: dict):
    """(codes (vocab, d), exponent) of the token embedding."""
    k = leaf_key(key, "embed/embedding", 0)
    c = topic_codes(k, spec["vocab_size"], spec["hidden_size"],
                    spec.get("embedding_topics", 0), spec.get("embedding_topic_share", 0.0),
                    spec["weight_bits"])
    return c, exponents(k, (), spec["hidden_size"])


def head_codes(key, spec: dict):
    """(codes (d, vocab), exponent) of an untied output head."""
    k = leaf_key(key, "lm_head/kernel", 0)
    shape = (spec["hidden_size"], spec["vocab_size"])
    return codes(k, shape, spec["weight_bits"]), exponents(k, (), spec["hidden_size"])


def final_norm(key, spec: dict) -> jax.Array:
    return norm_scale(leaf_key(key, "final_norm/scale", 0), (spec["hidden_size"],))


def layer_weights(key, spec: dict, layer) -> dict:
    """Every leaf of one layer as float32 (what the reference computes with)."""
    out = {}
    for name, (kind, _, _, _) in layer_leaves(spec).items():
        if kind == "codes":
            out[name] = dequantize(*layer_codes(key, spec, name, layer))
        else:
            out[name] = layer_float(key, spec, name, layer)
    return out
