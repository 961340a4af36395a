"""Plain float32 forward pass of the benchmark's decoder configurations.

Straightforward ``jax.numpy`` at ``highest`` matmul precision: token
embedding, then per layer RMSNorm -> attention (GQA or MHA, per-head
qk-norm, rotary embedding, causal softmax) -> RMSNorm -> SwiGLU MLP or a
softmax top-k mixture of experts, then a final RMSNorm and the output head
(tied to the embedding or not).  Where the cell's KV pool is int8, keys and
values are rounded as the configuration states the pool: per block of
``block`` positions and per KV head, a power-of-two step calibrated from
the block's first position.  Weights are regenerated layer by layer from
the seed (``weights``); nothing of the program is imported or used.

``control`` rounds every matmul input and the attention operands to
float8 (e4m3), the step below the bfloat16 the configuration computes in,
and holds an int8 pool's keys and values at 4 bits, the step below int8:
the comparison must fail it.

Only the logits the comparison needs leave the device: at each scored
position, the best reference logit, the reference logit of the token the
program served, and (control) the reference logit of the control's
first choice.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights

KV_QMAX = {"int8_fp": 127, "int4_fp": 7}
CONTROL_POOL = {"bf16": "bf16", "int8_fp": "int4_fp"}
KV_EXP_MIN, KV_EXP_MAX = -20, 20
Q_CHUNK = 512


def _round(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale)


def rope(x, positions, base):
    """Rotary embedding on (T, heads, hd), halves rotated (x1, x2)."""
    half = x.shape[-1] // 2
    freq = jnp.exp(-math.log(base) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None, None].astype(jnp.float32) * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def pool_round(x, pool: str, block: int):
    """(T, K, hd) keys or values as a quantized pool holds them: each
    block's step 2^e per head from its first position's largest magnitude,
    with one bit of headroom; values rounded to nearest and clipped."""
    if pool == "bf16":
        return x
    qmax = KV_QMAX[pool]
    T = x.shape[0]
    first = x[(jnp.arange(T) // block) * block]  # (T, K, hd)
    amax = jnp.max(jnp.abs(first), axis=-1, keepdims=True)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 2.0**-30)) + 1.0 - math.log2(qmax))
    e = jnp.clip(e, KV_EXP_MIN, KV_EXP_MAX)
    return jnp.clip(jnp.round(x * jnp.exp2(-e)), -qmax, qmax) * jnp.exp2(e)


def attention(q, k, v, valid_len):
    """Causal attention, q (T, H, hd), k/v (T, K, hd); query chunks bound
    the (H, chunk, T) score block."""
    T, H, hd = q.shape
    K = k.shape[1]
    g = H // K
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    n = -(-T // Q_CHUNK)
    qp = jnp.pad(q, ((0, n * Q_CHUNK - T), (0, 0), (0, 0))).reshape(n, Q_CHUNK, H, hd)
    kpos = jnp.arange(T)

    def one(args):
        qc, c = args
        qpos = c * Q_CHUNK + jnp.arange(Q_CHUNK)
        s = jnp.einsum("thd,shd->hts", qc, k) / math.sqrt(hd)
        mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] < valid_len)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hts,shd->thd", p, v)

    out = jax.lax.map(one, (qp, jnp.arange(n)))
    return out.reshape(n * Q_CHUNK, H, hd)[:T]


def moe(h, w, spec, lo):
    """Softmax router over all experts, top-k, gates renormalised over the
    k chosen; every expert computes every token, weighted by its gate."""
    E, k = spec["num_experts"], spec["num_experts_per_tok"]
    logits = h @ w["moe/router/kernel"]
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-9)
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], idx].set(top)
    x = _round(h, lo)
    gate = jnp.einsum("td,edf->etf", x, w["moe/experts/gate_proj/kernel"])
    up = jnp.einsum("td,edf->etf", x, w["moe/experts/up_proj/kernel"])
    act = _round(jax.nn.silu(gate) * up, lo)
    out = jnp.einsum("etf,efd->etd", act, w["moe/experts/down_proj/kernel"])
    return jnp.einsum("te,etd->td", gates, out)


def layer(x, w, positions, valid_len, *, spec, pool, block, lo):
    eps = spec["rms_norm_eps"]
    T, D = x.shape
    H, K, hd = spec["num_attention_heads"], spec["num_key_value_heads"], spec["head_dim"]
    h = _round(rmsnorm(x, w["pre_norm/scale"], eps), lo)
    q = jnp.einsum("td,dhk->thk", h, w["attn/q_proj/kernel"])
    k = jnp.einsum("td,dhk->thk", h, w["attn/k_proj/kernel"])
    v = jnp.einsum("td,dhk->thk", h, w["attn/v_proj/kernel"])
    if spec.get("qk_norm"):
        q = rmsnorm(q, w["attn/q_norm/scale"], eps)
        k = rmsnorm(k, w["attn/k_norm/scale"], eps)
    q = rope(q, positions, spec["rope_theta"])
    k = rope(k, positions, spec["rope_theta"])
    k, v = pool_round(k, pool, block), pool_round(v, pool, block)
    a = attention(_round(q, lo), _round(k, lo), _round(v, lo), valid_len)
    x = x + jnp.einsum("thk,hkd->td", _round(a, lo), w["attn/o_proj/kernel"])
    h = _round(rmsnorm(x, w["pre_mlp_norm/scale"], eps), lo)
    if spec.get("num_experts", 0):
        return x + moe(h, w, spec, lo)
    act = jax.nn.silu(h @ w["mlp/gate_proj/kernel"]) * (h @ w["mlp/up_proj/kernel"])
    return x + _round(act, lo) @ w["mlp/down_proj/kernel"]


class Reference:
    """The float32 model of one configuration file, cell pool and seed."""

    def __init__(self, spec: dict, pool: str, block: int, seed: int):
        self.spec, self.pool, self.block = spec, pool, block
        self.key = weights.root_key(seed)
        self._layer_weights = jax.jit(functools.partial(weights.layer_weights, spec=spec))
        self._embed = jax.jit(lambda key: weights.dequantize(*weights.embedding_codes(key, spec)))
        self._head = jax.jit(lambda key: weights.dequantize(*weights.head_codes(key, spec)))
        self._final = jax.jit(functools.partial(weights.final_norm, spec=spec))
        self._layers = {}

    def _layer_fn(self, lo):
        if lo not in self._layers:
            pool = self.pool if lo is None else CONTROL_POOL[self.pool]
            self._layers[lo] = jax.jit(functools.partial(
                layer, spec=self.spec, pool=pool, block=self.block, lo=lo))
        return self._layers[lo]

    def _hidden(self, tokens: np.ndarray, rows: np.ndarray, lo):
        """Final-normed hidden states at ``rows`` of the teacher-forced
        sequence ``tokens``, and the head matrix (d, vocab)."""
        spec = self.spec
        T = len(tokens)
        Tp = max(Q_CHUNK, 1 << (T - 1).bit_length())
        ids = np.zeros(Tp, np.int32)
        ids[:T] = tokens
        emb = self._embed(self.key)
        x = emb[jnp.asarray(ids)]
        positions = jnp.arange(Tp, dtype=jnp.int32)
        fn = self._layer_fn(lo)
        for i in range(spec["num_hidden_layers"]):
            w = self._layer_weights(self.key, layer=jnp.int32(i))
            x = fn(x, w, positions, jnp.int32(T))
        h = rmsnorm(x[jnp.asarray(rows)], self._final(self.key), spec["rms_norm_eps"])
        head = emb.T if spec["tie_word_embeddings"] else self._head(self.key)
        return h, head

    def logits(self, tokens: np.ndarray) -> np.ndarray:
        """Float32 logits (T, vocab) at every position of ``tokens``."""
        with jax.default_matmul_precision("highest"):
            h, head = self._hidden(tokens, np.arange(len(tokens)), None)
            return np.asarray(h @ head, np.float32)

    def score(self, tokens: np.ndarray, first: int, served: np.ndarray, control: bool = False):
        """Teacher-forced over ``tokens`` (prompt, then served tokens).  At
        the positions first .. first + len(served) - 1, which predicted the
        served tokens, returns float32 arrays: the best reference logit, the
        reference logit of each served token and, with ``control``, the
        reference logit of the token the float8 control puts first."""
        rows = np.arange(first, first + len(served))
        served = jnp.asarray(np.asarray(served, np.int32))
        with jax.default_matmul_precision("highest"):
            h, head = self._hidden(tokens, rows, None)
            ref = h @ head
            out = [jnp.max(ref, axis=-1),
                   jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]]
            if control:
                lo = jnp.float8_e4m3fn
                hc, _ = self._hidden(tokens, rows, lo)
                choice = jnp.argmax(_round(hc, lo) @ head, axis=-1)
                out.append(jnp.take_along_axis(ref, choice[:, None], axis=-1)[:, 0])
        return [np.asarray(o, np.float32) for o in out]
