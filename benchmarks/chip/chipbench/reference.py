"""The plain reference: the published decoder in float32 ``jax.numpy``.

It imports nothing of the program.  Its weights come from the seed
through ``chipbench.weights`` — the same function of (seed, path, layer)
that made the served tree — one layer at a time, dequantized element by
element, so nothing the program made is read.  Every contraction runs at
``Precision.HIGHEST``.

Architecture, as the configuration file states it: pre-norm RMSNorm
blocks (scale one), GQA attention with optional per-head RMS q/k norm
before RoPE, SwiGLU FFN, final RMSNorm, tied or untied head; the padded
vocabulary rows are masked.  RoPE rotates adjacent pairs (2i, 2i + 1) at
frequency theta^(-2i/hd), as the program does; the published rotate-half
form is the same model under a fixed permutation of each head's q and k
columns, which random weights do not tell apart.

``lower="fp8"`` is the control: every contraction's operands rounded to
float8 e4m3 (weights per tensor, activations per row, scaled to the
format's 448 maximum) — the precision step below the configuration's
bf16.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    padded_vocab: int
    tied: bool
    qk_norm: bool
    eps: float
    theta: float
    n_weights: int
    std: float
    index_dtype: str

    @classmethod
    def of(cls, config: dict) -> "Arch":
        hf, a = config["hf"], config["assumed"]
        heads = hf["num_attention_heads"]
        return cls(
            layers=hf["num_hidden_layers"], d=hf["hidden_size"], heads=heads,
            kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim", hf["hidden_size"] // heads),
            ffn=hf["intermediate_size"], vocab=hf["vocab_size"],
            padded_vocab=a["padded_vocab"],
            tied=bool(hf["tie_word_embeddings"]),
            qk_norm=bool(a["qk_norm"]), eps=float(hf["rms_norm_eps"]),
            theta=float(hf["rope_theta"]), n_weights=int(a["n_weights"]),
            std=float(a["weight_std"]), index_dtype=a["index_dtype"])

    def layer_shapes(self) -> dict:
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return {"attn/wq": (self.d, q), "attn/wk": (self.d, kv),
                "attn/wv": (self.d, kv), "attn/wo": (q, self.d),
                "mlp/w1": (self.d, self.ffn), "mlp/w3": (self.d, self.ffn),
                "mlp/w2": (self.ffn, self.d)}


def _dequant(arch: Arch, key, path: str, shape, layer=None):
    ids = W.index_plane(key, path, shape, arch.n_weights,
                        jnp.dtype(arch.index_dtype), layer)
    return W.codebook_value(key, ids, arch.n_weights, arch.std)


def _f8(x, axis):
    """x rounded to float8 e4m3 at a max-abs scale over ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / _F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, lower):
    if lower == "fp8":
        x, w = _f8(x, -1), _f8(w, None)
    return jnp.dot(x, w, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: (R, T, H, hd); adjacent pairs rotate together."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[None, :, None, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_weights(arch: Arch, key, layer):
    return {name: _dequant(arch, key, f"blocks/{name}/w_idx", shape, layer)
            for name, shape in arch.layer_shapes().items()}


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(arch: Arch, x, w, lower):
    R, T, _ = x.shape
    H, KV, hd = arch.heads, arch.kv_heads, arch.head_dim
    G = H // KV
    pos = jnp.arange(T)
    h = _rms(x, arch.eps)
    q = _mm(h, w["attn/wq"], lower).reshape(R, T, H, hd)
    k = _mm(h, w["attn/wk"], lower).reshape(R, T, KV, hd)
    v = _mm(h, w["attn/wv"], lower).reshape(R, T, KV, hd)
    if arch.qk_norm:
        q, k = _rms(q, arch.eps), _rms(k, arch.eps)
    q, k = _rope(q, pos, arch.theta), _rope(k, pos, arch.theta)
    causal = pos[:, None] >= pos[None, :]

    def head_group(args):                 # one KV head and its G q-heads
        qg, kg, vg = args                 # (R,T,G,hd), (R,T,hd), (R,T,hd)
        if lower == "fp8":
            qg, kg, vg = _f8(qg, -1), _f8(kg, -1), _f8(vg, -2)
        s = jnp.einsum("rtgd,rsd->rgts", qg, kg,
                       precision=HI) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        if lower == "fp8":
            p = _f8(p, -1)
        return jnp.einsum("rgts,rsd->rtgd", p, vg, precision=HI)

    o = jax.lax.map(head_group, (
        jnp.moveaxis(q.reshape(R, T, KV, G, hd), 2, 0),
        jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    o = jnp.moveaxis(o, 0, 2).reshape(R, T, H * hd)
    x = x + _mm(o, w["attn/wo"], lower)
    h = _rms(x, arch.eps)
    f = jax.nn.silu(_mm(h, w["mlp/w1"], lower)) * _mm(h, w["mlp/w3"], lower)
    return x + _mm(f, w["mlp/w2"], lower)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(arch: Arch, key, tokens):
    table = _dequant(arch, key, "embed/w_idx", (arch.padded_vocab, arch.d))
    return table[tokens]


@functools.partial(jax.jit, static_argnums=(0,))
def _head(arch: Arch, key):
    """The (d, padded vocab) output matrix."""
    if arch.tied:
        return _dequant(arch, key, "embed/w_idx",
                        (arch.padded_vocab, arch.d)).T
    return _dequant(arch, key, "lm_head/w_idx", (arch.d, arch.padded_vocab))


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(arch: Arch, h, head, lower):
    lg = _mm(_rms(h, arch.eps), head, lower)
    return jnp.where(jnp.arange(arch.padded_vocab) < arch.vocab, lg, -jnp.inf)


def hidden_rows(arch: Arch, key, tokens: np.ndarray, rows, lower=None):
    """Final hidden states (before the final norm) at ``rows`` [(r, t)]
    of the (R, T) token batch; causal, so right padding is inert."""
    x = _embed(arch, key, jnp.asarray(tokens, jnp.int32))
    for layer in range(arch.layers):
        x = _layer(arch, x, _layer_weights(arch, key, layer), lower)
    r, t = np.asarray(rows, np.int32).T
    return x[r, t]


def served_gaps(config: dict, seed: int, samples, rows_per_request: int,
                seq_len: int, lower=None, block: int = 256) -> dict:
    """For each served token of each sample — (prompt, served tokens) —
    the gap by which the reference's logit for it lies below the
    reference's best at that position.  With ``lower``, also the gap of
    the token the lower-precision control puts first there."""
    arch = Arch.of(config)
    key = W.base_key(seed)
    R = rows_per_request
    if len(samples) > R:
        raise ValueError(f"{len(samples)} samples > {R} reference rows")
    tokens = np.zeros((R, seq_len), np.int32)
    rows, want = [], []
    for r, (prompt, served) in enumerate(samples):
        seq = list(prompt) + list(served[:-1])
        if len(seq) > seq_len:
            raise ValueError(f"sample of {len(seq)} tokens > {seq_len}")
        tokens[r, :len(seq)] = seq
        for i, tok in enumerate(served):
            rows.append((r, len(prompt) - 1 + i))
            want.append(tok)
    h_ref = hidden_rows(arch, key, tokens, rows)
    h_low = (hidden_rows(arch, key, tokens, rows, lower)
             if lower else None)
    head = _head(arch, key)
    want = np.asarray(want)
    n = len(want)
    pad = -n % block
    out = {"served": [], "control": []}
    for a in range(0, n + pad, block):
        hb = jnp.pad(h_ref[a:a + block], ((0, max(0, a + block - n)),
                                          (0, 0)))
        ref = np.asarray(_logits(arch, hb, head, None))[:min(block, n - a)]
        best = ref.max(axis=1)
        idx = np.arange(len(ref))
        out["served"].extend(best - ref[idx, want[a:a + len(ref)]])
        if lower:
            lb = jnp.pad(h_low[a:a + block], ((0, max(0, a + block - n)),
                                              (0, 0)))
            low = np.asarray(_logits(arch, lb, head, lower))[:len(ref)]
            out["control"].extend(best - ref[idx, low.argmax(axis=1)])
    return {k: [float(x) for x in v] for k, v in out.items()}
