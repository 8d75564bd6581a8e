"""The plain reference: a dense decoder's forward pass in float32 jax.numpy.

No kernels, no cache, no batching tricks, and nothing of the program: the
weights are rebuilt from the seed by `weights` (FC weights through
`formats.dequantized`), the shapes come from the configuration's "model"
block. Matmuls run at `Precision.HIGHEST`. Layers run one at a time, so
only one layer's dense float32 weights live on the device at once.

The block is the one the configuration describes, with the departures its
file lists: x = embed[t] (+ pos_embed[p]); per layer
x += Wo attn(rope(rms(x) Wq), rope(rms(x) Wk), rms(x) Wv) (causal, GQA,
scale 1/sqrt(d_head)); x += mlp(rms(x)) with relu(h Wup) Wdown or
(silu(h Wgate) * h Wup) Wdown; logits = rms(x) embed^T. rms(x) is
x / sqrt(mean(x^2) + eps) * (1 + g).

`precision="fp8"` is the control: every operand of every weight matmul
rounded to float8 e4m3 (activations scaled per row, weights per column,
each by its largest magnitude), the step below the bfloat16 that the
configurations state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import weights

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def fc_shapes(m: dict) -> list:
    """[(role, (L, K, N))] of the FC weights."""
    L, d, f = m["n_layers"], m["d_model"], m["d_ff"]
    q, kv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    out = [("wq", (L, d, q)), ("wk", (L, d, kv)), ("wv", (L, d, kv)),
           ("wo", (L, q, d))]
    if m["mlp_act"] in ("swiglu", "geglu"):
        out += [("w_gate", (L, d, f)), ("w_up", (L, d, f))]
    else:
        out += [("w_up", (L, d, f))]
    return out + [("w_down", (L, f, d))]


def dense_shapes(m: dict) -> list:
    """[(role, shape, dtype)] of every other weight."""
    L, d, v = m["n_layers"], m["d_model"], m["vocab_size"]
    out = [("embed", (v, d), jnp.bfloat16), ("final_norm", (d,), jnp.float32),
           ("pre_norm", (L, d), jnp.float32),
           ("pre_mlp_norm", (L, d), jnp.float32)]
    if m.get("pos_emb", "rope") == "learned":
        out.append(("pos_embed", (m.get("pos_table", 4096), d), jnp.bfloat16))
    if not m.get("tie_embeddings", False):
        out.append(("lm_head", (d, v), jnp.bfloat16))
    return out


def _fp8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(x, w, fp8: bool):
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.dot(x, w, precision=HIGHEST)


def _rms(x, g, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * (1.0 + g)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh // 2, dtype=jnp.float32) * 2 / dh)
    ang = pos[..., None].astype(jnp.float32) * freqs  # (B, S, dh/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_layer(m: dict, fp8: bool):
    hq, hkv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    eps, glu = m["norm_eps"], m["mlp_act"] in ("swiglu", "geglu")
    rope = m.get("pos_emb", "rope") == "rope"
    theta = m.get("rope_theta", 10000.0)

    @jax.jit
    def layer(x, w, g1, g2, pos):
        b, s, _ = x.shape
        h = _rms(x, g1, eps)
        q = _mm(h, w["wq"], fp8).reshape(b, s, hq, dh)
        k = _mm(h, w["wk"], fp8).reshape(b, s, hkv, dh)
        v = _mm(h, w["wv"], fp8).reshape(b, s, hkv, dh)
        if rope:
            q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        q = q.reshape(b, s, hkv, hq // hkv, dh)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, precision=HIGHEST)
        sc = sc / math.sqrt(dh)
        causal = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(causal, sc, -jnp.inf)
        att = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(sc, axis=-1), v,
                         precision=HIGHEST).reshape(b, s, hq * dh)
        x = x + _mm(att, w["wo"], fp8)
        h = _rms(x, g2, eps)
        if glu:
            up = jax.nn.silu(_mm(h, w["w_gate"], fp8)) * _mm(h, w["w_up"], fp8)
        else:
            up = jax.nn.relu(_mm(h, w["w_up"], fp8))
        return x + _mm(up, w["w_down"], fp8)

    return layer


def hidden(m: dict, weight_format: str, seed: int, tokens: np.ndarray,
           rows: int, fp8: bool = False):
    """Final hidden states of the reference over `tokens` (R, S), at
    positions 0..S-1, as chunks of `rows` rows (the last chunk padded with
    copies of the first row), and the head that turns one into logits.
    Layers run one at a time: each layer's dense weights are built once and
    applied to every chunk."""
    layout = fc_shapes(m)
    fc = weights.dense_fc(layout, weight_format, seed)
    dense = weights.dense_leaves(dense_shapes(m), seed)
    tokens = np.asarray(tokens, np.int32)
    pad = -len(tokens) % rows
    tokens = np.concatenate([tokens, np.repeat(tokens[:1], pad, 0)])
    s = tokens.shape[1]
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (rows, s))

    def embed(chunk):
        x = jnp.take(dense["embed"], chunk, axis=0).astype(jnp.float32)
        if "pos_embed" in dense:
            table = dense["pos_embed"]
            x = x + jnp.take(table, jnp.clip(pos, 0, table.shape[0] - 1),
                             axis=0).astype(jnp.float32)
        return x

    xs = [embed(jnp.asarray(tokens[i:i + rows]))
          for i in range(0, len(tokens), rows)]
    layer = make_layer(m, fp8)
    for i in range(m["n_layers"]):
        w = {role: fc(role, i) for role, _ in layout}
        g1, g2 = dense["pre_norm"][i], dense["pre_mlp_norm"][i]
        xs = [layer(x, w, g1, g2, pos) for x in xs]
        del w
    head = dense["embed"].T if "lm_head" not in dense else dense["lm_head"]
    return xs, lambda x: _head(x, dense["final_norm"], head, m["norm_eps"], fp8)


@jax.jit
def _rows(logits, idx):
    """The logits rows (B, T, V) at positions `idx` (B, T)."""
    return jnp.take_along_axis(logits, idx[..., None], axis=1)


@jax.jit
def _gap_of(rows, token):
    """The reference's best logit minus its logit of `token`, per position."""
    chosen = jnp.take_along_axis(rows, token[..., None], -1)[..., 0]
    return rows.max(-1) - chosen


def _head_impl(x, g, head, eps, fp8):
    h = _rms(x, g, eps)
    return _mm(h, head.astype(jnp.float32), fp8)


_head = jax.jit(_head_impl, static_argnums=(3, 4))


def served_gaps(m: dict, weight_format: str, seed: int, requests, pad_to: int,
                rows: int, control: bool = False) -> dict:
    """For served requests [(prompt, served tokens)], the gap by which each
    served token's reference logit lies below the reference's best at its
    position. With `control`, also the gap of the token the fp8 control
    puts first at each of those positions. `rows` requests run together.
    Returns {"served": [(n,) per request], "control": [...] or None}."""
    r_n = len(requests)
    tokens = np.zeros((r_n, pad_to), np.int32)
    tlen = max(len(o) for _, o in requests)
    idx = np.zeros((r_n, tlen), np.int32)
    target = np.zeros((r_n, tlen), np.int32)
    for r, (prompt, out) in enumerate(requests):
        seq = np.concatenate([prompt, out[:-1]]).astype(np.int32)
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} tokens > pad_to={pad_to}")
        tokens[r, : len(seq)] = seq
        idx[r, : len(out)] = len(prompt) - 1 + np.arange(len(out))
        target[r, : len(out)] = out
    pad = -r_n % rows
    idx = np.concatenate([idx, np.repeat(idx[:1], pad, 0)])
    target = np.concatenate([target, np.repeat(target[:1], pad, 0)])
    chunks = range(0, r_n + pad, rows)
    xs, head = hidden(m, weight_format, seed, tokens, rows)
    ref_rows = [_rows(head(x), jnp.asarray(idx[i:i + rows]))
                for i, x in zip(chunks, xs)]
    del xs
    served = np.concatenate([
        np.asarray(_gap_of(rr, jnp.asarray(target[i:i + rows])))
        for i, rr in zip(chunks, ref_rows)
    ])
    ctrl = None
    if control:
        xs, head = hidden(m, weight_format, seed, tokens, rows, fp8=True)
        firsts = [_rows(head(x), jnp.asarray(idx[i:i + rows])).argmax(-1)
                  for i, x in zip(chunks, xs)]
        del xs
        ctrl = np.concatenate([np.asarray(_gap_of(rr, f))
                               for rr, f in zip(ref_rows, firsts)])
    n = [len(o) for _, o in requests]
    return {
        "served": [served[r, : n[r]] for r in range(r_n)],
        "control": None if ctrl is None else [ctrl[r, : n[r]] for r in range(r_n)],
    }
