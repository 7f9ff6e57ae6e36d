"""Weights from the seed, and the plain float32 reference, for the
dense decoder of ``config.json`` (phi4-mini-3.8b).

The reference is written from the architecture, not from the program:
pre-norm decoder blocks, RMSNorm, rotary positions on the first
``partial_rotary_factor`` of each head (its two halves rotated),
grouped-query causal attention, SwiGLU MLP, tied embedding as the output
head. Everything is float32 under ``jax.default_matmul_precision
("highest")``, one whole sequence at a time with no cache. It walks the
layers in a scan, casting one layer's weights to float32 at a time, so a
full-size model fits beside its bf16 weights.

Where the program departs from the source, ``config.json`` keeps the
source's value and lists the program's under ``departures``; the
reference runs what the program runs (``runs``), so that dropping a
departure from the file makes the reference follow the source again.
Norm weights are stored as offsets from 1 (``x * (1 + w)``).

``init_params`` gives the weights in the layout the program's serving
engine takes; both sides are handed the same arrays, which this file
makes.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def runs(cfg: Dict[str, Any], key: str):
    """The value the program runs for ``key``: the source's, unless
    ``config.json`` lists a departure for it."""
    dep = cfg.get("departures", {}).get(key)
    return cfg.get(key) if dep is None else dep["runs"]


def dims(cfg: Dict[str, Any]):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    return d, h, kv, hd, cfg["intermediate_size"], cfg["vocab_size"], \
        cfg["num_hidden_layers"]


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, h, kv, hd, f, v, L = dims(cfg)
    return {
        "tok_emb": (v, d),
        "final_norm": {"scale": (d,)},
        "layers": {
            "ln1": {"scale": (L, d)},
            "attn": {"wq": (L, d, h, hd), "wk": (L, d, kv, hd),
                     "wv": (L, d, kv, hd), "wo": (L, h, hd, d)},
            "ln2": {"scale": (L, d)},
            "ffn": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        },
    }


def _fan_in(path: str, shape) -> int:
    if path.endswith("wo"):
        return shape[1] * shape[2]
    if path.endswith("tok_emb"):
        return shape[1]
    return shape[-2] if path.endswith(("w_gate", "w_up", "w_down")) \
        else shape[1]


def init_params(cfg: Dict[str, Any], seed: int):
    """All weights from ``seed`` in one jitted call on the device, in the
    dtype they are served in: matrices N(0, 1/fan_in) in bf16, norm
    offsets N(0, 0.1^2) in float32."""
    shapes = param_shapes(cfg)
    wdt = jnp.dtype(cfg["torch_dtype"])
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, shape) in zip(keys, flat):
            p = jax.tree_util.keystr(path)
            if "scale" in p:
                out.append(0.1 * jax.random.normal(k, shape, jnp.float32))
            else:
                std = 1.0 / np.sqrt(_fan_in(p, shape))
                out.append((std * jax.random.normal(k, shape, jnp.float32))
                           .astype(wdt))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# the reference forward pass
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _rope(x, theta, fraction):
    """x [T, H, hd]: rotate the first ``fraction`` of each head, as
    (first half, second half) by position; the rest passes through."""
    T, _, hd = x.shape
    rot = int(hd * fraction)
    half = rot // 2
    inv = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * c - b * s, b * c + a * s, x[..., rot:]], -1)


def quantize_fp8(w):
    """Control precision: each output column of a weight to float8_e4m3
    with its own scale, then back to float32 (fake quantization)."""
    w = w.astype(jnp.float32)
    axes = tuple(range(w.ndim - 1)) if w.ndim > 1 else ()
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True) if axes else \
        jnp.max(jnp.abs(w))
    scale = jnp.maximum(amax, 1e-12) / 448.0
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def hidden_states(cfg: Dict[str, Any], params, tokens, quant: bool = False,
                  with_kv: bool = False):
    """Final-norm hidden states [T, D] of one sequence ``tokens`` [T];
    with ``with_kv`` also every layer's keys (after the rotation) and
    values, [L, T, kv_heads, head_dim] each."""
    d, h, kv, hd, f, v, L = dims(cfg)
    g = h // kv
    eps = runs(cfg, "rms_norm_eps")
    theta = cfg["rope_theta"]
    fraction = runs(cfg, "partial_rotary_factor")
    if runs(cfg, "rope_scaling") is not None:
        raise NotImplementedError("rope_scaling: the reference has none")
    q8 = quantize_fp8 if quant else (lambda w: w.astype(jnp.float32))
    T = tokens.shape[0]
    mask = jnp.tril(jnp.ones((T, T), bool))
    x = jnp.take(q8(params["tok_emb"]), tokens, axis=0)

    def layer(x, p):
        a = _rms(x, p["ln1"]["scale"], eps)
        q = jnp.einsum("td,dhk->thk", a, q8(p["attn"]["wq"]))
        k = jnp.einsum("td,dhk->thk", a, q8(p["attn"]["wk"]))
        vv = jnp.einsum("td,dhk->thk", a, q8(p["attn"]["wv"]))
        q, k = _rope(q, theta, fraction), _rope(k, theta, fraction)
        kv_out = (k, vv) if with_kv else None
        k = jnp.repeat(k, g, axis=1)
        vv = jnp.repeat(vv, g, axis=1)
        s = jnp.einsum("thk,shk->hts", q, k) / np.sqrt(hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        att = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hts,shk->thk", att, vv)
        x = x + jnp.einsum("thk,hkd->td", o, q8(p["attn"]["wo"]))
        b = _rms(x, p["ln2"]["scale"], eps)
        m = jax.nn.silu(b @ q8(p["ffn"]["w_gate"])) * (b @ q8(p["ffn"]["w_up"]))
        return x + m @ q8(p["ffn"]["w_down"]), kv_out

    x, kvs = jax.lax.scan(layer, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return (x, kvs) if with_kv else x


def _vocab_blocks(v: int) -> int:
    """Number of equal vocabulary blocks the logits are taken in."""
    n = 1
    while v % (n * 2) == 0 and v // n > 16384:
        n *= 2
    return n


def _gaps_fn(cfg: Dict[str, Any], control: bool):
    def fn(params, tokens, valid):
        """tokens [T]; valid [T] marks positions whose next token was
        served. Returns per position the reference's best logit minus its
        logit of the served token, or, with ``control``, of the token the
        reference in float8 weights puts first there. Logits are taken
        one block of the vocabulary at a time."""
        with jax.default_matmul_precision("highest"):
            T = tokens.shape[0]
            hs = hidden_states(cfg, params, tokens)
            hq = hidden_states(cfg, params, tokens, quant=True) \
                if control else None
            nxt = jnp.roll(tokens, -1)
            v, d = params["tok_emb"].shape
            nb = _vocab_blocks(v)
            emb = params["tok_emb"].reshape(nb, v // nb, d)
            neg = jnp.full((T,), -jnp.inf, jnp.float32)

            def block(carry, xs):
                best, picked, qbest = carry
                i, e = xs
                lg = hs @ e.astype(jnp.float32).T            # [T, v/nb]
                best = jnp.maximum(best, lg.max(-1))
                if control:
                    lq = hq @ quantize_fp8(e).T
                    j = jnp.argmax(lq, -1)
                    m = jnp.take_along_axis(lq, j[:, None], 1)[:, 0]
                    r = jnp.take_along_axis(lg, j[:, None], 1)[:, 0]
                    take = m > qbest
                    qbest = jnp.where(take, m, qbest)
                    picked = jnp.where(take, r, picked)
                else:
                    lo = i * (v // nb)
                    inb = (nxt >= lo) & (nxt < lo + v // nb)
                    got = jnp.take_along_axis(
                        lg, jnp.clip(nxt - lo, 0, v // nb - 1)[:, None],
                        1)[:, 0]
                    picked = jnp.where(inb, got, picked)
                return (best, picked, qbest), None

            (best, picked, _), _ = jax.lax.scan(
                block, (neg, neg, neg), (jnp.arange(nb), emb))
            return jnp.where(valid, best - picked, 0.0)

    return jax.jit(fn)


def served_gaps(cfg: Dict[str, Any], params, seqs, control: bool = False,
                pad_to: Optional[int] = None):
    """Largest logit gap of the served tokens of each sequence; with
    ``control``, of the tokens the reference in float8 weights would
    have served at the same positions (the control in the program's
    place).

    ``seqs``: list of (tokens, first) where ``tokens`` is the prompt, the
    given history and every served token, and served tokens are
    ``tokens[first:]``. All sequences are padded to ``pad_to`` so one
    compiled program serves them all; padding only follows the real
    tokens, which causal attention never lets them see.
    Returns a list of dicts {"gap": max gap, "tokens": served count}."""
    fn = _gaps_fn(cfg, control)
    T = pad_to or max(len(t) for t, _ in seqs)
    out = []
    for toks, first in seqs:
        toks = np.asarray(toks, np.int32)
        buf = np.zeros(T, np.int32)
        buf[:len(toks)] = toks
        valid = np.zeros(T, bool)
        valid[first - 1:len(toks) - 1] = True
        gaps = np.asarray(fn(params, jnp.asarray(buf), jnp.asarray(valid)))
        out.append({"gap": float(gaps.max()), "tokens": int(len(toks) - first)})
    return out


def _kv_fn(cfg: Dict[str, Any], control: bool):
    def rel(a, b, valid):
        m = valid[None, :, None, None]
        num = jnp.sum(jnp.where(m, (a - b) ** 2, 0.0), axis=(1, 2, 3))
        den = jnp.sum(jnp.where(m, b ** 2, 0.0), axis=(1, 2, 3))
        return jnp.sqrt(num / den)

    def fn(params, tokens, valid, k_prog, v_prog):
        """Per layer, the relative error over the valid positions of the
        program's cached keys and values against the reference's; with
        ``control``, of the float8 reference's in their place."""
        with jax.default_matmul_precision("highest"):
            _, (k, v) = hidden_states(cfg, params, tokens, with_kv=True)
            if control:
                _, (k_prog, v_prog) = hidden_states(
                    cfg, params, tokens, quant=True, with_kv=True)
            return [rel(k_prog.astype(jnp.float32), k, valid),
                    rel(v_prog.astype(jnp.float32), v, valid)]

    return jax.jit(fn)


def kv_errors(cfg: Dict[str, Any], params, slots, control: bool = False,
              pad_to: Optional[int] = None):
    """Worst relative error, over layers and over keys and values, of
    the KV cache the program holds for each slot; with ``control``, of
    the keys and values of the reference in float8 weights instead.

    ``slots``: list of (tokens, k, v), where ``tokens`` are the ``n``
    tokens whose keys and values the slot's cache holds at positions
    0..n-1, and ``k``, ``v`` are those cache rows, [L, n, kv_heads,
    head_dim]. Sequences are padded to ``pad_to`` so one compiled program
    serves them all. Returns a list of dicts {"kv_err": ..., "tokens": n}."""
    fn = _kv_fn(cfg, control)
    T = pad_to or max(len(t) for t, _, _ in slots)
    out = []
    for toks, k, v in slots:
        n = len(toks)
        buf = np.zeros(T, np.int32)
        buf[:n] = toks
        kp = np.zeros((k.shape[0], T) + k.shape[2:], k.dtype)
        vp = np.zeros_like(kp)
        kp[:, :n], vp[:, :n] = k, v
        valid = np.arange(T) < n
        res = [np.asarray(r) for r in fn(params, jnp.asarray(buf),
                                         jnp.asarray(valid), jnp.asarray(kp),
                                         jnp.asarray(vp))]
        out.append({"kv_err": float(max(res[0].max(), res[1].max())),
                    "tokens": n})
    return out
