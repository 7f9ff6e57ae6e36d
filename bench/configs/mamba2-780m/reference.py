"""Weights and batches from the seed, and the plain float32 reference,
for the Mamba-2 language model of ``config.json`` (mamba2-780m).

The reference is written from the architecture (arXiv:2405.21060), not
from the program: each block is RMSNorm, projections to z, x, B, C and
dt, a depthwise causal convolution with SiLU on x, B and C, the
selective state-space recurrence, a skip through D, a SiLU gate by z, a
gated RMSNorm and the output projection. The recurrence is evaluated in
its quadratic ("attention") form over the whole sequence,
``y_t = sum_{s<=t} (C_t . B_s) exp(sum_{s<r<=t} dt_r A) dt_s x_s``,
which is exact for a zero initial state and shares nothing with the
chunked scan the program runs. Loss is the mean next-token cross
entropy; the optimizer is AdamW as ``config.json`` states it. All
arithmetic is float32 under ``jax.default_matmul_precision("highest")``;
parameters are stored in the dtype the configuration states, as the
program stores them.

Departures from the paper's block that the program makes, and that the
reference therefore follows (stated in ``config.json``): norm weights
are offsets from 1, and the gated RMSNorm normalises each head on its
own.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def dims(cfg: Dict[str, Any]):
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    hd = cfg["headdim"]
    return d, d_in // hd, hd, cfg["d_state"], cfg["d_conv"], \
        cfg["vocab_size"], cfg["n_layer"]


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """name -> (shape, dtype, init) in the layout the program's trainer
    holds."""
    d, nh, hd, n, cw, v, L = dims(cfg)
    w = cfg["torch_dtype"]
    return {
        "tok_emb": ((v, d), w, ("normal", d)),
        "final_norm": {"scale": ((d,), "float32", ("offset", 0))},
        "layers": {
            "norm": {"scale": ((L, d), "float32", ("offset", 0))},
            "w_z": ((L, d, nh, hd), w, ("normal", d)),
            "w_x": ((L, d, nh, hd), w, ("normal", d)),
            "w_B": ((L, d, n), w, ("normal", d)),
            "w_C": ((L, d, n), w, ("normal", d)),
            "w_dt": ((L, d, nh), w, ("normal", d)),
            "conv_x": ((L, cw, nh, hd), w, ("normal", cw)),
            "conv_B": ((L, cw, n), w, ("normal", cw)),
            "conv_C": ((L, cw, n), w, ("normal", cw)),
            "conv_bx": ((L, nh, hd), w, ("offset", 0)),
            "conv_bB": ((L, n), w, ("offset", 0)),
            "conv_bC": ((L, n), w, ("offset", 0)),
            "A_log": ((L, nh), "float32", ("a_log", 0)),
            "D": ((L, nh), "float32", ("d_skip", 0)),
            "dt_bias": ((L, nh), "float32", ("dt_bias", 0)),
            "out_norm": ((L, nh, hd), "float32", ("offset", 0)),
            "w_out": ((L, nh, hd, d), w, ("normal", nh * hd)),
        },
    }


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def init_params(cfg: Dict[str, Any], seed: int, shardings=None):
    """All weights from ``seed`` in one jitted call on the device:
    matrices N(0, 1/fan_in); norm offsets and conv biases N(0, 0.1^2);
    A = -exp(A_log) with exp(A_log) uniform in [1, 16]; dt_bias the
    inverse softplus of dt log-uniform in [1e-3, 1e-1]; D near 1."""
    shapes = param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten(shapes, is_leaf=_is_leaf)

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (shape, dt, (kind, fan)) in zip(keys, flat):
            if kind == "normal":
                x = jax.random.normal(k, shape, F32) / np.sqrt(fan)
            elif kind == "offset":
                x = 0.1 * jax.random.normal(k, shape, F32)
            elif kind == "a_log":
                x = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
            elif kind == "d_skip":
                x = 1.0 + 0.1 * jax.random.normal(k, shape, F32)
            else:  # dt_bias
                u = jnp.exp(jax.random.uniform(k, shape, F32, np.log(1e-3),
                                               np.log(1e-1)))
                x = u + jnp.log(-jnp.expm1(-u))
            out.append(x.astype(jnp.dtype(dt)))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make, out_shardings=shardings)(jax.random.PRNGKey(seed))


def batch(cfg: Dict[str, Any], seed: int, cursor: int, rows: int, seq: int):
    """Batch ``cursor`` of the job: ``rows`` sequences of ``seq + 1``
    tokens drawn uniformly from the vocabulary; every row differs."""
    g = np.random.Generator(np.random.Philox(key=seed, counter=[0, 0, cursor, 0]))
    toks = g.integers(0, cfg["vocab_size"], size=(rows, seq + 1), dtype=np.int64)
    toks = toks.astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


# ---------------------------------------------------------------------------
# the reference model
# ---------------------------------------------------------------------------

def quantize_fp8(w):
    """Control precision: a weight to float8_e4m3 with one scale per
    last-axis column, then back to float32 (fake quantization). The
    gradient passes straight through to the weight, as in training with
    float8 weights."""
    w = w.astype(F32)
    axes = tuple(range(w.ndim - 1))
    amax = jnp.max(jnp.abs(w), axis=axes, keepdims=True) if axes else \
        jnp.max(jnp.abs(w))
    scale = jax.lax.stop_gradient(jnp.maximum(amax, 1e-12) / 448.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return w + jax.lax.stop_gradient(q - w)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def _conv(x, w, b):
    """Depthwise causal conv along time: x [B, T, ...], w [cw, ...];
    w[-1] weighs the current input."""
    cw = w.shape[0]
    T = x.shape[1]
    xp = jnp.pad(x, [(0, 0), (cw - 1, 0)] + [(0, 0)] * (x.ndim - 2))
    y = sum(xp[:, i:i + T] * w[i] for i in range(cw))
    return jax.nn.silu(y + b)


def block(cfg, p, x, quant: bool = False):
    """One Mamba-2 block with its residual: x [B, T, D] float32."""
    eps = cfg["rms_norm_eps"]
    q = quantize_fp8 if quant else (lambda w: w.astype(F32))
    h = _rms(x, p["norm"]["scale"], eps)
    z = jnp.einsum("btd,dhp->bthp", h, q(p["w_z"]))
    xs = jnp.einsum("btd,dhp->bthp", h, q(p["w_x"]))
    Bm = jnp.einsum("btd,dn->btn", h, q(p["w_B"]))
    Cm = jnp.einsum("btd,dn->btn", h, q(p["w_C"]))
    dt = jnp.einsum("btd,dh->bth", h, q(p["w_dt"]))
    xs = _conv(xs, p["conv_x"].astype(F32), p["conv_bx"].astype(F32))
    Bm = _conv(Bm, p["conv_B"].astype(F32), p["conv_bB"].astype(F32))
    Cm = _conv(Cm, p["conv_C"].astype(F32), p["conv_bC"].astype(F32))
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # [B, T, H]
    A = -jnp.exp(p["A_log"])                                # [H]
    cum = jnp.cumsum(dt * A, axis=1)                        # [B, T, H]
    T = x.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    diff = cum[:, :, None, :] - cum[:, None, :, :]          # [B, t, s, H]
    decay = jnp.exp(jnp.where(causal[None, :, :, None], diff, -jnp.inf))
    mix = jnp.einsum("btn,bsn->bts", Cm, Bm)[..., None] * decay
    y = jnp.einsum("btsh,bshp->bthp", mix, dt[..., None] * xs)
    y = y + xs * p["D"][:, None]
    y = y * jax.nn.silu(z)
    y = _rms(y, p["out_norm"], eps)
    return x + jnp.einsum("bthp,hpd->btd", y, q(p["w_out"]))


def loss_fn(cfg, params, tokens, targets, quant: bool = False):
    """Mean next-token cross entropy over every position of the batch."""
    q = quantize_fp8 if quant else (lambda w: w.astype(F32))
    emb = q(params["tok_emb"])
    x = jnp.take(emb, tokens, axis=0)

    def body(x, p):
        return block(cfg, p, x, quant), None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])

    def row(args):
        xr, tr = args                                       # [T, D], [T]
        lg = xr @ emb.T
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, tr[:, None], 1)[:, 0])

    per_row = jax.lax.map(jax.checkpoint(row), (x, targets))
    return jnp.sum(per_row) / targets.size


# ---------------------------------------------------------------------------
# AdamW as the configuration states it
# ---------------------------------------------------------------------------

def lr_at(opt: Dict[str, Any], step: int) -> float:
    """Warm-up then cosine decay to ``min_ratio`` of the peak."""
    s = float(step)
    warm = min(s / max(opt["warmup_steps"], 1), 1.0)
    frac = min(max((s - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    cos = 0.5 * (1.0 + np.cos(np.pi * frac))
    return opt["lr"] * warm * (opt["min_ratio"] + (1 - opt["min_ratio"]) * cos)


def _norms(tree) -> List[jax.Array]:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for x in jax.tree.leaves(tree)]


def make_steps(cfg: Dict[str, Any], quant: bool = False,
               half_batch: bool = False):
    """(grad_fn, update_fn), jitted. ``half_batch`` plants a fault: the
    loss is the mean over the first half of the rows only."""
    opt = cfg["optimizer"]

    def grad_fn(params, tokens, targets):
        if half_batch:
            tokens, targets = tokens[: tokens.shape[0] // 2], \
                targets[: targets.shape[0] // 2]
        with jax.default_matmul_precision("highest"):
            f = lambda p: loss_fn(cfg, p, tokens, targets, quant)
            loss, g = jax.value_and_grad(f)(params)
        g = jax.tree.map(lambda x: x.astype(F32), g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        g = jax.tree.map(lambda x: x * clip, g)
        return loss, g, _norms(g)

    def update_fn(params, g, m, v, count, lr):
        count = count + 1
        cf = count.astype(F32)
        bc1 = 1.0 - opt["b1"] ** cf
        bc2 = 1.0 - opt["b2"] ** cf

        def one(p, gg, mm, vv):
            mm = opt["b1"] * mm + (1 - opt["b1"]) * gg
            vv = opt["b2"] * vv + (1 - opt["b2"]) * gg * gg
            step = (mm / bc1) / (jnp.sqrt(vv / bc2) + opt["eps"])
            decay = opt["weight_decay"] if p.ndim >= opt["weight_decay_min_rank"] \
                else 0.0
            pf = p.astype(F32)
            return (pf - lr * (step + decay * pf)).astype(p.dtype), mm, vv

        out = jax.tree.map(one, params, g, m, v)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2), count

    return (jax.jit(grad_fn),
            jax.jit(update_fn, donate_argnums=(0, 2, 3)))


def change_norms_fn():
    """Per-leaf norm of (a - b), in float32."""
    return jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32) - y.astype(F32)))) for x, y in
        zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def leaf_norms_fn():
    return jax.jit(_norms)


SAMPLE = 1 << 17


def sample_strides(tree) -> List[int]:
    """Per leaf, the stride of the fixed sample ``sample_fn`` keeps: at
    most ``SAMPLE`` evenly spaced entries of the flattened leaf."""
    return [max(1, -(-int(np.prod(x.shape)) // SAMPLE))
            for x in jax.tree.leaves(tree)]


def sample_fn():
    """Jitted: every leaf flattened, every stride-th entry, in float32."""
    def f(tree):
        return [x.reshape(-1)[::st].astype(F32)
                for x, st in zip(jax.tree.leaves(tree), sample_strides(tree))]
    return jax.jit(f)


def train_reference(cfg: Dict[str, Any], seed: int, batches, start_step: int,
                    steps: int = 3, quant: bool = False,
                    half_batch: bool = False, shardings=None) -> Dict[str, Any]:
    """Run the reference from the weights of ``seed`` for ``steps``
    steps on ``batches``. Returns the loss of each step, each leaf's norm
    of the first (clipped) gradient, and each leaf's norm of the change
    of the parameters over the steps, in the order of the tree's
    leaves."""
    grad_fn, update_fn = make_steps(cfg, quant, half_batch)
    params = init_params(cfg, seed, shardings)
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    count = jnp.zeros((), jnp.int32)
    losses, first_gnorms, first_sample = [], None, None
    for i in range(steps):
        b = batches[i]
        loss, g, gn = grad_fn(params, jnp.asarray(b["tokens"]),
                              jnp.asarray(b["targets"]))
        losses.append(float(loss))
        if first_gnorms is None:
            first_gnorms = [float(x) for x in gn]
            first_sample = [np.asarray(x) for x in sample_fn()(g)]
        params, m, v, count = update_fn(params, g, m, v, count,
                                        jnp.float32(lr_at(cfg["optimizer"],
                                                          start_step + i)))
        del g
    del m, v
    p0 = init_params(cfg, seed, shardings)
    change = [float(x) for x in change_norms_fn()(params, p0)]
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(p0)[0]]
    return {"losses": losses, "grad_norms": first_gnorms,
            "grad_sample": first_sample, "strides": sample_strides(p0),
            "change_norms": change, "leaves": names}


def leaf_gaps(a, b, keep) -> np.ndarray:
    """Per leaf: |a - b| over the larger of b and the median of b over
    the leaves kept; 0 for leaves left out."""
    b = np.asarray(b, np.float64)
    a = np.asarray(a, np.float64)
    floor = np.median(b[keep])
    return np.where(keep, np.abs(a - b) / np.maximum(b, floor), 0.0)


def compare(ref: Dict[str, Any], prog: Dict[str, Any],
            exclude_below: float = 1e-3) -> Dict[str, Any]:
    """The numbers a training cell compares, program against reference:

    * ``loss_gap``: largest relative gap of a step's loss;
    * ``grad_err``: relative error of the first gradient as a vector,
      ``|g - g_ref| / |g_ref|`` over every leaf, each leaf represented by
      its fixed sample weighted by its stride;
    * ``grad_gap`` (logged, not compared): worst leaf's gap between the
      norms of the first gradient, over the larger of that leaf's
      reference norm and the median leaf's;
    * ``change_gap``: the same for the parameters' change over the
      steps, leaving out leaves whose reference gradient is under
      ``exclude_below`` of the median leaf's (they move by round-off).
    """
    def worst(a, b, keep):
        rel = leaf_gaps(a, b, keep)
        i = int(np.argmax(rel))
        return float(rel[i]), ref["leaves"][i]

    gref = np.asarray(ref["grad_norms"], np.float64)
    keep_all = np.ones(len(gref), bool)
    keep = gref >= exclude_below * np.median(gref)
    lr = np.asarray(ref["losses"], np.float64)
    lp = np.asarray(prog["losses"][:len(lr)], np.float64)
    g, gleaf = worst(prog["grad_norms"], ref["grad_norms"], keep_all)
    c, cleaf = worst(prog["change_norms"], ref["change_norms"], keep)
    num = den = 0.0
    leaf_err = []
    for a, b, st in zip(prog["grad_sample"], ref["grad_sample"],
                        ref["strides"]):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        d2, r2 = float(np.sum((a - b) ** 2)), float(np.sum(b * b))
        num += st * d2
        den += st * r2
        leaf_err.append(np.sqrt(d2 / r2) if r2 > 0 else 0.0)
    i = int(np.argmax(leaf_err))
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_err": float(np.sqrt(num / den)),
            "grad_err_worst_leaf": [ref["leaves"][i], float(leaf_err[i])],
            "grad_gap": g, "grad_gap_leaf": gleaf,
            "change_gap": c, "change_gap_leaf": cleaf,
            "excluded": [ref["leaves"][i] for i in np.nonzero(~keep)[0]]}
