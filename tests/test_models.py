"""Per-architecture smoke tests (reduced configs, one forward/train step
on CPU, shape + finiteness assertions) and attention semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_smoke_config, get_config, shapes_for
from repro.models import model as M
from repro.models import layers as L
from repro.parallel import context as pctx
from repro.train.step import make_train_step, cross_entropy
from repro.parallel.sharding import ParallelPlan, train_rules
from repro.optim import AdamWConfig, ScheduleConfig, init_opt_state

RNG = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16):
    b = {"tokens": jax.random.randint(RNG, (B, S), 0, cfg.vocab_size),
         "targets": jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        b["frames"] = jax.random.normal(
            RNG, (B, cfg.encoder_seq, cfg.frontend_dim), jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward(arch):
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, RNG)
    batch = _batch(cfg)
    with pctx.single_device_context():
        logits, aux = jax.jit(
            lambda p, b: M.forward_train(cfg, p, b))(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step(arch):
    """One full train step (fwd + bwd + AdamW) on the reduced config."""
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, RNG)
    plan = ParallelPlan(rules=train_rules(False, ("data",)), remat="full")
    opt_cfg = AdamWConfig(lr=1e-3)
    opt_state = init_opt_state(params, opt_cfg)
    # warmup_steps=1 so the very first step has a non-zero lr
    fn = make_train_step(cfg, plan, opt_cfg,
                         ScheduleConfig(warmup_steps=1), mesh=None)
    batch = _batch(cfg)
    with pctx.single_device_context():
        p2, o2, metrics = jax.jit(fn)(params, opt_state, batch,
                                      jnp.int32(1), jnp.float32(1.0))
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually moved
    moved = jax.tree.reduce(
        lambda a, x: a + float(jnp.sum(jnp.abs(x.astype(jnp.float32)))),
        jax.tree.map(lambda a, b: a.astype(jnp.float32) -
                     b.astype(jnp.float32), p2, params), 0.0)
    assert moved > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode_consistency(arch):
    """Greedy decode after prefill must match teacher-forced forward
    logits (same positions, same cache semantics)."""
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, RNG)
    B, S = 2, 12
    batch = _batch(cfg, B, S)
    toks = batch["tokens"]
    with pctx.single_device_context():
        logits, _ = M.forward_train(cfg, params, batch)
        cache = M.init_cache(cfg, B, 32)
        last, cache = M.prefill(cfg, params, toks, cache,
                                frames=batch.get("frames"))
        np.testing.assert_allclose(
            np.asarray(last, np.float32),
            np.asarray(logits[:, -1], np.float32), rtol=2e-2, atol=2e-2)
        # one decode step at position S using token S-1's argmax
        nxt = jnp.argmax(last, -1)[:, None].astype(jnp.int32)
        pos = jnp.full((B,), S, jnp.int32)
        lg, _ = M.decode_step(cfg, params, cache, nxt, pos)
        assert lg.shape == (B, cfg.vocab_size)
        assert np.all(np.isfinite(np.asarray(lg, np.float32)))


def test_decode_matches_prefill_stepwise():
    """Decoding token-by-token reproduces prefill logits (dense arch)."""
    cfg = get_smoke_config("phi4-mini-3.8b")
    params = M.init_params(cfg, RNG)
    B, S = 1, 8
    toks = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
    with pctx.single_device_context():
        batch = {"tokens": toks, "targets": toks}
        full_logits, _ = M.forward_train(cfg, params, batch)
        cache = M.init_cache(cfg, B, 16)
        # feed tokens one at a time through decode_step
        outs = []
        for t in range(S):
            lg, cache = M.decode_step(cfg, params, cache, toks[:, t:t+1],
                                      jnp.full((B,), t, jnp.int32))
            outs.append(lg)
        stepwise = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(stepwise, np.float32),
                               np.asarray(full_logits, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_window_attention_masks_past():
    """With a window of w, logits must not depend on tokens further back
    than w."""
    cfg = get_smoke_config("recurrentgemma-9b")
    w = cfg.attn_window
    params = M.init_params(cfg, RNG)
    B, S = 1, 40  # > window (32)
    t1 = jax.random.randint(RNG, (B, S), 0, cfg.vocab_size)
    # change a token far outside every attention window of the last pos,
    # but note rglru layers carry state, so compare attention-only layers:
    # use pure attention_forward instead.
    x = jax.random.normal(RNG, (B, S, cfg.d_model), jnp.float32)
    p = {k: v for k, v in M.init_params(cfg, RNG)
         ["rem0_rglru"]["mlp"].items()}  # unused; build attn params below
    from repro.models.layers import attention_template, attention_forward
    from repro.models.params import init_concrete
    ap = init_concrete(attention_template(cfg), "float32", RNG)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    out1, _ = attention_forward(cfg, ap, x, pos, window=w)
    x2 = x.at[:, 0].set(x[:, 0] + 100.0)  # outside window of last pos
    out2, _ = attention_forward(cfg, ap, x2, pos, window=w)
    np.testing.assert_allclose(np.asarray(out1[:, -1]),
                               np.asarray(out2[:, -1]), atol=1e-4)
    assert not np.allclose(np.asarray(out1[:, 1]), np.asarray(out2[:, 1]))


def test_chunked_equals_dense_attention():
    from repro.models.layers import chunked_attention, dense_attention
    B, Sq, Hkv, G, hd = 2, 64, 2, 3, 16
    q = jax.random.normal(RNG, (B, Sq, Hkv, G, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(RNG, 1), (B, Sq, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(RNG, 2), (B, Sq, Hkv, hd))
    pos = jnp.broadcast_to(jnp.arange(Sq)[None], (B, Sq)).astype(jnp.int32)
    a = chunked_attention(q, k, v, pos, pos, causal=True, chunk=16)
    b = dense_attention(q, k, v, pos, pos, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_cross_entropy_matches_naive():
    B, S, V = 2, 8, 32
    logits = jax.random.normal(RNG, (B, S, V), jnp.float32)
    targets = jax.random.randint(RNG, (B, S), 0, V)
    ce = cross_entropy(logits, targets)
    naive = -jnp.mean(jnp.take_along_axis(
        jax.nn.log_softmax(logits, -1), targets[..., None], -1))
    np.testing.assert_allclose(float(ce), float(naive), rtol=1e-6)


def test_param_counts_match_published():
    expect = {
        "chameleon-34b": 34.3e9, "kimi-k2-1t-a32b": 1043e9,
        "llama4-scout-17b-a16e": 108e9, "starcoder2-3b": 3.0e9,
        "qwen2.5-32b": 32.8e9, "qwen1.5-110b": 111e9,
        "phi4-mini-3.8b": 3.8e9, "mamba2-780m": 0.78e9,
        "recurrentgemma-9b": 8.5e9, "whisper-base": 0.071e9,
    }
    for arch, n in expect.items():
        got = M.param_count(get_config(arch))
        assert abs(got - n) / n < 0.05, (arch, got, n)


def test_long_500k_applicability():
    subq = {a for a in ARCH_IDS
            if any(s.name == "long_500k" for s in shapes_for(get_config(a)))}
    assert subq == {"mamba2-780m", "recurrentgemma-9b"}


def test_ssd_grads_finite_over_full_chunk():
    """A 256-token SSD chunk at the published mamba2 widths: the decay
    above the diagonal overflows exp, and a mask applied after the exp
    turns the backward into 0 * inf = nan. Grads must stay finite."""
    from repro.train.step import make_loss_fn
    cfg = get_config("mamba2-780m").replace(n_layers=1, vocab_size=256)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 257), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    loss_fn = make_loss_fn(cfg, M.CallOptions())
    (loss, _), g = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, batch)
    assert np.isfinite(float(loss))
    for path, v in jax.tree_util.tree_flatten_with_path(g)[0]:
        assert bool(jnp.all(jnp.isfinite(v))), jax.tree_util.keystr(path)
