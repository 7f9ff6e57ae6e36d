"""Each plain reference against the program at a small size on the CPU,
both in float32, on weights the benchmark makes: the same architecture
must give the same numbers."""
import jax
import jax.numpy as jnp
import numpy as np

from conftest import smoke_cell


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def test_phi4_logits_match_the_program():
    from repro.configs import registry
    from repro.models import model as M

    cell = smoke_cell("serve-phi4-snap")
    cfg, ref = cell.config, cell.reference()
    pcfg = registry.resolve_config(cfg["arch"]).replace(dtype="float32")
    params = _f32(ref.init_params(cfg, 3))
    toks = np.random.RandomState(0).randint(0, cfg["vocab_size"], 40)
    logits, _ = M.forward_train(pcfg, params,
                                {"tokens": jnp.asarray(toks[None])})
    hs = ref.hidden_states(cfg, params, jnp.asarray(toks))
    want = hs @ params["tok_emb"].T
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_phi4_served_gaps_are_zero_for_the_references_own_tokens():
    cell = smoke_cell("serve-phi4-snap")
    cfg, ref = cell.config, cell.reference()
    params = ref.init_params(cfg, 4)
    toks = list(np.random.RandomState(1).randint(0, cfg["vocab_size"], 10))
    for _ in range(6):          # greedy continuation by the reference
        hs = ref.hidden_states(cfg, params, jnp.asarray(toks, jnp.int32))
        lg = hs[-1] @ params["tok_emb"].astype(jnp.float32).T
        toks.append(int(jnp.argmax(lg)))
    got = ref.served_gaps(cfg, params, [(toks, 10)], pad_to=32)
    assert got[0]["tokens"] == 6
    assert got[0]["gap"] < 1e-5
    bad = list(toks)
    bad[12] = (bad[12] + 1) % cfg["vocab_size"]
    assert ref.served_gaps(cfg, params, [(bad, 10)], pad_to=32)[0]["gap"] > 0


def test_mamba2_loss_and_adamw_update_match_the_program():
    from repro.configs import registry
    from repro.models import model as M
    from repro.optim import AdamWConfig, apply_updates, init_opt_state
    from repro.train.step import make_loss_fn

    cell = smoke_cell("train-mamba2-snap")
    cfg, ref = cell.config, cell.reference()
    pcfg = registry.resolve_config(cfg["arch"]).replace(dtype="float32")
    params = _f32(ref.init_params(cfg, 5))
    b = ref.batch(cfg, 6, 0, 2, 64)
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    loss_p, g_p = jax.value_and_grad(
        lambda p: make_loss_fn(pcfg, M.CallOptions())(p, batch)[0])(params)
    opt = cfg["optimizer"]
    ocfg = AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"],
                       eps=opt["eps"], weight_decay=opt["weight_decay"],
                       grad_clip=opt["grad_clip"])
    lr = jnp.float32(ref.lr_at(opt, 489))
    new_p, _, _ = apply_updates(params, g_p, init_opt_state(params, ocfg),
                                ocfg, lr)

    grad_fn, update_fn = ref.make_steps(cfg)
    loss_r, g_r, _ = grad_fn(params, batch["tokens"], batch["targets"])
    np.testing.assert_allclose(float(loss_r), float(loss_p), rtol=1e-5)
    zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    new_r, _, _, _ = update_fn(params, g_r, zeros,
                               jax.tree.map(jnp.zeros_like, zeros),
                               jnp.zeros((), jnp.int32), lr)
    for a, b_ in zip(jax.tree.leaves(new_p), jax.tree.leaves(new_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-6)


def test_lr_schedule_matches_the_program():
    from repro.optim import ScheduleConfig, schedule_lr

    cell = smoke_cell("train-mamba2-snap")
    opt = cell.config["optimizer"]
    sc = ScheduleConfig(peak_lr=opt["lr"], warmup_steps=opt["warmup_steps"],
                        total_steps=opt["total_steps"],
                        min_ratio=opt["min_ratio"])
    for s in (0, 50, 489, 5000, 12000):
        assert abs(cell.reference().lr_at(opt, s)
                   - float(schedule_lr(sc, s))) < 1e-9


def test_phi4_reference_follows_the_source_once_a_departure_is_dropped():
    cell = smoke_cell("serve-phi4-snap")
    cfg, ref = cell.config, cell.reference()
    assert ref.runs(cfg, "partial_rotary_factor") == 1.0
    source = dict(cfg, departures={
        k: v for k, v in cfg["departures"].items()
        if k != "partial_rotary_factor"})
    assert ref.runs(source, "partial_rotary_factor") == 0.75
    params = ref.init_params(cfg, 8)
    toks = jnp.asarray(np.random.RandomState(2).randint(0, 256, 12))
    a = ref.hidden_states(cfg, params, toks)
    b = ref.hidden_states(source, params, toks)
    assert np.allclose(a[0], b[0], atol=1e-5)       # position 0 is unrotated
    assert not np.allclose(a, b, atol=1e-3)
