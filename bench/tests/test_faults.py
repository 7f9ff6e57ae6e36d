"""A run with the timed path broken underneath comes out not correct.

Each cell is driven at a ``-smoke`` size on the CPU (the harness's look
for a chip is skipped), with one fault planted in the program for the
whole run: a step that returns its state unchanged, half of the batch
left out, or a token (a loss) altered where it is produced. One chip
has no exchange between chips to leave out."""
import jax.numpy as jnp
import pytest


def _train_fault(kind, real):
    def make(cfg, plan, opt_cfg, sched_cfg, mesh=None):
        fn = real(cfg, plan, opt_cfg, sched_cfg, mesh)

        def step(params, opt_state, batch, step, lr_scale):
            if kind == "half_batch":
                half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
                return fn(params, opt_state, half, step, lr_scale)
            p, o, m = fn(params, opt_state, batch, step, lr_scale)
            if kind == "unchanged":
                return params, opt_state, m
            return p, o, dict(m, loss=m["loss"] * 1.05)
        return step
    return make


def _serve_fault(kind, real):
    def decode(cfg, params, cache, tokens, pos, opts=None):
        logits, new = real(cfg, params, cache, tokens, pos, opts)
        if kind == "unchanged":
            return logits, cache
        if kind == "half_batch":
            b = logits.shape[0]
            keep = logits[: b - b // 2]
            return jnp.concatenate([keep, keep[: b // 2]], 0), new
        return logits.at[0, 0].set(1e9), new
    return decode


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "altered"])
def test_train_fault_is_caught(smoke_ctx, monkeypatch, kind):
    from repro.train import step as step_mod
    monkeypatch.setattr(step_mod, "make_train_step",
                        _train_fault(kind, step_mod.make_train_step))
    ctx = smoke_ctx("train-mamba2-snap", seed=99, seconds=1.0)
    res = ctx.cell.driver().run(ctx)
    assert not all(c.ok for c in res["checks"]), res["checks"]


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "altered"])
def test_serve_fault_is_caught(smoke_ctx, monkeypatch, kind):
    from repro.models import model as M
    monkeypatch.setattr(M, "decode_step", _serve_fault(kind, M.decode_step))
    ctx = smoke_ctx("serve-phi4-snap", seed=98, seconds=1.0)
    res = ctx.cell.driver().run(ctx)
    assert not all(c.ok for c in res["checks"]), res["checks"]
