"""Multi-device tests (subprocess with virtual host devices): sharded
training, elastic restore across topologies, MoE expert parallelism,
dry-run machinery. See conftest.run_with_devices.

Marked slow (each case spawns a fresh jax process): excluded from the
default tier-1 run; opt in with  pytest -m slow  or  pytest -m ""."""
import pytest

pytestmark = pytest.mark.slow


def test_sharded_train_matches_single_device(subproc):
    """Same job on a (2,2) mesh and a (1,1) mesh: identical losses —
    the logical/physical split the C/R design relies on."""
    out = subproc("""
    import jax, numpy as np
    from repro.train.loop import Trainer, TrainJob
    job = TrainJob(arch="phi4-mini-3.8b-smoke", shape_key="train_s16_b4")
    losses = {}
    for shape in [(1,1),(2,2),(4,2)]:
        t = Trainer(job, shape, ("data","model"))
        t.init_state()
        m = [t.train_steps(1)["loss"] for _ in range(3)]
        losses[shape] = m
    base = losses[(1,1)]
    for shape, m in losses.items():
        np.testing.assert_allclose(m, base, rtol=2e-2, atol=2e-3), shape
    print("OK", losses)
    """, n_devices=8)
    assert "OK" in out


def test_elastic_restore_different_mesh(subproc):
    """Checkpoint on a (2,4) mesh, restore on (4,2) and (1,1): logical
    shardings rebind; continuation losses match across topologies."""
    out = subproc("""
    import tempfile, numpy as np
    from repro.core import CheckpointManager, LocalFSBackend
    from repro.launch.mesh import make_mesh
    from repro.train.loop import Trainer, TrainJob
    job = TrainJob(arch="qwen2.5-32b-smoke", shape_key="train_s16_b4")
    root = tempfile.mkdtemp()
    mgr = CheckpointManager(LocalFSBackend(root), async_save=False)
    t = Trainer(job, (2,4), ("data","model"), manager=mgr)
    t.init_state()
    t.train_steps(2)
    t.save(block=True)
    d0 = t.params_digest()
    del t
    results = {}
    for shape in [(4,2),(2,2),(1,1)]:
        t2 = Trainer.restore(mgr, mesh_factory=lambda s=shape: make_mesh(s, ("data","model")))
        assert int(t2.upper.get("step")) == 2
        assert t2.params_digest() == d0, (shape, "restore must be exact")
        results[shape] = t2.train_steps(1)["loss"]
    vals = list(results.values())
    np.testing.assert_allclose(vals, vals[0], rtol=2e-2, atol=2e-3)
    print("ELASTIC OK", results)
    """, n_devices=8)
    assert "ELASTIC OK" in out


def test_moe_expert_parallel_matches_local(subproc):
    """MoE with experts sharded over the model axis == single-shard MoE."""
    out = subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.launch.mesh import make_mesh
    from repro.parallel import context as pctx
    # capacity high enough that no token drops: capacity-factor MoE
    # output is otherwise legitimately sharding-dependent (which tokens
    # overflow depends on per-shard ranking — GShard semantics)
    cfg = get_smoke_config("kimi-k2-1t-a32b").replace(capacity_factor=16.0)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 16
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B,S), 0, cfg.vocab_size)}
    mesh1 = make_mesh((1,1), ("data","model"), devices=jax.devices()[:1])
    with pctx.mesh_context(mesh1):
        ref, _ = jax.jit(lambda p,b: M.forward_train(cfg,p,b))(params, batch)
    mesh = make_mesh((2,4), ("data","model"))
    with pctx.mesh_context(mesh):
        out, _ = jax.jit(lambda p,b: M.forward_train(cfg,p,b))(params, batch)
    np.testing.assert_allclose(np.asarray(ref,np.float32), np.asarray(out,np.float32), rtol=5e-2, atol=5e-2)
    print("MOE EP OK")
    """, n_devices=8)
    assert "MOE EP OK" in out


def test_dryrun_machinery_small_mesh(subproc):
    """The dry-run path (abstract lower + compile + analysis) works on a
    small mesh for train, prefill and decode kinds."""
    out = subproc("""
    import jax, jax.numpy as jnp
    from repro.configs import registry as R
    from repro.models import model as M
    from repro.optim import abstract_opt_state
    from repro.train import step as step_lib
    from repro.serving import engine as engine_lib
    from repro.launch.hlo_analysis import analyze_hlo, roofline_terms
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2,4), ("data","model"))
    cfg = R.get_smoke_config("qwen1.5-110b")
    for shape_key in ["train_s64_b8", "prefill_s64_b8", "decode_s64_b8"]:
        shape = R.get_shape(shape_key)
        ab = M.init_abstract(cfg)
        if shape.kind == "train":
            fn, info = step_lib.jit_train_step(cfg, shape, mesh)
            abo = abstract_opt_state(ab, info["opt_cfg"])
            lowered = fn.lower(ab, abo, info["input_specs"],
                               jax.ShapeDtypeStruct((), jnp.int32),
                               jax.ShapeDtypeStruct((), jnp.float32))
        elif shape.kind == "prefill":
            fn, _ = engine_lib.jit_prefill(cfg, shape, mesh)
            sp = engine_lib.serve_input_specs(cfg, shape)
            lowered = fn.lower(ab, sp["tokens"], sp["cache"])
        else:
            fn, _ = engine_lib.jit_decode_step(cfg, shape, mesh)
            sp = engine_lib.serve_input_specs(cfg, shape)
            lowered = fn.lower(ab, sp["cache"], sp["tokens"], sp["pos"])
        compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes >= 0
        counts = analyze_hlo(compiled.as_text())
        terms = roofline_terms(counts)
        assert counts.flops > 0, shape_key
        print("CELL OK", shape_key, terms["dominant"])
    print("DRYRUN OK")
    """, n_devices=8, timeout=900)
    assert "DRYRUN OK" in out


def test_grad_compression_shard_map(subproc):
    """int8+EF gradient psum inside shard_map: mean of per-shard grads
    within quantization error of the exact mean."""
    out = subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import compressed_psum, init_error_feedback
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 1024), jnp.float32)
    ef = jnp.zeros((8, 1024), jnp.float32)
    def f(gl, el):
        red, e2 = compressed_psum({"w": gl[0]}, {"w": el[0]}, "data")
        return red["w"][None], e2["w"][None]
    red, e2 = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data")), check_vma=False))(g, ef)
    exact = jnp.mean(g, axis=0)
    got = np.asarray(red[0])
    err = np.abs(got - np.asarray(exact)).max()
    scale = np.abs(g).max() / 127
    assert err < 2*scale, (err, scale)
    print("COMPRESS OK", err)
    """, n_devices=8)
    assert "COMPRESS OK" in out


def test_pipeline_parallel_matches_scan(subproc):
    """GPipe over a stage axis == plain scan over layers (toy blocks)."""
    out = subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import pipeline_forward, bubble_fraction
    mesh = make_mesh((4, 2), ("pod", "data"))
    L, M, mb, D = 8, 6, 4, 16
    rng = jax.random.PRNGKey(0)
    W = jax.random.normal(rng, (L, D, D), jnp.float32) * 0.2
    X = jax.random.normal(jax.random.fold_in(rng, 1), (M, mb, D))
    def block(w, x):
        return jnp.tanh(x @ w)
    ref = X
    for l in range(L):
        ref = block(W[l], ref)
    out = jax.jit(lambda w, x: pipeline_forward(
        block, w, x, mesh, stage_axis="pod"))(W, X)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert 0 < bubble_fraction(4, 6) < 0.5
    print("PP OK")
    """, n_devices=8)
    assert "PP OK" in out
