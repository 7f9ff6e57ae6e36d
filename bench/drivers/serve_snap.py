"""Serving under periodic snapshots: a closed loop of requests through
``ServingEngine`` attached to a ``CheckpointSession``.

Set-up makes the weights from the seed, builds the engine through the
logged runtime, warms every shape the traffic will use (the prefill
buckets of its prompts, the capture kernels at the cache's leaf
shapes), and admits the first requests at staggered points of their
generation. The window runs engine steps for ``--seconds``: every slot
stays busy, a finished request is replaced at once from the seeded
stream, and ``maybe_snapshot()`` follows every step. After the window
every save is waited for, the engine is dropped, JAX's caches are
cleared, the newest save is restored and stepped once, and a sample of
the requests the window finished is compared with the reference.

The traffic file's keys (``bench/workloads/<cell>.json``, ``traffic``):
``slots``, ``max_seq``, ``prompt_len`` and ``output_len`` ([lo, hi]),
``length_alpha`` (the shape of the bounded Pareto law both lengths are
drawn from, so most are short and a few reach ``hi``), ``stagger_steps``
(the first requests finish evenly over this many steps), ``pool``
(requests generated). The seed draws every length, token id and weight.
"""
from __future__ import annotations

import gc
import shutil
from typing import Any, Dict, List

import numpy as np

from harness import Check, log, now, sub_seeds, tree_digest_fn


def _width(n: int, max_seq: int) -> int:
    """The engine's admission bucket for a history of ``n`` tokens."""
    return min(max(8, 1 << (int(n) - 1).bit_length()), max_seq)


def bounded_pareto(rng, lo: int, hi: int, alpha: float, n: int):
    """``n`` whole numbers in [lo, hi] from the Pareto law of shape
    ``alpha`` cut at ``hi`` (inverse of its distribution function)."""
    u = rng.random(n)
    x = lo / (1.0 - u * (1.0 - (lo / hi) ** alpha)) ** (1.0 / alpha)
    return np.clip(np.floor(x), lo, hi).astype(int)


def widths(traffic: Dict[str, Any]) -> List[int]:
    """Every admission bucket the traffic can reach: from the shortest
    prompt to the longest prompt with a whole output as history."""
    lo = _width(traffic["prompt_len"][0] - 1, traffic["max_seq"])
    hi = _width(traffic["prompt_len"][1] + traffic["output_len"][1] - 1,
                traffic["max_seq"])
    return [w for w in (1 << k for k in range(3, 32)) if lo <= w <= hi]


def requests(traffic: Dict[str, Any], vocab: int, seed: int) -> List[dict]:
    """The seeded stream: the first ``slots`` requests come with part of
    their output as history, so they finish evenly over
    ``stagger_steps``; the rest start from their prompt."""
    n = traffic["pool"]
    slots = traffic["slots"]
    rng = np.random.Generator(np.random.Philox(key=seed))
    a = traffic["length_alpha"]
    prompts = bounded_pareto(rng, *traffic["prompt_len"], a, n)
    outs = bounded_pareto(rng, *traffic["output_len"], a, n)
    out = []
    for i in range(n):
        given = 0
        if i < slots:
            left = int(round((i + 0.5) / slots * traffic["stagger_steps"]))
            given = max(int(outs[i]) - left, 0)
        out.append({
            "rid": i, "max_new": int(outs[i]),
            "prompt": rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            "given": rng.integers(0, vocab, given, dtype=np.int32)})
    return out


def run(ctx) -> Dict[str, Any]:
    """One run of the cell: serve, then what was served against the
    plain float32 reference."""
    res, got = serve(ctx)
    checks, readings = compare(ctx.cell, got)
    res["checks"] += checks
    res["run"].update(readings)
    return res


def serve(ctx):
    """Set-up, the window, the wait for its saves and the restore.
    Returns the result with the checks of saves and restore, and what
    ``compare`` reads: the weights, the served requests and the KV cache
    the window left."""
    import jax

    from repro.api import CheckpointSession, Policy
    from repro.kernels.ckpt_codec import ops
    from repro.serving.engine import Request, ServingEngine

    cell, rec = ctx.cell, ctx.record
    cfg, traffic = cell.config, cell.workload["traffic"]
    ref, flops = cell.reference(), cell.flops()
    s_weights, s_traffic, s_sample = sub_seeds(ctx.seed, 3)
    S, max_seq = traffic["slots"], traffic["max_seq"]

    # --- set-up --------------------------------------------------------
    marks = [("start", now())]
    params = jax.block_until_ready(ref.init_params(cfg, s_weights))
    marks.append(("weights", now()))
    spec = f"localfs:{ctx.store}"
    pol = cfg["policy"]
    sess = CheckpointSession(spec, Policy(chain=pol["chain"],
                                          interval=pol["interval"]))
    eng = sess.attach(ServingEngine.create(
        cfg["arch"], params, (1, 1), n_slots=S, max_seq=max_seq,
        manager=sess.manager))
    marks.append(("engine", now()))
    stream = requests(traffic, cfg["vocab_size"], s_traffic)
    first = stream[:S]
    first_widths = {_width(len(r["prompt"]) + len(r["given"]) - 1, max_seq)
                    for r in first}
    for w in sorted(set(widths(traffic)) - first_widths):
        eng._prefill_slot(0, np.zeros(w, np.int32))
    marks.append(("prefill buckets", now()))
    live: Dict[int, Any] = {}

    def submit(r):
        req = Request(rid=r["rid"], prompt=r["prompt"], max_new=r["max_new"],
                      out=[int(t) for t in r["given"]])
        live[r["rid"]] = (req, r)
        eng.submit(req)

    for r in first:
        submit(r)
    nxt = S
    eng.step()
    marks.append(("first admissions", now()))
    digest = tree_digest_fn()
    jax.block_until_ready(digest(eng.cache))
    _warm_capture(ops, eng.cache)
    jax.block_until_ready(eng.cache)
    marks.append(("capture kernels", now()))
    setup_s = now() - ctx.t_start
    log("set-up: " + ", ".join(f"{n} {b - a:.2f}s" for (_, a), (n, b)
                               in zip(marks, marks[1:]))
        + f"; {setup_s:.2f}s from process start")

    # --- window --------------------------------------------------------
    saves: List[Dict[str, Any]] = []
    tokens = steps = 0
    model_flops = 0
    finished: List[int] = []
    record_next = None
    next_tokens: Dict[int, list] = {}
    ctx.begin_window()
    t0 = now()
    while now() - t0 < ctx.seconds:
        free = [s for s in range(S) if eng.slot_req[s] is None]
        with rec.span("admit"):
            eng._admit()
        for s in free:
            if eng.slot_req[s] is not None:
                req = eng.slot_req[s]
                model_flops += flops.prefill(cfg, len(req.prompt) + len(req.out) - 1)
        active = [(s, eng.slot_req[s]) for s in range(S) if eng.slot_req[s]]
        model_flops += sum(flops.decode_token(cfg, int(eng.slot_pos[s]) + 1)
                           for s, _ in active)
        with rec.span("step"):
            n = eng.step()
        tokens += n
        steps += 1
        if record_next is not None:
            next_tokens[record_next] = [(s, r.rid, r.out[-1]) for s, r in active]
            record_next = None
        for s, r in active:
            if eng.slot_req[s] is None:
                finished.append(r.rid)
                if nxt < len(stream):
                    submit(stream[nxt])
                    nxt += 1
        if n == 0:
            continue            # the stream ran dry: nothing stepped
        t_call = now()
        with rec.span("snapshot"):
            h = sess.maybe_snapshot()
        if h is not None:
            save = {"step": h.step, "t_call": t_call, "stall": now() - t_call,
                    "digest": digest(eng.cache),
                    "host": _host_state(eng), "handle": h}
            saves.append(save)
            h.add_done_callback(lambda f, sv=save: sv.__setitem__(
                "t_commit", now()))
            record_next = h.step
    window_s = now() - t0
    ctx.end_window()
    if record_next is not None:
        # the window closed right after a save: take the step after it,
        # untimed, so the restore has a step to be compared with
        active = [(s, eng.slot_req[s]) for s in range(S) if eng.slot_req[s]]
        eng.step()
        next_tokens[record_next] = [(s, r.rid, r.out[-1]) for s, r in active]
    attempted = len(live)
    log(f"window {window_s:.2f}s: {steps} engine steps, {tokens} tokens, "
        f"{len(saves)} saves, {len(finished)} requests finished")

    sess.wait()
    peak = ctx.memory_peak()
    stats = dict(sess.stats)
    durable = [sv["t_commit"] - sv["t_call"] for sv in saves]
    for sv, d in zip(saves, durable):
        log(f"save at step {sv['step']}: stall {sv['stall']:.3f}s, capture "
            f"{sv['handle'].timings.get('capture', 0):.3f}s, encode+commit "
            f"{sv['handle'].timings.get('encode_commit', 0):.2f}s, durable "
            f"{d:.2f}s")
        del sv["handle"]
    for sv in saves:
        sv["digest"] = [np.asarray(x).tolist() for x in sv["digest"]]
    served = {rid: (np.concatenate([r["prompt"], np.asarray(req.out, np.int32)]),
                    len(r["prompt"]) + len(r["given"]))
              for rid, (req, r) in live.items()
              if len(req.out) > len(r["given"])}
    # the KV cache of every slot in use, as the window left it: positions
    # 0..slot_pos-1 hold the request's tokens but the pending one
    cache = jax.device_get({"k": eng.cache["k"], "v": eng.cache["v"]})
    in_cache = [(np.concatenate([np.asarray(req.prompt, np.int32),
                                 np.asarray(req.out, np.int32)])[:pos],
                 cache["k"][:, s, :pos], cache["v"][:, s, :pos])
                for s, req, pos in ((s, eng.slot_req[s], int(eng.slot_pos[s]))
                                    for s in range(S)) if req is not None]
    del cache
    sess.close()
    del eng, sess, live
    gc.collect()
    jax.clear_caches()

    # --- restore the newest save and step once -------------------------
    checks: List[Check] = [Check("saves_missing", float(len(saves) == 0), 0.0)]
    restore: Dict[str, float] = {}
    resume_s = float("nan")
    if saves:
        last = saves[-1]
        sess2 = CheckpointSession(spec)
        t_a = now()
        with rec.span("restore"):
            eng2 = sess2.restore(step=last["step"], expect_kind="serving",
                                 params=params)
        t_b = now()
        got = [np.asarray(x).tolist() for x in digest(eng2.cache)]
        host2 = _host_state(eng2)
        bad = sum(a != b for a, b in zip(got, last["digest"])) \
            + sum(host2[k] != last["host"][k] for k in last["host"])
        t_c = now()
        with rec.span("restore"):
            eng2._admit()
            before = [(s, eng2.slot_req[s]) for s in range(S)
                      if eng2.slot_req[s]]
            eng2.step()
        t_d = now()
        resume_s = (t_b - t_a) + (t_d - t_c)
        want = next_tokens.get(last["step"], [])
        got_next = [(s, r.rid, r.out[-1]) for s, r in before]
        miss = sum(a != b for a, b in zip(got_next, want)) \
            + abs(len(got_next) - len(want))
        checks += [Check("restore_mismatch", float(bad), 0.0),
                   Check("next_step_mismatch", float(miss), 0.0)]
        restore = {k: float(v) for k, v in eng2.incarnation.timings.items()
                   if isinstance(v, (int, float))}
        sess2.close()
        del eng2, sess2
        gc.collect()
    shutil.rmtree(ctx.store, ignore_errors=True)
    log(f"restore: resume {resume_s:.2f}s {restore}")

    return {
        "attempted": attempted, "failed": 0,
        "end_to_end": {
            "serve_tokens_per_s": tokens / window_s,
            "durable_s": float(np.mean(durable)) if durable else float("nan"),
            "resume_s": resume_s,
            "setup_s": setup_s,
        },
        "checks": checks,
        "memory_peak_bytes": peak,
        "run": {
            "window_s": window_s, "tokens": tokens, "steps": steps,
            "model_flops": model_flops,
            "stalls": [sv["stall"] for sv in saves],
            "durable": durable,
            "stats": stats,
            "restore": restore,
        },
    }, {"params": params, "served": served, "finished": finished,
        "in_cache": in_cache, "sample_seed": s_sample}


def compare(cell, got, control: bool = False):
    """The served tokens of a sample of the finished requests, and the KV
    cache of every slot the window left, against the plain float32
    reference, each under its limit. With ``control`` the reference in
    float8 weights is put in the program's place: the tokens it would
    have served, and its keys and values, are judged the same way.
    Returns the checks and the readings per request and per slot."""
    cfg, ref = cell.config, cell.reference()
    limits = cell.workload["check"]["limits"]
    max_seq = cell.workload["traffic"]["max_seq"]
    served, params = got["served"], got["params"]
    who = "control" if control else "program"
    t_ref = now()
    sample = _sample(served, got["finished"], cell.workload["check"]["sample"],
                     got["sample_seed"])
    gaps = ref.served_gaps(cfg, params, [served[r] for r in sample],
                           control=control, pad_to=max_seq)
    gap = max((g["gap"] for g in gaps), default=float("nan"))
    log(f"{who} against the reference ({now() - t_ref:.1f}s) over requests "
        f"{sample}: {sum(g['tokens'] for g in gaps)} served tokens, widest "
        f"logit gap {gap!r}")
    kv = ref.kv_errors(cfg, params, got["in_cache"], control=control,
                       pad_to=max_seq)
    kv_err = max((g["kv_err"] for g in kv), default=float("nan"))
    log(f"{who} KV cache of {len(kv)} slots "
        f"({sum(g['tokens'] for g in kv)} positions): worst relative error "
        f"{kv_err!r} ({now() - t_ref:.1f}s in all)")
    return ([Check("logit_gap", gap, limits["logit_gap"]),
             Check("kv_err", kv_err, limits["kv_err"])],
            {"served_sample": gaps, "kv_sample": kv})


def _host_state(eng) -> Dict[str, Any]:
    return {"slot_pos": [int(x) for x in eng.slot_pos],
            "slot_tok": [int(x) for x in np.asarray(eng.slot_tok).ravel()],
            "slots": [None if r is None else (r.rid, len(r.out), r.max_new,
                                              [int(t) for t in r.out[-4:]])
                      for r in eng.slot_req],
            "queue": [r.rid for r in eng.queue]}


def _sample(served: Dict[int, tuple], finished: List[int], k: int,
            seed: int) -> List[int]:
    """The request with the most served tokens, and ``k - 1`` more drawn
    from the seed; finished requests first, others if too few finished."""
    pool = [r for r in finished if r in served] or list(served)
    if not pool:
        return []
    longest = max(pool, key=lambda r: len(served[r][0]) - served[r][1])
    rest = sorted(set(pool) - {longest})
    rng = np.random.Generator(np.random.Philox(key=seed))
    pick = list(rng.permutation(rest)[:max(k - 1, 0)]) if rest else []
    return [longest] + [int(r) for r in pick]


def _warm_capture(ops, cache) -> None:
    """Compile the capture kernels at the cache's leaf shapes, as the
    window's snapshots call them: the fingerprint of a base, the fused
    capture of a link (its first buffer), and the gather that finishes
    an overflowed link at each bucket a link can reach."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ckpt_codec.ref import FP_CHUNK_BYTES

    if jax.default_backend() != "tpu":
        return
    seen = set()
    for leaf in jax.tree.leaves(cache):
        key = (leaf.shape, str(leaf.dtype))
        if key in seen or leaf.nbytes < 2 * FP_CHUNK_BYTES:
            continue
        seen.add(key)
        fp = ops.chunk_fingerprints(leaf)
        r = ops.fused_dirty_chunk_capture(leaf, fp + 1)
        jax.block_until_ready(r[0])
        n_chunks = -(-leaf.nbytes // FP_CHUNK_BYTES)
        size = 512
        while size < n_chunks:
            idx = jnp.zeros(size, jnp.int32)
            jax.block_until_ready(ops._gather_chunks(
                leaf, idx, chunk_bytes=FP_CHUNK_BYTES))
            size *= 2
