"""Training under periodic snapshots: ``Trainer`` attached to a
``CheckpointSession`` whose policy saves every ``interval`` steps.

Set-up builds the trainer through the logged runtime (its step compiles
there), hands it the weights made from the seed and the job's step
counter, and drives it through its first three steps with the window's
own call and feed. Those steps give what the reference is compared on:
each step's loss, each leaf's norm of the first gradient as the
optimizer got it (its first moment over ``1 - b1``), and each leaf's
norm of the parameters' change over the three steps, read before the
fourth step takes them. The window runs ``train_steps(1)`` for
``--seconds`` with ``maybe_snapshot()`` after each step; the cadence
puts one save early in the window. After the window the save is waited
for, the trainer is dropped, JAX's caches are cleared, the save is
restored and stepped once, and the reference runs the first three steps
on its own.

The traffic file's keys (``bench/workloads/<cell>.json``, ``traffic``):
``rows`` and ``seq_len`` of a batch, and ``checked_steps``.
"""
from __future__ import annotations

import gc
import shutil
from typing import Any, Dict, List

import numpy as np

from harness import Check, log, now, sub_seeds, tree_digest_fn


class Batches:
    """The job's data: batch ``cursor`` made from the seed by the
    benchmark, in the form the trainer's pipeline hands its step."""

    def __init__(self, ref, cfg, seed: int, rows: int, seq: int) -> None:
        self.ref, self.cfg, self.seed, self.rows, self.seq = \
            ref, cfg, seed, rows, seq

    def batch_at(self, cursor: int) -> Dict[str, np.ndarray]:
        return self.ref.batch(self.cfg, self.seed, cursor, self.rows,
                              self.seq)


def _state(tr) -> Dict[str, Any]:
    return {"params": tr.upper.get("params"),
            "opt_state": tr.upper.get("opt_state")}


def _scalars(tr) -> List[int]:
    return [int(tr.upper.get("step")), int(tr.upper.get("data_cursor"))]


def run(ctx) -> Dict[str, Any]:
    import jax

    from repro.api import CheckpointSession, Policy
    from repro.train.loop import Trainer, TrainJob

    cell, rec = ctx.cell, ctx.record
    cfg, traffic = cell.config, cell.workload["traffic"]
    limits = cell.workload["check"]["limits"]
    ref, flops = cell.reference(), cell.flops()
    s_weights, s_data = sub_seeds(ctx.seed, 2)
    rows, seq = traffic["rows"], traffic["seq_len"]
    n_check = traffic["checked_steps"]
    b1 = cfg["optimizer"]["b1"]

    # --- set-up: the one trainer, driven through its first steps -------
    marks = [("start", now())]
    spec = f"localfs:{ctx.store}"
    pol = cfg["policy"]
    sess = CheckpointSession(spec, Policy(chain=pol["chain"],
                                          interval=pol["interval"]))
    job = TrainJob(arch=cfg["arch"], shape_key=f"train_s{seq}_b{rows}",
                   init_seed=s_weights)
    tr = sess.attach(Trainer(job, (1, 1), ("data", "model"),
                             manager=sess.manager))
    marks.append(("trainer", now()))
    tr.init_state()
    tr.upper.update("params", ref.init_params(cfg, s_weights,
                                              shardings=tr.pshard))
    tr.upper.update("step", np.int64(cfg["start_step"]))
    data = Batches(ref, cfg, s_data, rows, seq)
    tr.pipeline = data
    norms, change, digest = ref.leaf_norms_fn(), ref.change_norms_fn(), \
        tree_digest_fn()
    marks.append(("state", now()))
    losses: List[float] = []
    grad_norms: List[float] = []
    grad_sample: List[np.ndarray] = []
    for i in range(n_check):
        losses.append(tr.train_steps(1)["loss"])
        if i == 0:
            # the first moment after one step is (1 - b1) times the
            # gradient the optimizer got
            mu = tr.upper.get("opt_state")["mu"]
            grad_norms = [float(x) / (1 - b1) for x in norms(mu)]
            grad_sample = [np.asarray(x) / (1 - b1)
                           for x in ref.sample_fn()(mu)]
    marks.append(("first steps", now()))
    p0 = ref.init_params(cfg, s_weights, shardings=tr.pshard)
    change_norms = [float(x) for x in change(tr.upper.get("params"), p0)]
    del p0
    jax.block_until_ready(digest(_state(tr)))
    marks.append(("norms", now()))
    setup_s = now() - ctx.t_start
    log("set-up: " + ", ".join(f"{n} {b - a:.2f}s" for (_, a), (n, b)
                               in zip(marks, marks[1:]))
        + f"; {setup_s:.2f}s from process start")

    # --- window --------------------------------------------------------
    saves: List[Dict[str, Any]] = []
    step_loss: Dict[int, float] = {}
    steps = 0
    ctx.begin_window()
    t0 = now()
    while now() - t0 < ctx.seconds:
        with rec.span("step"):
            m = tr.train_steps(1)
        steps += 1
        step_loss[tr.checkpoint_step()] = m["loss"]
        t_call = now()
        with rec.span("snapshot"):
            h = sess.maybe_snapshot()
        if h is not None:
            save = {"step": h.step, "t_call": t_call, "stall": now() - t_call,
                    "digest": digest(_state(tr)), "scalars": _scalars(tr),
                    "handle": h}
            saves.append(save)
            h.add_done_callback(lambda f, sv=save: sv.__setitem__(
                "t_commit", now()))
    window_s = now() - t0
    ctx.end_window()
    tokens = steps * rows * seq
    if saves and saves[-1]["step"] + 1 not in step_loss:
        # the window closed right after the save: take the step after
        # it, untimed, so the restore has a step to be compared with
        step_loss[saves[-1]["step"] + 1] = tr.train_steps(1)["loss"]
    log(f"window {window_s:.2f}s: {steps} steps, {len(saves)} saves at "
        f"{[sv['step'] for sv in saves]}")

    sess.wait()
    peak = ctx.memory_peak()
    stats = dict(sess.stats)
    durable = [sv["t_commit"] - sv["t_call"] for sv in saves]
    for sv, d in zip(saves, durable):
        log(f"save at step {sv['step']}: stall {sv['stall']:.3f}s, "
            f"encode+commit {sv['handle'].timings.get('encode_commit', 0):.2f}s"
            f", durable {d:.2f}s")
        del sv["handle"]
    for sv in saves:
        sv["digest"] = [np.asarray(x).tolist() for x in sv["digest"]]
    sess.close()
    del tr, sess
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
            tr2 = sess2.restore(step=last["step"], expect_kind="train")
        t_b = now()
        tr2.pipeline = data
        got = [np.asarray(x).tolist() for x in digest(_state(tr2))]
        bad = sum(a != b for a, b in zip(got, last["digest"])) \
            + abs(len(got) - len(last["digest"])) \
            + sum(a != b for a, b in zip(_scalars(tr2), last["scalars"]))
        t_c = now()
        with rec.span("restore"):
            loss = tr2.train_steps(1)["loss"]
        t_d = now()
        resume_s = (t_b - t_a) + (t_d - t_c)
        want = step_loss.get(last["step"] + 1)
        checks += [Check("restore_mismatch", float(bad), 0.0),
                   Check("next_step_mismatch",
                         float(want is None or loss != want), 0.0)]
        restore = {k: float(v) for k, v in tr2.incarnation.timings.items()
                   if isinstance(v, (int, float))}
        sess2.close()
        del tr2, sess2
        gc.collect()
    shutil.rmtree(ctx.store, ignore_errors=True)
    jax.clear_caches()

    # --- the reference over the first steps -----------------------------
    log(f"restore: resume {resume_s:.2f}s {restore}")
    t_ref = now()
    batches = [data.batch_at(i) for i in range(n_check)]
    r = ref.train_reference(cfg, s_weights, batches, cfg["start_step"],
                            steps=n_check)
    cmp = ref.compare(r, {"losses": losses, "grad_norms": grad_norms,
                          "grad_sample": grad_sample,
                          "change_norms": change_norms})
    log(f"reference ({now() - t_ref:.1f}s): losses {r['losses']} vs program "
        f"{losses}; {cmp}")
    keep = np.ones(len(r["leaves"]), bool)
    for what in ("grad_norms", "change_norms"):
        gaps = ref.leaf_gaps(locals()[what], r[what], keep)
        log(f"{what} gap by leaf: " + ", ".join(
            f"{r['leaves'][i]} {gaps[i]:.4f} (ref {r[what][i]:.4g})"
            for i in np.argsort(-gaps)[:6]))
    for k in ("loss_gap", "grad_err", "change_gap"):
        checks.append(Check(k, cmp[k], limits[k]))

    return {
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_tokens_per_s": tokens / window_s,
            "durable_s": float(np.mean(durable)) if durable else float("nan"),
            "resume_s": resume_s,
            "setup_s": setup_s,
        },
        "checks": checks,
        "memory_peak_bytes": peak,
        "run": {
            "window_s": window_s, "tokens": tokens, "steps": steps,
            "model_flops": flops.train_token(cfg) * tokens,
            "stalls": [sv["stall"] for sv in saves],
            "durable": durable,
            "stats": stats,
            "restore": restore,
            "compare": cmp,
            "reference": {k: v for k, v in r.items() if k != "grad_sample"},
            "program": {"losses": losses, "grad_norms": grad_norms,
                        "change_norms": change_norms},
        },
    }
