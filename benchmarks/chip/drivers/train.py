"""Training traffic: the program's compiled train step, driven from the seed.

Set-up builds one object, the jitted step with its state (weights and
activation-subspace warm starts drawn from the seed), and drives it
through its first ``check_steps`` steps on pool batches that all differ.
Those steps compile the step and give the numbers the plain reference is
compared with: each step's loss, the leaf norms of the first gradient as
the optimizer received it (read from AdamW's first moment after one step)
and the leaf norms of the parameters' change over those steps (taken on
the host against a host copy of the starting weights, so that the check
holds no second set of weights on the device). The same state then runs
the window, cycling through the pool, with at most two steps in flight.

A traffic file of this kind holds ``batch``, ``seq``, ``pool`` (batches
drawn before the window), ``check_steps`` and ``optimizer``.
"""
from __future__ import annotations

import gc

import harness
import model

def tcfg_of(opt: dict):
    from repro.config import TrainConfig

    return TrainConfig(optimizer=opt["name"], lr=opt["lr"],
                       weight_decay=opt["weight_decay"],
                       clip_norm=opt["clip_norm"], schedule="constant",
                       steps=1 << 30, checkpoint_every=0)


def draw_pool(seed: int, mix: dict, vocab: int):
    """``pool`` batches of uniformly drawn tokens, each row different,
    labels the next token; made on the device before the window."""
    import jax
    import jax.numpy as jnp

    b, s, n = mix["batch"], mix["seq"], mix["pool"]

    @jax.jit
    def draw(key):
        t = jax.random.randint(key, (n, b, s + 1), 0, vocab, jnp.int32)
        return {"tokens": t[..., :-1], "labels": t[..., 1:]}

    pool = draw(harness.jax_key(seed, 3))
    return [jax.tree.map(lambda x: x[i], pool) for i in range(n)]


def job_config(cfg: dict, mix: dict) -> dict:
    """The configuration as the job trains it: ``param_dtype`` in the
    traffic file sets the type the program keeps its weights in."""
    if "param_dtype" not in mix:
        return cfg
    return dict(cfg, program=dict(cfg["program"], dtype=mix["param_dtype"]))


def build(cfg: dict, mix: dict, seed: int, fault: str | None = None):
    """The program's train state and jitted step for this cell and seed."""
    import jax

    cfg = job_config(cfg, mix)
    from repro.models.lm import lm_loss
    from repro.train.step import make_train_state, make_train_step

    mcfg = model.model_config(cfg)
    model.install_plan(cfg, mcfg, batch=mix["batch"], seq=mix["seq"])
    tcfg = tcfg_of(mix["optimizer"])
    params = model.draw_params(seed, model.params_template(mcfg))
    states = model.draw_states(
        seed, model.states_template(mcfg, mix["batch"], mix["seq"]))
    state = make_train_state(jax.random.PRNGKey(0), params, mcfg, tcfg,
                             asi_states=states)
    step = make_train_step(lm_loss, mcfg, tcfg)
    if fault == "unchanged":
        inner = step
        step = lambda s, b: (s, inner(s, b)[1])
    elif fault == "half_batch":
        inner = step
        step = lambda s, b: inner(
            s, jax.tree.map(lambda x: x[:x.shape[0] // 2], b))
    return mcfg, state, jax.jit(step, donate_argnums=0)


def program_numbers(cfg: dict, mix: dict, seed: int, state, step, pool):
    """Drive the first ``check_steps`` steps; return the state after them
    and the numbers the reference checks."""
    import jax

    from reference.qwen2 import leaf_norms

    b1 = mix["optimizer"]["beta1"]
    p0 = jax.device_get(state.params)
    losses, grad = [], None
    for i in range(mix["check_steps"]):
        state, metrics = step(state, pool[i])
        losses.append(float(metrics["loss"]))
        if i == 0:
            # AdamW's first moment after one step is (1 - beta1) * g
            mu = state.opt.mu
            grad = jax.device_get(leaf_norms(
                jax.tree.map(lambda m: m / (1 - b1), mu)))
    change = host_change_norms(state.params, p0)
    del p0
    return state, {"losses": losses, "grad": grad, "change": change}


def host_change_norms(params, p0) -> dict:
    """``leaf_norms`` of ``params - p0``, with ``p0`` on the host, taken
    on the host in float32."""
    import jax
    import numpy as np

    from reference.qwen2 import leaf_norms

    flat, tree = jax.tree_util.tree_flatten(params)
    diffs = [np.asarray(jax.device_get(a), np.float32)
             - np.asarray(b, np.float32)
             for a, b in zip(flat, jax.tree_util.tree_leaves(p0))]
    return leaf_norms(jax.tree_util.tree_unflatten(tree, diffs), xp=np)


def reference_numbers(cfg: dict, mix: dict, seed: int, prec: str = "f32"):
    """The plain reference's numbers for the same seed, from weights and
    states it draws itself."""
    from reference import qwen2

    cfg = job_config(cfg, mix)
    mcfg = model.model_config(cfg)
    params = model.draw_params(seed, model.params_template(mcfg))
    states = model.draw_states(
        seed, model.states_template(mcfg, mix["batch"], mix["seq"]))
    pool = draw_pool(seed, mix, cfg["model"]["vocab_size"])
    return qwen2.train_reference(prec, cfg, mix["optimizer"], params, states,
                                 pool[:mix["check_steps"]])


def compare(prog: dict, ref: dict, limits: dict, *, skip_share: float):
    """The compared numbers: the widest loss gap over the checked steps,
    and for the first gradient and the change the worst leaf's gap between
    the program's norm and the reference's, over the larger of that leaf's
    reference norm and the median leaf's. Leaves whose reference gradient
    is under ``skip_share`` of the median leaf's are left out of the
    change (they move by round-off alone); of the others, a leaf the
    reference moves and the program leaves exactly where it was fails."""
    import numpy as np

    loss_gap = max(abs(a - b) for a, b in zip(prog["losses"], ref["losses"]))

    def flat(d):
        keys = sorted(d)
        return keys, np.concatenate([np.asarray(d[k], np.float64).ravel()
                                     for k in keys])

    _, rg = flat(ref["grad"])
    _, pg = flat(prog["grad"])
    _, rc = flat(ref["change"])
    _, pc = flat(prog["change"])
    gmed, cmed = np.median(rg), np.median(rc)
    grad_gap = float(np.max(np.abs(pg - rg) / np.maximum(rg, gmed)))
    moving = rg >= skip_share * gmed
    change_gap = float(np.max((np.abs(pc - rc) / np.maximum(rc, cmed))[moving]))
    unmoved = int(np.sum(moving & (rc > 0) & (pc == 0)))
    return [harness.check("unmoved_leaves", unmoved, 0),
            harness.check("loss_gap", loss_gap, limits["loss_gap"]),
            harness.check("grad_norm_gap", grad_gap, limits["grad_norm_gap"]),
            harness.check("change_norm_gap", change_gap,
                          limits["change_norm_gap"])]


def left_out(ref: dict, skip_share: float) -> int:
    """How many leaves the rule of ``compare`` leaves out of the change."""
    import numpy as np

    rg = np.concatenate([np.asarray(v).ravel() for v in ref["grad"].values()])
    return int(np.sum(rg < skip_share * np.median(rg)))


def run(w: dict, seed: int, seconds: float, trace_dir, t_start: float,
        fault: str | None = None) -> dict:
    import jax

    cfg, mix = w["cfg"], w["mix"]
    mcfg, state, step = build(cfg, mix, seed, fault)
    pool = draw_pool(seed, mix, cfg["model"]["vocab_size"])
    jax.block_until_ready((state, pool))
    peak_built = harness.memory_peak_bytes(w["chips"])
    state, prog = program_numbers(cfg, mix, seed, state, step, pool)
    # the rest of the warm-up: one more step, then nothing compiles
    state, m = step(state, pool[mix["check_steps"] % len(pool)])
    jax.block_until_ready((state, m))
    peak_setup = harness.memory_peak_bytes(w["chips"])
    tokens_per_step = mix["batch"] * mix["seq"]
    counter = harness.CompileCounter()

    watch = harness.HostWatch()
    setup_s = harness.now() - t_start
    counter.active = True
    steps, i = 0, mix["check_steps"] + 1
    prof = harness.Profiler(trace_dir, mix["trace_start_s"],
                            mix["trace_seconds"])
    traced = [0, 0]             # steps done when the trace started, stopped
    t0 = harness.now()
    pending = None
    while True:
        if prof.poll(harness.now() - t0, pending):
            traced[prof.done] = steps
        with harness.span("bench.train_dispatch"):
            state, m = step(state, pool[i % len(pool)])
        i += 1
        steps += 1
        if pending is not None:
            with harness.span("bench.train_wait"):
                jax.block_until_ready(pending)
            watch.mark()
        pending = m["loss"]
        if harness.now() - t0 >= seconds:
            break
    with harness.span("bench.train_wait"):
        jax.block_until_ready((state, m))
    window_s = harness.now() - t0
    host = watch.stop()
    if prof.on:
        prof.stop()
        traced[1] = steps
    counter.active = False
    peak = harness.memory_peak_bytes(w["chips"])
    losses_ok = bool(jax.numpy.isfinite(m["loss"]))
    del state, step, pool, m, pending
    gc.collect()

    ref = reference_numbers(cfg, mix, seed)
    checks = compare(prog, ref, w["limits"], skip_share=mix["skip_share"])
    checks.append(harness.check("window_loss_not_finite",
                                0.0 if losses_ok else 1.0, 0.0))
    return {
        "attempted": steps, "failed": 0 if losses_ok else steps,
        "checks": checks, "memory_peak_bytes": peak,
        "window_s": window_s, "setup_s": setup_s,
        "counters": {"steps": steps, "tokens": steps * tokens_per_step,
                     "compiles_in_window": counter.count,
                     "traced_steps": traced[1] - traced[0],
                     "traced_s": prof.traced_s},
        "e2e": {"train_tok_s": steps * tokens_per_step / window_s,
                "peak_hbm_mib": peak / 2**20,
                "setup_s": setup_s},
        "info": {"losses": prog["losses"], "reference_losses": ref["losses"],
                 "leaves_left_out_of_change": left_out(ref, mix["skip_share"]),
                 "step_s_mean": window_s / steps,
                 "peak_mib_built": peak_built / 2**20,
                 "peak_mib_setup": peak_setup / 2**20,
                 "host": host},
    }
