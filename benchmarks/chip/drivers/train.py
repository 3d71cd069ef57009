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
drawn before the window), ``check_steps`` and ``optimizer``. The
configuration's family (``families/``) gives the loss and what a batch
holds; its ``reference`` key names the plain reference,
``reference/<name>.py`` (default ``qwen2``).
"""
from __future__ import annotations

import gc
import importlib

import harness
import model


def tcfg_of(opt: dict):
    from repro.config import TrainConfig

    return TrainConfig(optimizer=opt["name"], lr=opt["lr"],
                       weight_decay=opt["weight_decay"],
                       clip_norm=opt["clip_norm"], schedule="constant",
                       steps=1 << 30, checkpoint_every=0)


def reference(cfg: dict):
    """The configuration's plain reference module."""
    return importlib.import_module(
        "reference." + cfg.get("reference", "qwen2"))


def draw_pool(seed: int, cfg: dict, mix: dict):
    """``pool`` batches drawn from the seed, made on the device before the
    window."""
    import jax

    fam = model.family(cfg)
    pool = jax.jit(lambda k: fam.draw_batches(cfg, mix, mix["pool"], k))(
        harness.jax_key(seed, 3))
    return [jax.tree.map(lambda x: x[i], pool) for i in range(mix["pool"])]


def batch_template(cfg: dict, mix: dict):
    """One batch's shapes, as ``jax.ShapeDtypeStruct``s."""
    import jax

    fam = model.family(cfg)
    one = jax.eval_shape(lambda k: fam.draw_batches(cfg, mix, 1, k),
                         jax.random.PRNGKey(0))
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                        one)


def job_config(cfg: dict, mix: dict) -> dict:
    """The configuration as the job trains it: ``param_dtype`` in the
    traffic file sets the type the program keeps its weights in."""
    if "param_dtype" not in mix:
        return cfg
    return dict(cfg, program=dict(cfg["program"], dtype=mix["param_dtype"]))


def _program(cfg: dict, mix: dict):
    """(job configuration, ``ModelConfig``, ``TrainConfig``, step function)
    with the plan installed: what ``build`` and ``lowered`` share."""
    from repro.train.step import make_train_step

    cfg = job_config(cfg, mix)
    mcfg = model.model_config(cfg)
    model.install_plan(cfg, mcfg, batch=mix["batch"], seq=mix["seq"])
    tcfg = tcfg_of(mix["optimizer"])
    step = make_train_step(model.family(cfg).loss(cfg), mcfg, tcfg)
    return cfg, mcfg, tcfg, step


def _jit(step):
    import jax

    return jax.jit(step, donate_argnums=0)


def build(cfg: dict, mix: dict, seed: int, fault: str | None = None):
    """The program's train state and jitted step for this cell and seed."""
    import jax
    import jax.numpy as jnp

    from repro.train.step import make_train_state

    cfg, mcfg, tcfg, step = _program(cfg, mix)
    params = model.draw_params(seed, cfg, mcfg)
    states = model.draw_states(seed, model.states_template(
        cfg, mcfg, mix["batch"], mix["seq"]))
    if mcfg.wasi.project:
        # project mode starts from a truncated SVD of every scoped weight;
        # on a TPU that SVD compiles for minutes in every process, so the
        # program makes its state on the host's CPU device, moved after
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            state = make_train_state(jax.random.PRNGKey(0),
                                     jax.device_put(params, cpu), mcfg, tcfg,
                                     asi_states=jax.device_put(states, cpu))
        state = jax.device_put(state, jax.devices()[0])
    else:
        state = make_train_state(jax.random.PRNGKey(0), params, mcfg, tcfg,
                                 asi_states=states)
    del params, states
    if fault == "unchanged":
        inner = step
        step = lambda s, b: (s, inner(s, b)[1])
    elif fault == "half_batch":
        # the second half of the rows replaced by the first: the mean is
        # taken over half the batch, and every shape stays as it was
        inner = step
        step = lambda s, b: inner(s, jax.tree.map(
            lambda x: jnp.concatenate([x[:x.shape[0] // 2]] * 2), b))
    return mcfg, state, _jit(step)


def lowered(cfg: dict, mix: dict):
    """The step ``build`` jits for this configuration and traffic, lowered
    on ``jax.ShapeDtypeStruct``s (no weights on the device)."""
    import jax

    from repro.train.step import make_train_state

    cfg, mcfg, tcfg, step = _program(cfg, mix)
    state = jax.eval_shape(
        lambda p, s: make_train_state(jax.random.PRNGKey(0), p, mcfg, tcfg,
                                      asi_states=s),
        model.params_template(cfg, mcfg),
        model.states_template(cfg, mcfg, mix["batch"], mix["seq"]))
    return _jit(step).lower(state, batch_template(cfg, mix))


def program_numbers(cfg: dict, mix: dict, seed: int, state, step, pool):
    """Drive the first ``check_steps`` steps; return the state after them
    and the numbers the reference checks."""
    import jax

    leaf_norms = reference(cfg).leaf_norms
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
    change = host_change_norms(state.params, p0, leaf_norms)
    del p0
    return state, {"losses": losses, "grad": grad, "change": change}


def host_change_norms(params, p0, leaf_norms) -> dict:
    """``leaf_norms`` of ``params - p0``, with ``p0`` on the host, taken
    on the host in float32."""
    import jax
    import numpy as np

    flat, tree = jax.tree_util.tree_flatten(params)
    diffs = [np.asarray(jax.device_get(a), np.float32)
             - np.asarray(b, np.float32)
             for a, b in zip(flat, jax.tree_util.tree_leaves(p0))]
    return leaf_norms(jax.tree_util.tree_unflatten(tree, diffs), xp=np)


def reference_numbers(cfg: dict, mix: dict, seed: int, prec: str = "f32"):
    """The plain reference's numbers for the same seed, from weights and
    states it draws itself."""
    ref = reference(cfg)
    cfg = job_config(cfg, mix)
    mcfg = model.model_config(cfg)
    params = model.draw_params(seed, cfg, mcfg)
    states = model.draw_states(seed, model.states_template(
        cfg, mcfg, mix["batch"], mix["seq"]))
    pool = draw_pool(seed, cfg, mix)
    return ref.train_reference(prec, cfg, mix["optimizer"], params, states,
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
    pool = draw_pool(seed, cfg, mix)
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
