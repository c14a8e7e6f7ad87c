"""The LFA fine-tune window: the program's jitted train step
(``make_train_step`` over the LFA mask and masked AdamW, the pieces
``Session.finetune`` composes) driven step after step on synthetic token
rows made on the device from the seed.

Set-up builds one state and one compiled step, and drives them through the
first ``check_steps`` steps with the same call and feed as the window; the
window then continues the same state.  Those first steps are what the plain
reference follows: each step's loss, every trainable leaf's first gradient
as the optimizer got it (``mu / (1 - b1)`` after one step: the clipped
gradient), and every trainable leaf's change after the last check step.
"""

from __future__ import annotations

import numpy as np

from bench import generator
from bench.harness import now


def make_feed(mix: dict, seed: int, vocab: int):
    """``feed(i)``: step ``i``'s rows, all different, made on the device."""
    import jax
    import jax.numpy as jnp
    lo, hi = generator.seed_words(seed)
    base = jax.random.fold_in(jax.random.PRNGKey(hi), lo + 7)
    shape = (mix["batch"], mix["seq_len"])

    @jax.jit
    def rows(i):
        t = jax.random.randint(jax.random.fold_in(base, i), shape, 0, vocab,
                               jnp.int32)
        return {"tokens": t, "labels": t}

    return lambda i: rows(jnp.int32(i))


def build(model, params, mix: dict):
    import jax
    from repro.core import lightweight
    from repro.optim import optimizers
    from repro.train.steps import TrainState, make_train_step
    mask = lightweight.trainable_mask(params, mode="lfa")
    opt = optimizers.adamw(mix["lr"], weight_decay=mix["weight_decay"],
                           mask=mask)
    step = jax.jit(make_train_step(model, opt))
    return step, TrainState(params, opt.init(params)), mask


def _host(tree) -> list:
    import jax
    import jax.numpy as jnp
    return [np.asarray(x, np.float32) for x in jax.device_get(
        [x.astype(jnp.float32) for x in jax.tree.leaves(tree)])]


def _leaf_norms(tree) -> list[float]:
    import jax
    import jax.numpy as jnp
    return [float(x) for x in jax.device_get(
        [jnp.linalg.norm(x.astype(jnp.float32).ravel())
         for x in jax.tree.leaves(tree)])]


def first_steps(step, state, feed, mix: dict):
    """Run the check steps; returns (state, readings of the program)."""
    import jax
    import jax.numpy as jnp
    p0 = state.params
    flat, tdef = jax.tree.flatten(p0)
    losses, grads = [], None
    for i in range(mix["check_steps"]):
        state, m = step(state, feed(i))
        losses.append(float(m["loss"]))
        if i == 0:
            inner = tdef.flatten_up_to(state.opt_state.inner)
            b1 = mix["b1"]
            first = _host([s["mu"] / (1 - b1) if isinstance(s, dict)
                           else jnp.zeros(()) for s in inner])
            grads = [float(np.linalg.norm(g)) for g in first]
    change = _leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        state.params, p0))
    return state, {"loss": losses, "grad": grads, "change": change,
                   "first_grad": first}


def window(step, state, feed, mix: dict, seconds: float, annotate,
           on_window):
    """Steps until ``seconds`` have passed; the window closes when the step
    running at that moment has finished."""
    k = mix["check_steps"]
    t0 = now()
    on_window("open", t0)
    done = 0
    while now() - t0 < seconds:
        on_window("tick", now())
        with annotate("bench.train_step"):
            state, m = step(state, feed(k + done))
            loss = float(m["loss"])
        if not np.isfinite(loss):
            raise RuntimeError(f"non-finite loss {loss} at step {k + done}")
        done += 1
    t1 = now()
    on_window("close", t1)
    tokens = done * mix["batch"] * mix["seq_len"]
    return state, {"t0": t0, "t_close": t1, "steps": done,
                   "train_tok_s": tokens / (t1 - t0)}


# --------------------------------------------------------------------------
# the reference
# --------------------------------------------------------------------------


def reference_readings(ref, rcfg: dict, params, feed, mix: dict,
                       quant=None, rows=None) -> dict:
    """The plain reference's losses, first clipped gradients and changes
    after ``check_steps`` steps (``rows`` keeps only the first rows of each
    batch: the half-batch fault)."""
    import jax
    opt = {k: mix[k] for k in ("b1", "b2", "eps", "lr", "weight_decay",
                               "grad_clip")}

    def grads_fn(p, k):
        toks = feed(k)["tokens"]
        if rows is not None:
            toks = toks[:rows]
        return ref.loss_and_grad(rcfg, p, toks, quant)

    losses, first, last = ref.adamw_steps(opt, params, grads_fn,
                                          mix["check_steps"])
    change = jax.tree.map(lambda a, b: a - b, last, params)
    first = _host(first)
    return {"loss": losses, "grad": [float(np.linalg.norm(g)) for g in first],
            "first_grad": first,
            "change": _leaf_norms(change),
            "trainable": [ref.lfa_trainable(p) for p, _ in
                          jax.tree_util.tree_flatten_with_path(params)[0]]}


def compare(prog: dict, ref: dict) -> dict:
    """The numbers that decide ``correct``:

    * ``loss_rel_gap``: the largest relative gap of a check step's loss;
    * ``grad_norm_gap`` / ``change_norm_gap``: over trainable leaves, the
      largest gap between the program's and the reference's norms, over the
      larger of the reference leaf's norm and the median leaf's.  Leaves
      whose reference gradient is under a thousandth of the median leaf's
      move by round-off alone under Adam and are left out of the change;
    * ``grad_rel_err``: over trainable leaves, the largest norm of the
      difference of the first gradients, over the same denominator.  Gaps
      of norms barely see element-wise rounding noise (it adds to a norm
      in quadrature); this number does;
    * ``grad_rel_err_med``: the same per-leaf error, its median over the
      trainable leaves.  It is steady from seed to seed where the largest
      swings with the noisiest leaf, and a lower precision moves every
      leaf, so it separates a lower precision.
    """
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    tr = np.asarray(ref["trainable"])
    gp, gr = np.asarray(prog["grad"])[tr], np.asarray(ref["grad"])[tr]
    cp, cr = np.asarray(prog["change"])[tr], np.asarray(ref["change"])[tr]
    gmed = np.median(gr)
    moving = gr >= 1e-3 * gmed
    cmed = np.median(cr[moving])
    rel = np.array([
        np.linalg.norm(a - b) / max(float(np.linalg.norm(b)), gmed)
        for a, b, t in zip(prog["first_grad"], ref["first_grad"], tr) if t])
    return {
        "loss_rel_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_gap": float(np.max(np.abs(gp - gr)
                                      / np.maximum(gr, gmed))),
        "change_norm_gap": float(np.max(
            np.abs(cp - cr)[moving] / np.maximum(cr[moving], cmed))),
        "grad_rel_err": float(np.max(rel)),
        "grad_rel_err_med": float(np.median(rel)),
        "leaves_compared": int(moving.sum()),
        "leaves_still": int((~moving).sum()),
    }
