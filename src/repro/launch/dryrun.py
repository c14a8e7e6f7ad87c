# The dry-run (and ONLY the dry-run) builds the production mesh out of 512
# placeholder host devices.  Must run before ANY other import — jax locks the
# device count on first init.
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402

from repro import configs                       # noqa: E402
from repro.configs.base import SHAPES           # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as M             # noqa: E402
from repro.parallel import sharding as S        # noqa: E402
from repro.train.steps import TrainState, make_train_step  # noqa: E402
from repro import optim                          # noqa: E402
from repro.core import lightweight               # noqa: E402


from repro.launch.hlo_analysis import analyze as hlo_analyze  # noqa: E402
from repro.launch.roofline import active_param_count  # noqa: E402


# ---------------------------------------------------------------------------
# per-cell dry-run
# ---------------------------------------------------------------------------


def abstract_state(model, mesh, rules, *, lfa: bool = True, lr=1e-4):
    """(TrainState shapes, TrainState shardings, optimizer) — no allocation."""
    from repro.core.layers import Annot
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    is_annot = lambda x: isinstance(x, Annot)
    params_shape = jax.tree.map(lambda a: a.value, tree, is_leaf=is_annot)
    axes = jax.tree.map(lambda a: a.axes, tree, is_leaf=is_annot)
    p_shardings = S.tree_shardings(axes, params_shape, mesh, rules)

    mask = lightweight.trainable_mask(params_shape,
                                      mode="lfa" if lfa else "full")
    opt = optim.adamw(lr, mask=mask)
    state_shape = jax.eval_shape(lambda p: TrainState(p, opt.init(p)),
                                 params_shape)

    # optimizer moments mirror each param's sharding (same shape); scalars
    # (step counter) replicate.
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    flat_sh, tdef = jax.tree.flatten(p_shardings)
    subtrees = tdef.flatten_up_to(state_shape.opt_state.inner)
    inner_sh = tdef.unflatten([
        jax.tree.map(lambda sd: sh if sd.shape else repl, sub)
        for sh, sub in zip(flat_sh, subtrees)])
    state_sh = TrainState(p_shardings, optim.OptState(repl, inner_sh))
    return state_shape, state_sh, opt, params_shape, p_shardings


def build_step(arch: str, shape_name: str, mesh, *, mpo: bool = True,
               lfa: bool = True, overrides=None):
    """Returns (jitted fn, example args of ShapeDtypeStructs, cfg)."""
    cfg = configs.get_config(arch, **(overrides or {}))
    if not mpo:
        cfg = dataclasses.replace(
            cfg, mpo=dataclasses.replace(cfg.mpo, enabled=False))
    elif lfa:
        # LFA at the graph level too: frozen central cores produce no
        # gradients at all (no compute, no reduction) — §Perf it.16
        cfg = dataclasses.replace(
            cfg, mpo=dataclasses.replace(cfg.mpo, freeze_central_grads=True))
    shape = SHAPES[shape_name]
    model = M.build(cfg)
    # head-split guard mirrors serving (see sharding.head_safe_rules)
    rules = S.head_safe_rules(
        S.make_rules(mesh, sp=cfg.parallelism == "sp"), cfg, mesh)

    specs = M.input_specs(cfg, shape)
    in_shardings = S.batch_sharding(specs, mesh, rules)

    if shape.kind == "train":
        state_shape, state_sh, opt, _, _ = abstract_state(
            model, mesh, rules, lfa=lfa)
        step_fn = make_train_step(model, opt)
        fn = jax.jit(step_fn, in_shardings=(state_sh, in_shardings),
                     out_shardings=(state_sh, None))
        return fn, (state_shape, specs), cfg

    _, _, _, params_shape, p_shardings = abstract_state(model, mesh, rules)

    cache_shape = M.cache_specs(cfg, shape)
    c_shardings = S.cache_sharding(cache_shape, mesh, rules)

    if shape.kind == "prefill":
        def pf(params, batch, cache):
            return model.prefill(params, batch, cache)

        fn = jax.jit(pf, in_shardings=(p_shardings, in_shardings, c_shardings),
                     out_shardings=(None, c_shardings))
        return fn, (params_shape, specs, cache_shape), cfg

    def dec(params, tokens, cache):
        return model.decode_step(params, tokens, cache)

    fn = jax.jit(dec, in_shardings=(p_shardings, in_shardings["tokens"],
                                    c_shardings),
                 out_shardings=(None, c_shardings))
    return fn, (params_shape, specs["tokens"], cache_shape), cfg


def model_flops(cfg, shape, n_active: int) -> float:
    """MODEL_FLOPS = 6 N D (train) / 2 N D (inference), N = active params."""
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch  # decode: one token


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, mpo=True,
             lfa=True, overrides=None, verbose=True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = SHAPES[shape_name]
    t0 = time.time()
    from repro.parallel.ctx import current_mesh, sequence_parallel
    sp = configs.get_config(arch, **(overrides or {})).parallelism == "sp"
    with mesh, current_mesh(mesh), sequence_parallel(sp):
        fn, args, cfg = build_step(arch, shape_name, mesh, mpo=mpo, lfa=lfa,
                                   overrides=overrides)
        # static placement lint at the PRODUCTION mesh, before paying for
        # the lowering: the PR-4 bug class (head-splitting rules, data-
        # sharded norm leaves) surfaces here with provenance instead of as
        # a compiled-artifact numeric drift
        from repro.analysis import format_findings, lint_sharding, summarize
        lint_findings = lint_sharding(cfg, mesh)
        if any(f.severity == "error" for f in lint_findings):
            print(format_findings(lint_findings), file=sys.stderr)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    t1 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = hlo_analyze(compiled.as_text())
    n_dev = mesh.devices.size
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": n_dev,
        "compile_s": round(t1 - t0, 1),
        # sharding-lint verdict at this exact production mesh (errors were
        # already printed to stderr above)
        "sharding_lint": summarize(lint_findings),
        # raw cost_analysis (per-device, scan bodies counted ONCE — see
        # hlo_analysis docstring); kept for cross-checking
        "xla_flops_raw": cost.get("flops", 0.0),
        "xla_bytes_raw": cost.get("bytes accessed", 0.0),
        # trip-count-corrected per-device numbers (primary roofline source)
        "flops_per_device": hlo["hlo_dot_flops_per_device"],
        "bytes_per_device": hlo["hlo_dot_bytes_per_device"],
        "bytes_upper_bound_per_device": hlo["hlo_bytes_written_per_device"],
        "collective_bytes": hlo["hlo_collective_bytes_per_device"],
        # useful-work references: MPO-compressed active params and the
        # dense-equivalent (what the matmuls in `reconstruct` mode compute)
        "model_flops": model_flops(cfg, shape, active_param_count(cfg)),
        "model_flops_dense": model_flops(
            cfg, shape, active_param_count(dataclasses.replace(
                cfg, mpo=dataclasses.replace(cfg.mpo, enabled=False)))),
    }
    try:
        rec["memory_analysis"] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.temp_size_in_bytes
                           + mem.output_size_in_bytes),
        }
    except Exception:
        rec["memory_analysis"] = str(mem)
    if verbose:
        print(json.dumps(rec, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dense", action="store_true",
                    help="disable MPO (baseline parameterization)")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    cells = []
    if args.all:
        cells = [(a, s) for a, s, skip in configs.cells() if not skip]
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    records = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape, multi_pod=mp, mpo=not args.dense)
            except Exception as e:  # a failing cell is a bug — surface it
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "error": f"{type(e).__name__}: {e}"}
                print(json.dumps(rec), file=sys.stderr)
            records.append(rec)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec, default=str) + "\n")
    n_err = sum(1 for r in records if "error" in r)
    print(f"# dry-run complete: {len(records) - n_err}/{len(records)} cells OK")
    sys.exit(1 if n_err else 0)


if __name__ == "__main__":
    main()
