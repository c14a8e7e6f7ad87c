"""What every cell shares: finding a cell's files by name, building the
system under test from its configuration file, making its weights from the
seed, counting compiles, and printing.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``); its correctness limits are in
``limits/<cell>.json`` and each per-layer metric's reader in
``metrics/<metric>.py``.  Nothing here is specific to one of them.

A configuration file reaches the program and the reference whole.  Every
key that is not in ``METADATA`` goes to the program's ``ModelConfig``: to
the field that ``_FIELDS`` names where the published name differs, else to
the field of the same name; a key with no such field stops the run with
"program has no field for <key>", unless ``_FIXED`` gives the one value
the program runs and the file states it.  So a program that learns an
architecture adds its fields under the published names (``kv_lora_rank``,
``moe_intermediate_size``, ...), and a file that states them then runs
with no edit here.  The reference gets the same keys (``reference_config``)
and, as ``published_<key>``, each one that ``published`` restates.  A
layer cut to this chip's share of the experts is the one case where a
published value reaches the program (``program_config``).
"""

from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(tag: str, **fields) -> None:
    """An earlier line of a run's standard output (never the last one)."""
    print(f"[{tag}] " + json.dumps(fields, default=str), flush=True)


def spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(*parts) -> dict:
    with open(BENCH.joinpath(*parts)) as f:
        return json.load(f)


def cell(name: str) -> dict:
    """The cell's entry, its configuration, mix and limits, and the metric
    entries it reports."""
    s = spec()
    cells = {w["name"]: w for w in s["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in s["configs"] if c["name"] == w["config"])
    e2e = [m for m in s["end_to_end"] if name in m.get("workloads", [name])]
    layer = [m for m in s["per_layer"] if name in m.get("workloads", [name])]
    return {"workload": w, "config": load_json(os.path.relpath(
                ROOT / conf["file"], BENCH)),
            "config_entry": conf,
            "mix": load_json("traffic", f"{w['traffic']}.json"),
            "limits": load_json("limits", f"{name}.json"),
            "end_to_end": e2e, "per_layer": layer}


def metric_reader(name: str):
    """``metrics/<name>.py``'s ``read(obs)`` (metric names hold dots, so the
    file is loaded by path)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec_ = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(mod)
    return mod.read


def reference(name: str):
    return importlib.import_module(f"bench.references.{name}")


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------


@contextlib.contextmanager
def program_env(mix: dict):
    """The program's own switches that a mix fixes (``"program_env"``:
    variable -> value), set while the run lasts and restored after.  A mix
    pins a choice here that the program would otherwise make afresh in
    each checkout, such as a tuner verdict that flips on a close race."""
    pins = {k: str(v) for k, v in mix.get("program_env", {}).items()}
    saved = {k: os.environ.get(k) for k in pins}
    os.environ.update(pins)
    try:
        yield pins
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def use_checkout_program() -> None:
    """Import the system under test from this checkout's ``src`` only."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro
    where = Path(repro.__file__).resolve()
    if ROOT / "src" not in where.parents:
        raise SystemExit(f"repro imported from {where}, not from {src}")


# Keys of a configuration file that are no sizes of the model: its
# provenance and the program's own settings (placed apart), and keys that
# select nothing in the program.  Neither the program nor the reference
# gets them as sizes.
METADATA = frozenset({
    "name", "source", "arch", "reference", "mpo", "dtype", "published",
    "reduced", "assumed", "deployment",
    "max_position_embeddings",   # positions come from the traffic
    "model_type",                # the family label; ``arch`` selects it
    "ep_size",                   # the source's expert-parallel degree at
                                 # run time; the cut states experts held
})

# Keys the program has no field for because it runs one value only: a
# file that states that value passes, any other stops the run by name.
_FIXED = {"attention_bias": False, "num_nextn_predict_layers": 0,
          "moe_layer_freq": 1}

# published name -> program ModelConfig field, where the two differ (a
# key not listed goes to the field of its own name)
_FIELDS = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab_size",
           "num_hidden_layers": "num_layers", "rope_theta": "rope_theta",
           "qk_norm": "qk_norm", "hidden_act": "mlp_act",
           "tie_word_embeddings": "tie_embeddings",
           "num_experts": "num_experts", "n_routed_experts": "num_experts",
           "num_local_experts": "num_experts",
           "num_experts_per_tok": "top_k", "sliding_window": "local_window",
           "rms_norm_eps": "norm_eps"}


def expert_key(conf: dict) -> str | None:
    """The file's key that counts the routed experts, if it states one."""
    keys = [k for k in conf if _FIELDS.get(k) == "num_experts"]
    if len(keys) > 1:
        raise SystemExit(f"{' and '.join(keys)} both count the experts")
    return keys[0] if keys else None


def program_config(conf: dict):
    """The program's ``ModelConfig`` built from a configuration file: its
    registry entry (``arch``) with every key the file states that is not
    in ``METADATA``, each placed on the field ``_FIELDS`` names or on the
    field of its own name.  A key with no such field stops the run by
    name, so a program that learns an architecture adds its fields under
    the published names.  Two kinds of key are checked instead of placed
    where the program has no field: ``rms_norm_eps`` against the one
    epsilon it runs, and the keys of ``_FIXED`` against the one value it
    runs.

    Experts held: a file that cuts a layer to this chip's share counts the
    experts held here (listed in ``reduced``) and restates the model's
    count under ``published``.  The program gets the published count as
    ``num_experts`` (the router's width) and the file's as
    ``experts_held``; a program without that field stops the run whenever
    the two differ.  No other published value reaches the program."""
    import dataclasses

    from repro import configs
    base = configs.get_config(conf["arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    over = {}
    for key, value in conf.items():
        if key in METADATA:
            continue
        field = _FIELDS.get(key, key)
        if field in fields:
            over[field] = value
        elif field == "norm_eps":
            pass                             # checked below
        elif key not in _FIXED:
            raise SystemExit(f"program has no field for {key}")
        elif value != _FIXED[key]:
            raise SystemExit(f"program runs only {key} = {_FIXED[key]!r}, "
                             f"the file states {value!r}")
    ek = expert_key(conf)
    if ek is not None:
        held = conf[ek]
        over["num_experts"] = conf.get("published", {}).get(ek, held)
        if "experts_held" in fields:
            over["experts_held"] = held
        elif held != over["num_experts"]:
            raise SystemExit(
                f"program has no field for experts_held: the file holds "
                f"{held} of {over['num_experts']} experts")
    cfg = dataclasses.replace(
        base, **over, mpo=dataclasses.replace(base.mpo, **conf["mpo"]),
        dtype=conf["dtype"])
    if cfg.vocab_size != conf["vocab_size"]:
        raise SystemExit(f"program pads vocab {conf['vocab_size']} to "
                         f"{cfg.vocab_size}; state the padded size")
    if "rms_norm_eps" in conf and \
            program_norm_eps(cfg) != conf["rms_norm_eps"]:
        raise SystemExit(f"program RMSNorm epsilon {program_norm_eps(cfg)} "
                         f"is not the file's {conf['rms_norm_eps']}")
    return cfg


def program_norm_eps(cfg) -> float:
    """The RMSNorm epsilon the program runs: its configuration's
    ``norm_eps`` where it has one, else ``apply_rmsnorm``'s default (one
    epsilon for every model)."""
    import inspect

    from repro.models import nn
    default = inspect.signature(nn.apply_rmsnorm).parameters["eps"].default
    return getattr(cfg, "norm_eps", default)


def _hashable(value):
    """Lists as tuples and groups as sorted ``(key, value)`` tuples, so a
    configuration stays a static argument of a jitted reference."""
    if isinstance(value, (list, tuple)):
        return tuple(_hashable(v) for v in value)
    if isinstance(value, dict):
        return tuple(sorted((k, _hashable(v)) for k, v in value.items()))
    return value


def reference_config(conf: dict) -> dict:
    """What the plain reference reads: every key of the configuration file
    that is not in ``METADATA``, and ``published_<key>`` for each of them
    that ``published`` restates (a share cut's full count: the router's
    width, the whole vocabulary)."""
    out = {k: _hashable(v) for k, v in conf.items() if k not in METADATA}
    out.update({f"published_{k}": _hashable(v)
                for k, v in conf.get("published", {}).items() if k in out})
    return out


def id_vocab(conf: dict) -> int:
    """Token ids are drawn below this: the file's vocabulary (a slice's
    rows) or the published one where that is smaller (ids past it are
    the program's padding)."""
    return min(conf["vocab_size"],
               conf.get("published", {}).get("vocab_size",
                                             conf["vocab_size"]))


def make_weights(model, seed: int):
    """Random weights from the seed, on the device, in one jitted call, in
    the program's parameter layout (float32 MPO cores, unit norms).  Each
    matrix's cores get ``sigma = (var_W / prod(bonds)) ** (1 / 2n)`` so the
    matrix they contract to has entries of variance ``var_W`` (``1 / fan_in``;
    the embedding ``0.02 ** 2``).  Every other leaf named ``scale`` is ones;
    any other leaf of two or more dimensions (a router) is drawn at
    variance ``1 / fan_in``, ``fan_in`` its second-to-last dimension, and
    a one-dimensional one (a bias) is zero.  Those draws take a key stream
    of their own, so the MPO cores do not depend on them."""
    import jax
    import jax.numpy as jnp
    from repro.core import layers as L
    from bench.generator import seed_words
    shapes, _ = L.split_annotations(jax.eval_shape(model.init,
                                                   jax.random.PRNGKey(0)))

    def one_matrix(key, cores: dict, is_embed: bool):
        names = sorted(cores)
        lead = cores[names[0]].ndim - 4
        dims = [cores[n].shape[lead:] for n in names]
        n = len(names)
        fan_in = math.prod(d[1] for d in dims)
        bonds = math.prod(d[3] for d in dims if d[3] != 1) or 1
        var = 0.02 ** 2 if is_embed else 1.0 / fan_in
        sigma = (var / bonds) ** (1.0 / (2 * n))
        keys = jax.random.split(key, n)
        return {nm: sigma * jax.random.normal(k, cores[nm].shape, jnp.float32)
                for nm, k in zip(names, keys)}

    def one_leaf(key, leaf):
        if leaf.ndim < 2:
            return jnp.zeros(leaf.shape, leaf.dtype)
        sigma = leaf.shape[-2] ** -0.5
        return (sigma * jax.random.normal(key, leaf.shape, jnp.float32)
                ).astype(leaf.dtype)

    def build(key):
        counter, others = [0], [0]
        # the MPO matrices fold in 1, 2, ...; every other leaf folds its
        # own counter into the key's 0th fold, which no matrix takes
        other_key = jax.random.fold_in(key, 0)

        def walk(node, path):
            if isinstance(node, dict) and "cores" in node:
                counter[0] += 1
                k = jax.random.fold_in(key, counter[0])
                return {"cores": one_matrix(k, node["cores"],
                                            path == ("embed",))}
            if isinstance(node, dict):
                return {k: walk(v, path + (k,)) for k, v in node.items()}
            if path[-1] == "scale":
                return jnp.ones(node.shape, node.dtype)     # norm scales
            others[0] += 1
            return one_leaf(jax.random.fold_in(other_key, others[0]), node)
        return walk(shapes, ())

    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.jit(build)(key)


# --------------------------------------------------------------------------
# compiles, device
# --------------------------------------------------------------------------


class CompileMeter:
    """Compiles (backend compile events) and persistent-cache hits, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.hits = 0.0, 0, 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits}


class GcMeter:
    """The collector's passes while it is on (``start``/``stop``), to tell
    a host stall that the collector made from one of the machine."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t0 = None

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def start(self) -> None:
        gc.callbacks.append(self._on_gc)

    def stop(self) -> dict:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        full = [d for g, d in self.pauses if g == 2]
        return {"passes": len(self.pauses),
                "max_ms": 1e3 * max((d for _, d in self.pauses), default=0),
                "full_passes": len(full),
                "full_max_ms": 1e3 * max(full, default=0)}


def check_devices(chips: int):
    """The accelerator devices, or exit non-zero with no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devs[0].platform}); refusing to "
              "measure another backend", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


def memory_peak(devices) -> int:
    """Peak device memory of the fullest chip: the larger of the
    allocator's peak in use and its peak reserved for compiled programs'
    temporaries (a TPU reserves those apart from buffers in use)."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(max(st.get("peak_bytes_in_use", 0),
                         st.get("peak_bytes_reserved", 0)))
    return int(max(peaks))


def free_program() -> None:
    """Drop the compiled programs once the caller has let go of the
    program's state: the reference then has the chip in one piece
    (compiled programs split the free space)."""
    import gc

    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
    log("freed", live_array_bytes=sum(a.nbytes for a in jax.live_arrays()))


def now() -> float:
    return time.perf_counter()
