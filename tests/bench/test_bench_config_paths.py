"""A configuration file reaches the program and the reference whole.

The two configurations already measured build, weigh, feed and count
exactly as they did when the harness read only the nine dense sizes (that
construction is kept here as the reference), and a file that states more
than a dense model either reaches the program or stops the run by name."""

import dataclasses
import importlib.util
import math

import numpy as np
import pytest
from bench_tiny import TINY_CONF, TINY_MIX

CONFIGS = ["qwen3-14b", "mistral-nemo-12b"]
SEED = 2 ** 31 + 4242


def _file(name):
    from bench import harness
    return harness.load_json("configs", f"{name}.json")


def _tiny(name):
    """The file as ``bench_tiny.run`` overrides it."""
    conf = _file(name)
    return dict(conf, **dict(TINY_CONF,
                             mpo=dict(conf["mpo"], **TINY_CONF["mpo"])))


def _conf(name, size):
    return _file(name) if size == "file" else _tiny(name)


# --------------------------------------------------------------------------
# the construction that read only the dense sizes
# --------------------------------------------------------------------------

_DENSE_FIELDS = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
                 "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
                 "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                 "num_hidden_layers": "num_layers", "rope_theta": "rope_theta",
                 "qk_norm": "qk_norm"}


def _dense_program_config(conf):
    from repro import configs
    base = configs.get_config(conf["arch"])
    return dataclasses.replace(
        base, **{f: conf[k] for k, f in _DENSE_FIELDS.items()},
        mpo=dataclasses.replace(base.mpo, **conf["mpo"]),
        dtype=conf["dtype"], tie_embeddings=conf["tie_word_embeddings"],
        mlp_act=conf["hidden_act"])


def _dense_make_weights(model, seed):
    import jax
    import jax.numpy as jnp
    from repro.core import layers as L
    from bench.generator import seed_words
    shapes, _ = L.split_annotations(jax.eval_shape(model.init,
                                                   jax.random.PRNGKey(0)))

    def one_matrix(key, cores, is_embed):
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

    def build(key):
        counter = [0]

        def walk(node, path):
            if isinstance(node, dict) and "cores" in node:
                counter[0] += 1
                k = jax.random.fold_in(key, counter[0])
                return {"cores": one_matrix(k, node["cores"],
                                            path == ("embed",))}
            if isinstance(node, dict):
                return {k: walk(v, path + (k,)) for k, v in node.items()}
            return jnp.ones(node.shape, node.dtype)
        return walk(shapes, ())

    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.jit(build)(key)


def _dense_counts(conf, pairs, positions):
    d, h, kv, dh, ff = (conf["hidden_size"], conf["num_attention_heads"],
                        conf["num_key_value_heads"], conf["head_dim"],
                        conf["intermediate_size"])
    per_layer = d * h * dh * 2 + d * kv * dh * 2 + 3 * d * ff
    layers = conf["num_hidden_layers"]
    return (per_layer * layers + d * conf["vocab_size"],
            4 * h * dh * pairs * layers,
            2 * kv * dh * 2 * positions * layers)


def _metric_module(name):
    from bench import harness
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        harness.BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------
# the existing configurations are unchanged
# --------------------------------------------------------------------------


@pytest.mark.parametrize("size", ["file", "tiny"])
@pytest.mark.parametrize("config", CONFIGS)
def test_program_config_is_the_dense_construction(config, size):
    from bench import harness
    conf = _conf(config, size)
    new, old = harness.program_config(conf), _dense_program_config(conf)
    for f in dataclasses.fields(old):
        assert getattr(new, f.name) == getattr(old, f.name), f.name


@pytest.mark.parametrize("config", CONFIGS)
def test_reference_config_keeps_the_six_dense_keys(config):
    from bench import harness
    conf = _file(config)
    rcfg = harness.reference_config(conf)
    for k in ("num_attention_heads", "num_key_value_heads", "head_dim",
              "rope_theta", "rms_norm_eps", "qk_norm"):
        assert rcfg[k] == conf[k] and type(rcfg[k]) is type(conf[k]), k
    hash(tuple(sorted(rcfg.items())))


@pytest.mark.parametrize("config", CONFIGS)
def test_weights_are_bit_identical_to_the_dense_draw(config):
    import jax
    from bench import harness
    from repro.models import model as M
    model = M.build(harness.program_config(_tiny(config)))
    new = jax.tree_util.tree_flatten_with_path(
        harness.make_weights(model, SEED))[0]
    old = jax.tree_util.tree_flatten_with_path(
        _dense_make_weights(model, SEED))[0]
    assert [p for p, _ in new] == [p for p, _ in old]
    for (path, a), (_, b) in zip(new, old):
        assert a.dtype == b.dtype and np.array_equal(a, b), path


@pytest.mark.parametrize("size", ["file", "tiny"])
@pytest.mark.parametrize("config", CONFIGS)
def test_feed_draws_the_same_first_rows(config, size):
    import jax
    from bench import harness
    from bench.loops import train
    conf = _conf(config, size)
    before = conf.get("published", {}).get("vocab_size", conf["vocab_size"])
    assert harness.id_vocab(conf) == before
    mix = dict(harness.load_json("traffic", "lfa-finetune.json"),
               **TINY_MIX["mistral-nemo-12b.lfa-finetune"])
    new = train.make_feed(mix, SEED, harness.id_vocab(conf))
    old = train.make_feed(mix, SEED, before)
    for i in range(2):
        assert np.array_equal(jax.device_get(new(i)["tokens"]),
                              jax.device_get(old(i)["tokens"]))


@pytest.mark.parametrize("config", CONFIGS)
def test_counts_are_the_dense_counts(config):
    from bench import counts
    conf = _file(config)
    weights, attn, kv = _dense_counts(conf, 12345, 678)
    assert counts.dense_weights(conf) == weights
    assert counts.attention_flops(conf, 12345) == attn
    assert counts.kv_bytes(conf, 678) == kv
    # a float context (decode's mean over steps) multiplies in the same
    # order as before
    assert counts.attention_flops(conf, 1234.5) == (
        4 * conf["num_attention_heads"] * conf["head_dim"] * 1234.5
        * conf["num_hidden_layers"])


def test_lfa_step_counts_stay_as_measured():
    """mistral-nemo-12b at 4 x 2048: 1.762e9 dense-equivalent weights and
    5.94e13 FLOPs a step under ``mfu.train``'s count."""
    from bench import counts
    conf = _file("mistral-nemo-12b")
    assert round(counts.dense_weights(conf) / 1e9, 3) == 1.762
    flops = _metric_module("mfu.train").work(conf, 4, 2048)
    assert round(flops / 1e13, 2) == 5.94


# --------------------------------------------------------------------------
# a file that states more than a dense model
# --------------------------------------------------------------------------

# a tiny file of the program's own MoE family: 4 experts, top-1 (its
# registry entry says 16 and 2)
MOE = dict(TINY_CONF, name="tiny-moe", arch="phi3.5-moe-42b-a6.6b",
           reference="dense", rope_theta=10000.0, rms_norm_eps=1e-6,
           qk_norm=False, hidden_act="silu", tie_word_embeddings=False,
           max_position_embeddings=4096, num_experts=4,
           num_experts_per_tok=1, hidden_size=128,
           mpo=dict(TINY_CONF["mpo"], shard_multiple=1))


def test_expert_count_and_top_k_reach_the_program():
    from bench import harness
    cfg = harness.program_config(MOE)
    assert (cfg.num_experts, cfg.top_k, cfg.d_model) == (4, 1, 128)
    cfg = harness.program_config(
        {k: v for k, v in MOE.items() if k != "num_experts"}
        | {"num_local_experts": 8})
    assert cfg.num_experts == 8


def test_two_keys_that_count_the_experts_stop_the_run():
    from bench import harness
    with pytest.raises(SystemExit, match="n_routed_experts"):
        harness.program_config(dict(MOE, n_routed_experts=4))


@pytest.fixture(scope="module")
def moe_weights():
    from bench import harness
    from repro.models import model as M
    return harness.make_weights(M.build(harness.program_config(MOE)), SEED)


def test_router_weights_come_from_the_seed(moe_weights):
    w = np.asarray(moe_weights["layers"]["moe"]["router"]["w"])
    layers, d, experts = w.shape
    assert (layers, d, experts) == (2, 128, 4)
    assert abs(w.var() * d - 1.0) < 0.2, w.var() * d
    assert not np.array_equal(w[0], w[1])
    # each token's scores differ across the experts, so top-k is no tie
    x = np.random.default_rng(0).standard_normal((16, d))
    assert (np.ptp(x @ w[0], axis=-1) > 0).all()


def test_norm_scales_stay_ones(moe_weights):
    scales = [np.asarray(moe_weights["layers"][k]["scale"])
              for k in ("ln1", "ln2")]
    scales.append(np.asarray(moe_weights["final_norm"]["scale"]))
    assert all((s == 1.0).all() for s in scales)


def test_a_vector_that_is_no_scale_starts_at_zero():
    import jax.numpy as jnp
    from bench import harness
    from repro.core.layers import Annot

    class Model:
        @staticmethod
        def init(key):
            return {"norm": {"scale": Annot(jnp.zeros((8,)), (None,))},
                    "proj": {"bias": Annot(jnp.ones((8,)), (None,)),
                             "w": Annot(jnp.zeros((3, 64, 8)),
                                        (None, None, None))}}

    p = harness.make_weights(Model, SEED)
    assert (np.asarray(p["norm"]["scale"]) == 1).all()
    assert (np.asarray(p["proj"]["bias"]) == 0).all()
    assert abs(np.asarray(p["proj"]["w"]).var() * 64 - 1.0) < 0.2


@pytest.mark.parametrize("key,value", [("kv_lora_rank", 512),
                                       ("moe_intermediate_size", 1408)])
def test_a_key_the_program_lacks_stops_the_run_by_name(key, value):
    from bench import harness
    for conf in (MOE, _file("qwen3-14b")):
        with pytest.raises(SystemExit,
                           match=f"program has no field for {key}"):
            harness.program_config(dict(conf, **{key: value}))


def test_a_key_the_program_runs_at_one_value_is_checked():
    from bench import harness
    cfg = harness.program_config(dict(MOE, attention_bias=False,
                                      num_nextn_predict_layers=0))
    assert cfg == harness.program_config(MOE)
    with pytest.raises(SystemExit, match="attention_bias"):
        harness.program_config(dict(MOE, attention_bias=True))


def test_fewer_experts_held_than_published_needs_experts_held():
    from bench import harness
    conf = dict(MOE, num_experts=2,
                published={"vocab_size": 500, "num_experts": 4})
    with pytest.raises(SystemExit, match="experts_held"):
        harness.program_config(conf)
    # held as published: the program's router and experts agree
    cfg = harness.program_config(dict(conf, num_experts=4))
    assert cfg.num_experts == 4


def test_reference_config_carries_the_published_counts():
    from bench import harness
    conf = dict(MOE, layer_types=["full", "sliding"],
                published={"vocab_size": 500, "num_experts": 16,
                           "num_hidden_layers": 32})
    rcfg = harness.reference_config(conf)
    assert rcfg["num_experts"] == 4 and rcfg["published_num_experts"] == 16
    assert rcfg["published_num_hidden_layers"] == 32
    assert rcfg["published_vocab_size"] == 500
    assert rcfg["layer_types"] == ("full", "sliding")
    assert not set(rcfg) & harness.METADATA
    hash(tuple(sorted(rcfg.items())))


def test_a_sliced_vocabulary_draws_ids_from_the_slice():
    import jax
    from bench import generator, harness
    from bench.loops import train
    conf = dict(MOE, vocab_size=256,
                published={"vocab_size": 2048})
    vocab = harness.id_vocab(conf)
    assert vocab == 256
    mix = dict(harness.load_json("traffic", "lfa-finetune.json"),
               batch=4, seq_len=256)
    rows = jax.device_get(train.make_feed(mix, SEED, vocab)(0)["tokens"])
    assert rows.max() < 256 and rows.max() > 200
    chat = harness.load_json("traffic", "chat-open.json")
    reqs = generator.open_loop(chat, SEED, 5.0, vocab)
    assert max(int(r.prompt.max()) for r in reqs) < 256


# Moonlight-16B-A3B (hf:moonshotai/Moonlight-16B-A3B) at the one-chip cut:
# each layer over 8 chips, so 8 of its 64 experts and 20480 of its 163840
# vocabulary rows are held here; one dense layer and four expert layers
MOONLIGHT = {
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_attention_heads": 16,
    "num_key_value_heads": 16, "kv_lora_rank": 512, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "n_routed_experts": 8, "num_experts_per_tok": 6, "n_shared_experts": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 5,
    "vocab_size": 20480, "dtype": "bfloat16",
    "published": {"n_routed_experts": 64, "num_hidden_layers": 27,
                  "vocab_size": 163840}}


def test_moonlight_one_chip_counts_by_hand():
    from bench import counts
    d = 2048
    attention = (d * 16 * (128 + 64)          # q, no q_lora
                 + d * (512 + 64)             # kv_a: latent and rope key
                 + 512 * 16 * (128 + 128)     # kv_b
                 + 16 * 128 * d)              # o
    assert attention == counts.attention_weights(MOONLIGHT) == 13_762_560
    dense = attention + 3 * d * 11264
    expert = 3 * d * 1408
    assert expert == 8_650_752
    share = attention + (0.75 + 2) * expert + d * 64
    assert counts.layer_weights(MOONLIGHT, 0) == dense == 82_968_576
    for i in range(1, 5):
        assert counts.layer_weights(MOONLIGHT, i) == share == 37_683_200
    assert counts.head_weights(MOONLIGHT) == 41_943_040
    assert counts.dense_weights(MOONLIGHT) == (82_968_576 + 4 * 37_683_200
                                               + 41_943_040)
    assert counts.attention_flops(MOONLIGHT, 1) == 2 * 16 * 320 * 5
