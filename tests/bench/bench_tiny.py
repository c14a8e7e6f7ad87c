"""Tiny sizes and planted faults for driving whole benchmark runs on the
CPU (the look for a chip skipped)."""

import contextlib

TINY_CONF = {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 2, "head_dim": 16,
             "intermediate_size": 128, "vocab_size": 512,
             "num_hidden_layers": 2, "published": {"vocab_size": 500},
             "mpo": {"n": 5, "bond_embed": 8, "bond_attn": 8, "bond_ffn": 8},
             "dtype": "float32"}

_POOL = {"slots": 4, "paged": True, "page_size": 16, "bucket_prompts": True,
         "prefill_chunk": 32}

TINY_MIX = {
    "qwen3-14b.chat-open": {
        "rate_rps": 10.0, "drain_s": 30,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 1.0,
                       "min": 8, "max": 96},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                       "min": 2, "max": 32},
        "pool": dict(_POOL, max_len=128), "check": {"requests": 4}},
    "qwen3-14b.decode-batch": {
        "clients": 4, "per_client": 4,
        "prompt_len": {"dist": "uniform", "min": 8, "max": 40},
        "output_len": {"dist": "uniform", "min": 4, "max": 20},
        "pool": dict(_POOL, max_len=64), "check": {"requests": 4}},
    "mistral-nemo-12b.lfa-finetune": {"batch": 2, "seq_len": 32},
}


# Cells whose files stay under bench/ but which BENCHMARK.json leaves out
# until they are proven on the chip; their loops and checks are still
# driven here.
LEFT_OUT = {
    "configs": [],
    "workloads": [
        {"name": "qwen3-14b.decode-batch", "config": "qwen3-14b",
         "traffic": "decode-batch", "chips": 1}],
}


def _spec_with_left_out(spec):
    out = dict(spec)
    for key, extra in LEFT_OUT.items():
        names = {e["name"] for e in spec[key]}
        out[key] = spec[key] + [e for e in extra if e["name"] not in names]
    return out


def run(cell, seed=2 ** 31 + 77, seconds=1.0, control=False,
        readings_out=None, conf=None, mix=None):
    """One whole run of ``cell`` on the CPU at tiny sizes: ``conf``
    overrides the configuration file's keys (default ``TINY_CONF``) and
    ``mix`` the traffic mix's (default ``TINY_MIX[cell]``), so a cell
    brings its own tiny sizes (expert counts, ranks) as arguments."""
    from unittest import mock

    from bench import harness, run as R
    conf = TINY_CONF if conf is None else conf
    mix = TINY_MIX[cell] if mix is None else mix
    spec = _spec_with_left_out(harness.spec())
    with mock.patch.object(harness, "spec", lambda: spec):
        # tiny bonds, with whatever else the configuration's mpo group
        # fixes (a pinned plan) kept
        mpo = dict(harness.cell(cell)["config"]["mpo"], **conf["mpo"])
        return R.run_cell(cell, seed, seconds, False, control=control,
                          check_chip=False,
                          conf_override=dict(conf, mpo=mpo),
                          mix_override=mix,
                          readings_out=readings_out)


# --------------------------------------------------------------------------
# planted faults: each wraps a program step where its result is produced
# --------------------------------------------------------------------------


@contextlib.contextmanager
def serve_fault(monkeypatch, kind):
    """``token_altered``: every decoded token is shifted by one id;
    ``state_unchanged``: the decode step hands back the cache it was given."""
    from repro.pipeline import scheduler
    real = scheduler.make_serve_steps

    def patched(*a, **kw):
        steps = real(*a, **kw)
        decode = steps.decode

        def broken(params, tokens, cache):
            tok, logits, new_cache = decode(params, tokens, cache)
            if kind == "token_altered":
                return (tok + 1) % logits.shape[-1], logits, new_cache
            return tok, logits, cache
        return steps._replace(decode=broken)

    monkeypatch.setattr(scheduler, "make_serve_steps", patched)
    yield


@contextlib.contextmanager
def train_fault(monkeypatch, kind):
    """``state_unchanged``: the step returns the state it was given;
    ``half_batch``: the step sees only the first half of the batch rows."""
    from repro.train import steps
    real = steps.make_train_step

    def patched(model, opt, loss_fn=None):
        step = real(model, opt, loss_fn)

        def broken(state, batch):
            if kind == "half_batch":
                half = batch["tokens"].shape[0] // 2
                return step(state, {k: v[:half] for k, v in batch.items()})
            _, metrics = step(state, batch)
            return state, metrics
        return broken

    monkeypatch.setattr(steps, "make_train_step", patched)
    yield
