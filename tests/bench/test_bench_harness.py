"""The harness builds the program from a configuration file only where the
program runs what the file states."""

import pytest


@pytest.mark.parametrize("config", ["qwen3-14b", "mistral-nemo-12b"])
def test_program_runs_the_norm_epsilon_the_file_states(config):
    from bench import harness
    conf = harness.load_json("configs", f"{config}.json")
    cfg = harness.program_config(conf)
    assert harness.program_norm_eps(cfg) == conf["rms_norm_eps"]
    with pytest.raises(SystemExit, match="epsilon"):
        harness.program_config(dict(conf, rms_norm_eps=0.5))
