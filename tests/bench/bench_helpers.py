"""A cell run at ``configs.base.reduced``-like sizes on the CPU, through
the same functions as a chip run."""

import copy

#: widths of ``configs.base.reduced``; two layers
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
        "vocab_size": 256}
TINY_TRAFFIC = {
    "train": {"seq_len": 64},
    "serve": {"rate_per_s": 8.0,
              "prompt_len": {"median": 16, "sigma": 0.75, "min": 4,
                             "max": 40},
              "output_len": {"median": 8, "sigma": 0.75, "min": 2,
                             "max": 16}},
}


def tiny_run(cell, seed=2**31 + 11, seconds=1.0, chips=None, batch=None):
    """A ``core.Run`` of ``cell`` at tiny sizes, on the CPU."""
    from bench import core
    c = core.load_cell(cell)
    w = copy.deepcopy(c["workload"])
    cfg = dict(c["config"], **TINY)
    traffic = dict(c["traffic"], **TINY_TRAFFIC[w["kind"]])
    if chips:
        w["chips"] = chips
    if w["kind"] == "serve":
        w["serve"]["slots"] = 4
    if batch:
        w["train"]["per_chip_batch"] = batch
    run = core.Run(cell=cell, workload=w, config=cfg, traffic=traffic,
                   seed=seed, seconds=seconds, trace=False, chips=w["chips"])
    run.device = {"platform": "cpu", "kind": "TPU v5 lite",
                  "count": w["chips"]}
    run.peaks = core.load_peaks("TPU v5 lite")
    return run


def run_cell(run):
    from bench import core
    core.load_module("kinds", run.workload["kind"]).run_cell(run)
    return run
