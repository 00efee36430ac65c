"""Smoke-size cells for the CPU tests: a checkout root of their own, with
a ``BENCHMARK.json``, the cell files, and links to the benchmark's
drivers and per-layer metric readers."""
from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LM = {
    "name": "lm-smoke", "source": "test", "reduced": [],
    "head_dim": 32, "hidden_size": 128, "intermediate_size": 256,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": True, "torch_dtype": "float32", "vocab_size": 512,
}
CNN = {
    "name": "cnn-smoke", "source": "test", "reduced": [], "task": "fmnist_cnn",
    "model": {"image_side": 28, "image_channels": 1, "kernel": 2,
              "channels": 32, "fc_width": 128, "n_classes": 10},
    "protocol": {"method": "teasq", "p_s": 0.25, "p_q": 8, "channel_iters": 12,
                 "alpha": 0.6, "a": 0.5, "mu": 0.01, "epochs": 2,
                 "batch_size": 40, "lr": 0.08},
}
MIXES = {
    "fleet-smoke": {"kind": "fl_fleet", "n_devices": 8, "partition": "iid",
                    "n_train": 640, "n_test": 200, "c_fraction": 0.25,
                    "gamma": 0.25},
    "chat-smoke": {"kind": "serve_open_loop",
                   "arrivals": {"process": "poisson", "rate": 20.0},
                   "prompt": {"dist": "choice", "values": [8, 16]},
                   "answer": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                              "clip": [4, 16]}},
}
CELLS = {
    "fl-smoke": {"driver": "fl", "cohort_size": 1, "scheduler": "heap",
                 "handler_mode": "serial", "server": "single",
                 "eval_every": 10, "slice_aggregations": 4,
                 "warmup_aggregations": 2,
                 "limits": {"round_mismatch": 0.01, "agg_rel_err": 1e-5}},
    "serve-smoke": {"driver": "serve", "slots": 4, "cache_len": 32,
                    "sample_tokens": 96, "sample_max": 12,
                    "limits": {"logit_gap": 1e-3, "mean_gap": 1e-4}},
}
BENCH = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"],
    "run_seconds": 2,
    "configs": [
        {"name": "cnn-smoke", "source": "test", "file": "bench/configs/cnn-smoke.json",
         "reduced": [], "why": "test"},
        {"name": "lm-smoke", "source": "test", "file": "bench/configs/lm-smoke.json",
         "reduced": [], "why": "test"}],
    "workloads": [
        {"name": "fl-smoke", "config": "cnn-smoke", "traffic": "fleet-smoke",
         "chips": 1, "why": "test"},
        {"name": "serve-smoke", "config": "lm-smoke", "traffic": "chat-smoke",
         "chips": 1, "why": "test"}],
    "end_to_end": [
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"},
        {"name": "fl_updates_per_s", "unit": "updates/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": ["fl-smoke"]},
        {"name": "ttft_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["serve-smoke"]},
        {"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": ["serve-smoke"]},
        {"name": "serve_tokens_per_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.05, "source": "host_clock", "workloads": ["serve-smoke"]}],
    "per_layer": [],
}


def write_root(root: str, bench=BENCH, cells=CELLS, configs=(CNN, LM),
               mixes=MIXES) -> str:
    """Write a checkout root holding ``BENCHMARK.json`` and the smoke
    cells' files under ``bench/``; returns ``root``."""
    def put(rel, obj):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(obj, f)
    put("BENCHMARK.json", bench)
    for c in configs:
        put(f"bench/configs/{c['name']}.json", c)
    for name, m in mixes.items():
        put(f"bench/traffic/mixes/{name}.json", m)
    for name, c in cells.items():
        put(f"bench/workloads/{name}.json", c)
    for sub in ("drivers", "metrics"):
        os.makedirs(os.path.join(root, "bench", sub), exist_ok=True)
        for f in os.listdir(os.path.join(BENCH_DIR, sub)):
            if f.endswith(".py"):
                os.symlink(os.path.join(BENCH_DIR, sub, f),
                           os.path.join(root, "bench", sub, f))
    return root
