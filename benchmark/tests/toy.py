"""Toy-size configurations and mixes for the CPU tests: the same files'
shapes, tiny numbers."""
import copy
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


def load(kind, name):
    with open(os.path.join(BENCH_DIR, kind, f"{name}.json")) as f:
        return json.load(f)


def bert_toy():
    cfg = load("configs", "bert_base_mlm")
    cfg.update(vocab_size=1024, hidden_size=64, num_hidden_layers=2,
               num_attention_heads=2, intermediate_size=128,
               max_position_embeddings=128)
    cfg["train"]["optimizer"]["kwargs"]["learning_rate"] = 1e-2
    return cfg


def pretrain_toy(mesh=None):
    mix = load("traffic", "pretrain_s128_dp4" if mesh else "pretrain_s128")
    mix.update(per_chip_batch=4, seq_len=16, steps_per_epoch=4,
               warmup_steps=3, trace_seconds=0.2)
    return mix


def gpt_toy():
    cfg = load("configs", "gpt2_xl")
    cfg.update(vocab_size=1024, n_embd=64, n_layer=2, n_head=2, n_inner=128,
               n_positions=128, dtype="float32")
    cfg["serve"] = {"max_active": 4, "kv_blocks": 48, "block_size": 16,
                    "max_seq_len": 128, "temperature": 0.0}
    cfg["forced_check"] = {"prompt_lens": [5, 14], "bucket": 16, "tol": 0.05}
    return cfg


def serve_mix_toy(name, rate, new=(2, 6)):
    mix = copy.deepcopy(load("traffic", name))
    mix["arrival"]["rate"] = rate
    ten = mix["tenants"][0]
    ten["prompt"].update(median=12, lo=5, hi=30)
    ten["new"] = {"kind": "uniform", "lo": new[0], "hi": new[1]}
    if "seed_burst" in mix:
        mix["seed_burst"]["count"] = 4
    mix.update(lead_in_s=0.5, deadline_s=min(mix["deadline_s"], 10.0),
               sample_every_s=0.05, trace_seconds=0.3)
    return mix


def cell(name, config, traffic, chips=1, seconds=1.5, seed=0,
         trace_dir=None):
    """run.py's Cell around toy data (the real cells' names, so that
    BENCHMARK.json says which metrics each reports)."""
    from benchmark.run import Cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    c = Cell(bench, name, chips, config, traffic, seed, seconds,
             trace_dir is not None, trace_dir)
    c.t_process_start = time.perf_counter()
    return c
