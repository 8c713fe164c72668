"""A temporary copy of the benchmark with a tiny configuration added by files
and entries alone — what a later PR does, and what the CPU rehearsals run."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = dict(
    source="none: a test size", architecture="dense_lm", hidden_size=256, num_attention_heads=2,
    num_key_value_heads=1, intermediate_size=512, vocab_size=512, num_hidden_layers=2,
    max_position_embeddings=512, rope_theta=1e4, rms_norm_eps=1e-5,
    training=dict(compute_dtype="bfloat16", param_dtype="float32", optimizer="adamw", learning_rate=3e-4),
    program=dict(remat=False, scan_unroll=2), correct=dict(grad_rel_limit=0.03),
)

READER = '''"""Added by the test: the last loss of the window."""
LAYER = "model"
UNIT = "nats"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def read(ctx):
    return ctx["steps"][-1]["loss"] if ctx["steps"] else None
'''


def make_copy(tmp: str, groups: int = 1) -> str:
    """Copies `benchmark/` and `BENCHMARK.json` into `tmp` and adds, with no
    edit to any copied file: a configuration, a traffic mix, a cell and a
    per-layer metric.  Returns the copy's root."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), os.path.join(tmp, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__", ".pytest_cache"))
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        doc = json.load(f)
    bench = os.path.join(tmp, "benchmark")
    with open(os.path.join(bench, "configs", "tiny.json"), "w", encoding="utf-8") as f:
        json.dump(TINY, f)
    with open(os.path.join(bench, "traffic", f"steady-{'4g' if groups > 1 else '1g'}.json"), encoding="utf-8") as f:
        traffic = json.load(f)
    traffic.update(groups=groups, seq_len=256, sequences_per_step=2)
    if groups > 1:
        # At this size the speculative update fits beside the state, so `TrainStep`
        # switches to it after its first step: one more program to warm.
        traffic.update(warmup_steps=2)
    with open(os.path.join(bench, "traffic", "tiny-steady.json"), "w", encoding="utf-8") as f:
        json.dump(traffic, f)
    cell = "tiny.tiny-steady"
    doc["configs"].append(dict(name="tiny", source="none", file="benchmark/configs/tiny.json", reduced=[], why="test"))
    doc["workloads"].append(dict(name=cell, config="tiny", traffic="tiny-steady", chips=groups, why="test"))
    rate = "tokens_per_s.4g" if groups > 1 else "tokens_per_s"
    with open(os.path.join(bench, "layer_metrics", "last_loss.tiny.py"), "w", encoding="utf-8") as f:
        f.write(READER.replace('"tokens_per_s"', json.dumps(rate)))
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in metric and rate in (metric["name"], metric.get("moves")):
            metric["workloads"].append(cell)
    doc["per_layer"].append(dict(name="last_loss.tiny", unit="nats", better="lower", source="program_counter",
                                 layer="model", moves=rate, workloads=[cell]))
    with open(os.path.join(tmp, "BENCHMARK.json"), "w", encoding="utf-8") as f:
        json.dump(doc, f)
    return tmp
