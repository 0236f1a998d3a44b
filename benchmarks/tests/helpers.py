"""Shared by the benchmark's tests: a throw-away copy of the benchmark
with two more configurations (a small conv net on the shipped reference;
a small language model with a reference module of its own), their mixes
(one with a generator of its own), a metric and their cells dropped in
as files."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TINY_CONFIG = {
    "name": "tiny",
    "source": "none: a throw-away net holding every layer type the "
              "reference knows",
    "net_conf": "benchmarks/configs/tiny.conf",
    "args": {"batch_size": 8, "num_class": 10, "input_size": 16,
             "compute_dtype": "float32"},
    "reduced": [],
    "limits": {"loss_gap": 1e-4, "update_norm_gap": 1e-3,
               "dparam_norm_gap": 1e-3, "feed_gap_levels": 6.0},
}

TINY_MIX = {
    "name": "tiny_synth", "dev": "cpu", "batch_scale": 1,
    "chunks_per_round": 3,
    "conf": ["data = train", "iter = synthetic", "  nsample = {nsample}",
             "  input_shape = {input_shape}", "  nclass = {num_class}",
             "  label_width = 1", "  seed_data = {seed}", "iter = end"],
}

# another family: the program's byte-level transformer under adam at toy
# size, on layer types lib/netconf.py does not know, with its reference
# (data/tiny_lm_reference.py) and its feed (data/tiny_text_generator.py)
TINY_LM_CONFIG = {
    "name": "tiny_lm",
    "source": "none: a throw-away two-block token model",
    "builder": "cxxnet_tpu.models.transformer_lm_conf",
    "reference": "benchmarks/references/tiny_lm_reference.py",
    "args": {"batch_size": 8, "vocab": 256, "seq_len": 16, "dim": 32,
             "nhead": 4, "nlayer": 2, "compute_dtype": "float32"},
    "reduced": [],
    # the copy's tools/limits.py --cpu-toy on 3 seeds: sound largest
    # 1.6e-7 / 4.3e-6 / 4.8e-6, bfloat16 control smallest 2.0e-4 / 2.5e-3
    # / 0.19 (CPU, PR 27); a fed token is the file's or it is not
    "limits": {"loss_gap": 1e-5, "update_norm_gap": 1e-4,
               "dparam_norm_gap": 1e-3, "feed_gap_levels": 0.0},
}

TINY_TEXT_MIX = {
    "name": "tiny_text", "dev": "cpu", "batch_scale": 1,
    "chunks_per_round": 3,
    "generator": "benchmarks/traffic/tiny_text_generator.py",
    "conf": ["data = train", "iter = text", "  filename = {text_file}",
             "  seq_len = {seq_len}", "  shuffle = 1",
             "  seed_data = {seed}", "iter = end"],
}

TINY_METRIC = '''"""Chunks in the window: a throw-away metric."""
LAYER = "round loop"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "train_samples_s_chip"


def read(run):
    return float(run["window"]["chunks"])
'''


def copy_with_dropins(tmp: str) -> str:
    """Copy the benchmark into ``tmp`` and ADD files only: nothing that
    is there is edited, except BENCHMARK.json's lists, which grow."""
    dst = os.path.join(tmp, "benchmarks")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(HERE, "data", "tiny.conf"),
                os.path.join(dst, "configs", "tiny.conf"))
    with open(os.path.join(dst, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(dst, "traffic", "tiny_synth.json"), "w") as f:
        json.dump(TINY_MIX, f)
    with open(os.path.join(dst, "metrics", "chunks_in_window.py"), "w") as f:
        f.write(TINY_METRIC)
    shutil.copy(os.path.join(HERE, "data", "tiny_lm_reference.py"),
                os.path.join(dst, "references", "tiny_lm_reference.py"))
    shutil.copy(os.path.join(HERE, "data", "tiny_text_generator.py"),
                os.path.join(dst, "traffic", "tiny_text_generator.py"))
    with open(os.path.join(dst, "configs", "tiny_lm.json"), "w") as f:
        json.dump(TINY_LM_CONFIG, f)
    with open(os.path.join(dst, "traffic", "tiny_text.json"), "w") as f:
        json.dump(TINY_TEXT_MIX, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny", "source": "none",
                             "file": "benchmarks/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_cell", "config": "tiny",
                               "traffic": "tiny_synth", "chips": 1,
                               "why": "test"})
    # the shipped image mix under the throw-away net: no accepted cell
    # runs it yet, so this is where its generator and feed check run
    bench["workloads"].append({"name": "tiny_jpeg_cell", "config": "tiny",
                               "traffic": "train_jpeg", "chips": 1,
                               "why": "test"})
    bench["configs"].append({"name": "tiny_lm", "source": "none",
                             "file": "benchmarks/configs/tiny_lm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny_lm_cell", "config": "tiny_lm",
                               "traffic": "tiny_text", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({
        "name": "chunks_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "round loop",
        "moves": "train_samples_s_chip",
        "workloads": ["tiny_cell", "tiny_lm_cell"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


CHILD = """
import json, sys
sys.path.insert(0, {root!r})
if {frozen}:
    # the timed path broken underneath: the scanned step runs and hands
    # back its losses, but the weights and the momentum stay as they were
    import jax
    from cxxnet_tpu.nnet.trainer import NetTrainer
    inner = NetTrainer.update_scan
    def update_scan(self, data, labels, *args, **kw):
        keep = jax.tree_util.tree_map(lambda x: x.copy(),
                                      (self.params, self.ustates))
        out = inner(self, data, labels, *args, **kw)
        self.params, self.ustates = keep
        return out
    NetTrainer.update_scan = update_scan
import importlib.util
spec = importlib.util.spec_from_file_location("bench_run_copy", {run!r})
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
res = mod.run_cell(mod.parse_args({argv!r}))
print("RESULT " + json.dumps(res))
"""


def run_cell_in_child(bench_dir: str, argv, frozen: bool = False) -> dict:
    """Drive the copy's ``run_cell`` in a process of its own: a CLI run
    leaves metrics in the program's process-wide registry that other
    test files of the same pytest worker would then read."""
    code = CHILD.format(root=ROOT, frozen=frozen, argv=list(argv),
                        run=os.path.join(bench_dir, "run.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    lines = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")]
    if out.returncode != 0 or not lines:
        raise AssertionError(out.stdout[-2000:] + out.stderr[-2000:])
    return json.loads(lines[-1][len("RESULT "):])
