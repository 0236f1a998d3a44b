"""The configuration of ISSUE 42 (``trinity_mini``), its cell, mix,
reference and metric readers, on the CPU: the file against the catalog
row key by key, ``reduced`` and ``assumed`` complete, the cell the one
the issue names, picked up by files alone and rehearsed ``correct``, the
float8 control failing a limit, each new reader on a fixture record, the
counting functions against a hand count."""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from benchmarks.lib import scopes, stage_scopes  # noqa: E402
from benchmarks.tests import helpers  # noqa: E402

CELL = "trinity_mini_train_packed16k"
CONFIG = "trinity_mini"
MIX = "train_packed16k"
NEW_METRICS = ["attn_window_core_ms_step", "attn_full_core_ms_step",
               "attn_window_pairs_pct", "attn_core_roofline_pct"]
SHARED = ["device_step_ms", "compiles_in_window", "device_mfu_pct",
          "device_idle_pct", "peak_hbm_gib", "xla_compile_s",
          "jax_trace_lower_s", "iter_init_s", "loop_next_ms_step",
          "loop_copy_ms_step", "loop_stack_ms_step", "loop_self_ms_step",
          "h2d_enqueue_ms_step", "scan_dispatch_ms_step",
          "device_wait_ms_step", "chunk_recycled_pct", "chunk_overlap_pct",
          "loop_device_step_ms", "loop_device_idle_pct", "round_head_ms_step",
          "h2d_tail_ms_step", "chunk_starved_pct", "attention_ms_step",
          "mlp_ms_step", "head_loss_ms_step", "adam_update_ms_step",
          "tokens_per_step", "packed_docs_per_seq", "moe_ms_step",
          "moe_route_dispatch_ms_step", "expert_matmul_ms_step",
          "expert_pairs_per_expert", "expert_load_max_over_mean",
          "expert_pairs_dropped", "expert_dispatch_compact_pct",
          "attn_flash_pct"]
#: the catalog row's ``config``
#: (/opt/skills/guides/model-configs/architectures.jsonl, Trinity-Mini)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 8,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200192}


@pytest.fixture(scope="module")
def config():
    return run.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


@pytest.fixture(scope="module")
def ref(config):
    return run.load_reference(config)


@pytest.fixture(scope="module")
def toy(config, ref):
    """(conf text, the reference's reading of it) at rehearsal size."""
    args = dict(config["args"], **config["rehearsal_args"])
    text = run.net_text(config, args, "cpu")
    return text, ref.describe(text, int(args["batch_size"]))


# ----------------------------------------------------------------------
def test_the_configuration_keeps_every_published_number(config):
    """Every key of the catalog's ``config`` under its own name; only
    what ``reduced`` lists differs, and no width is among it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Trinity-Mini")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 25024)
    assert config["published"] == {k: PUBLISHED[k] for k in differs}
    assert sorted(config["reduced_why"]) == differs
    assert 8 * config["vocab_size"] == PUBLISHED["vocab_size"]
    assert 16 * config["num_experts"] == PUBLISHED["num_experts"]
    # no width among the cuts
    for key in differs:
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size"
    # the kept layers are layer 0 and one whole period in its published
    # order, and the builder's string says the same
    kept = config["kept_layers"]
    assert kept == [0, 4, 5, 6, 7]
    a = config["args"]
    assert "".join(PUBLISHED["layer_types"][i][0] for i in kept) == \
        a["layer_types"] == "ssssf"
    assert len(a["layer_types"]) == config["num_hidden_layers"]
    assert a["num_dense_layers"] == config["num_dense_layers"]
    assert len(a["layer_types"]) - a["num_dense_layers"] >= 4
    assert (a["hidden"], a["vocab"], a["mlp_hidden"], a["num_experts"],
            a["experts_held"], a["experts_per_tok"], a["expert_hidden"],
            a["shared_hidden"], a["route_scale"]) == (
        2048, 25024, 6144, 128, 8, 8, 1024, 1024, 2.826)
    assert (a["attn_heads"], a["attn_kv_heads"], a["head_dim"],
            a["sliding_window"], a["rope_theta"], a["eps"],
            a["mup_enabled"]) == (32, 4, 128, 2048, 10000.0, 1e-5, 1)
    assert (a["seq_len"], a["batch_size"], a["scan_steps"],
            a["compute_dtype"], a["eta"]) == (16384, 1, 8, "bfloat16", 3e-4)
    # the four items the modeling code gave, with their provenance
    assumed = config["assumed"]
    for key in ("sandwich_norms", "output_gate", "qk_norm",
                "rotary_on_sliding_layers_only"):
        assert assumed[key].startswith("MODELING CODE")
    assert "modeling_afmoe.py" in assumed["provenance"]
    for key in ("window_edge", "score_bias", "router_gradient", "init",
                "documents", "optimizer", "norm", "mup", "group_limit"):
        assert assumed[key]
    assert "rank 0" in config["deployment"] and "FOLDED" in \
        config["deployment"]
    assert "THROUGH THE POST NORM AS IT IS" in config["deployment"]
    assert "16 chips share each layer" in config["deployment"]
    text = run.net_text(config, dict(a), "tpu")
    assert text.count("= attention:") == 5
    assert text.count("  window = 2048\n") == 4
    assert text.count("= routed_experts:") == 4
    assert text.count("= gated_mlp:") == 1 and "mlp0" in text
    assert text.count("postnorm = 1") == 10


def test_the_memory_rule_chose_eight_held_experts(config):
    """ISSUE 42: 16 held unless the compiled step is live above 14.4 GB;
    it was (14.97), so 8 are held and the row stays 16384."""
    mem = config["memory_analysis_v5e"]
    live = lambda m: (m["argument_size_in_bytes"]  # noqa: E731
                      + m["output_size_in_bytes"] - m["alias_size_in_bytes"]
                      + m["temp_size_in_bytes"])
    assert live(mem["held16"]) > 14.4e9 >= live(mem["b1_t16384_scan8"])
    assert live(mem["b1_t16384_scan8"]) == 12_354_895_360
    assert config["args"]["experts_held"] == 8
    assert "14.97" in config["reduced_why"]["num_experts"]


def test_the_cell_is_the_one_the_issue_names():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and "1024 pairs" in cell["why"]
    assert "host share" in cell["why"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 6 and len(bench["configs"]) >= 7
    entry = bench["configs"][6]
    assert entry["name"] == CONFIG and entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    config = run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] and "arcee-ai" in \
        entry["source"]
    assert entry["reduced"] == config["reduced"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW_METRICS[0])
    assert listed[at:at + 4] == NEW_METRICS and at == 57
    for name in NEW_METRICS:
        assert by_name[name]["workloads"][0] == CELL
        assert by_name[name]["moves"] == "train_samples_s_chip"
        mod = run.load_metric(name)
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            by_name[name]["unit"], by_name[name]["source"],
            by_name[name]["layer"], by_name[name]["moves"])
    for name in SHARED:
        assert by_name[name]["workloads"].index(CELL) == len(
            [w for w in by_name[name]["workloads"]
             if names.index(w) < 6]), name
    # no third twin: expert_matmul_roofline_pct's reader names
    # qwen3_next's reference, which cannot read this conf
    for name in ("expert_matmul_roofline_pct", "ssd_scan_ms_step",
                 "gdn_scan_ms_step", "mla_core_ms_step", "mtp_ms_step",
                 "train_metric_ms_step", "dispatch_gap_ms_step",
                 "latent_expert_matmul_roofline_pct"):
        assert CELL not in by_name[name]["workloads"]
    assert sum(CELL in m.get("workloads", []) for m in listed_before(
        bench, at + 4)) == len(SHARED) + len(NEW_METRICS)


def listed_before(bench, n):
    """The per-layer metrics this PR left (a later one appends)."""
    return bench["per_layer"][:n]


def test_the_mix_is_the_generator_that_is_there_with_long_documents():
    mix = run.load_json(os.path.join(BENCH, "traffic", MIX + ".json"))
    old = run.load_json(os.path.join(BENCH, "traffic", "train_packed8k.json"))
    assert mix["generator"] == old["generator"] == \
        "benchmarks/traffic/packed_tokens.py"
    assert mix["documents"] == {"median": 4096, "sigma": 1.2, "min": 16}
    assert mix["chunks_per_round"] == 3 and mix["dev"] == "tpu"
    assert "  attn_window = {sliding_window}" in mix["conf"]
    assert [l for l in mix["conf"] if "attn_window" not in l] == old["conf"]
    assert mix["rehearsal"]["dev"] == "cpu"
    # what the cell's why says of the documents
    gen = run.load_generator(mix)
    raw = gen.stream(64 * 16384, 16384, 25024, mix["documents"], 7)
    ends = np.flatnonzero(raw == 0)
    lens = np.diff(ends, prepend=-1)
    assert 0.6 < (lens > 2048).mean() < 0.85
    assert lens[lens > 2048].sum() / lens.sum() > 0.9
    assert 0.05 < (lens == 16384).mean() < 0.25
    assert raw.max() < 25024 and raw.dtype == np.dtype("<u2")


# ----------------------------------------------------------------------
# picked up by files alone: the cell as BENCHMARK.json has it, rehearsed
@pytest.fixture(scope="module")
def rehearsal():
    res = helpers.run_cell_in_child(
        BENCH, ["--workload", CELL, "--seed", "4100000642", "--seconds", "6",
                "--trace", "1", "--cpu-rehearsal"])
    out = os.path.join(ROOT, "bench_out", CELL, "seed4100000642_trace1")
    with open(os.path.join(out, "compare.json")) as f:
        return res, json.load(f), out


def test_the_program_s_first_chunk_is_the_reference_s(rehearsal):
    """``--cpu-rehearsal`` walks to its end: the CLI trains the conf the
    builder writes and the harness holds its first chunk against
    ``references/afmoe.py``, float32 on both sides."""
    res, nums, out = rehearsal
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert nums["loss_gap"] < 1e-5 and nums["update_norm_gap"] < 1e-4
    assert nums["dparam_norm_gap"] < 1e-3
    assert nums["feed_gap_levels"] == 0 and nums["rows"] == 8
    assert 0.9 * np.log(64) < nums["losses_reference"][0] < 1.6 * np.log(64)
    conf = open(os.path.join(out, "cell.conf")).read()
    assert "iter = tokens" in conf and "eval_train = 0" in conf
    assert "updater = adam" in conf and "remat = 1" in conf
    assert "  attn_window = 32\n" in conf
    assert conf.count("= attention:") == 3
    assert conf.count("  window = 32\n") == 2
    assert conf.count("= routed_experts:") == 2


def test_the_counters_reach_the_line_and_device_metrics_stay_out(rehearsal):
    res, _, out = rehearsal
    with open(os.path.join(out, "telemetry.jsonl")) as f:
        rounds = [json.loads(line) for line in f if line.strip()]
    assert rounds
    for r in rounds:
        c, steps = r["counters"], r["steps"]
        assert c["tokens"] == steps * 128
        # documents of a median of 64 tokens in rows of 128 under a
        # window of 32: fewer pairs than under the diagonal, more than
        # a token each
        assert steps * 128 < c["attn_window_pairs"] < c["attn_pairs"]
        assert c["attn_pairs"] < steps * 128 * 129 // 2
        assert c["attn_tokens"] == steps * 128 * 3
        assert c["expert_pairs_dropped"] == 0
    m = res["metrics"]
    if "tokens_per_step" in m:  # a whole round fell inside the window
        assert m["tokens_per_step"]["value"] == 128.0
        assert m["expert_pairs_dropped"]["value"] == 0.0
        assert 10.0 < m["attn_window_pairs_pct"]["value"] < 100.0
        assert m["attn_flash_pct"]["value"] == 0.0     # the CPU: mha's rows
    # a CPU trace holds no device plane: nothing to read, left out
    for name in ["attn_window_core_ms_step", "attn_full_core_ms_step",
                 "attn_core_roofline_pct", "device_step_ms", "moe_ms_step",
                 "attention_ms_step"]:
        assert name not in m
    assert "device_wait_ms_step" in m and "chunk_overlap_pct" in m


# ----------------------------------------------------------------------
# the control: the reference one precision down must come out apart
@pytest.mark.parametrize("seed", [21, 22])
def test_the_control_fails_the_limits_at_rehearsal_size(ref, toy, seed):
    import jax

    from benchmarks.lib import reference

    _, net = toy
    data, labels = ref.seeded_chunk(net, seed, 4)

    def chunk(control):
        l, p, m = ref.train_chunk(net, ref.make_weights(net, seed), data,
                                  labels, None, control=control)
        return {"losses": l, "params": p, "momentum": m}

    start = jax.device_get(ref.make_weights(net, seed))
    plain = chunk(None)
    sound = reference.compare_chunk(chunk("bfloat16"), plain, start)
    control = reference.compare_chunk(chunk(True), plain, start)
    limits = {k: 3 * sound[k] for k in
              ("loss_gap", "update_norm_gap", "dparam_norm_gap")}
    assert run.held_to_limits(sound, limits)
    assert not run.held_to_limits(control, limits)
    assert control["update_norm_gap"] > 3 * sound["update_norm_gap"]


def test_the_limits_lie_between_their_two_readings(config):
    lim, got = config["limits"], config["limits_readings"]
    for name in ("loss_gap", "update_norm_gap", "dparam_norm_gap"):
        r = got[name]
        assert r["limit"] == lim[name] and r["why"]
        assert r["sound_largest"] < lim[name] < r["control_smallest"]
    assert lim["feed_gap_levels"] == 0
    assert got["how"]


def test_seeded_chunk_draws_the_mix_s_long_documents(ref, config):
    net = ref.describe(run.net_text(config, dict(config["args"]), "tpu"), 1)
    data, labels = ref.seeded_chunk(net, 3, 2)
    assert data.shape == labels.shape == (2, 1, 16384)
    assert data.max() < 25024 and (data == 0).sum() < 40
    np.testing.assert_array_equal(data[0, 0, 1:], labels[0, 0, :-1])
    assert ref.DOC_MEDIAN == 4096


# ----------------------------------------------------------------------
# the counting functions, at one small shape, against a hand count
def test_flops_and_bytes_match_a_hand_count(ref):
    from cxxnet_tpu.models import afmoe_conf

    text = afmoe_conf(
        vocab=50, seq_len=32, hidden=8, layer_types="sf", num_dense_layers=1,
        sliding_window=6, attn_heads=4, attn_kv_heads=2, head_dim=3,
        mlp_hidden=10, num_experts=8, experts_per_tok=2, expert_hidden=12,
        shared_hidden=5, experts_held=4, batch_size=3, dev="cpu")
    net = ref.describe(text, 3)
    tok, d, h, dh = 3 * 32, 8, 4, 3
    pairs = tok * 2 * 4 / 8             # one expert layer, 2 picks, half held
    assert ref.expected_pairs(net) == pairs
    assert ref.expert_flops(net, 10) == 10 * 3 * d * 12 * 2 * 3
    held = 4 * 3 * d * 12
    assert ref.expert_min_bytes(net, 10) == 2 * (3 * held + 5 * 10 * d)
    # a pair: 4 heads x (3 for the score + 3 for the value) multiply-adds;
    # the sliding layer at the windowed pairs, the full one at all
    assert ref.attn_core_flops(net, 100, 1000) == (100 + 1000) * h * 2 * dh * 6
    assert ref.row_pairs(32) == 32 * 33 / 2
    assert ref.row_pairs(32, 6) == 21 + 26 * 6
    assert ref.row_pairs(4, 6) == 10
    attn = d * (2 * h * dh + 2 * 2 * dh) + h * dh * d     # q|gate, k, v; out
    core = h * 2 * dh * (ref.row_pairs(32, 6) + ref.row_pairs(32)) * 3
    moe = 8 * d + 3 * d * 5                  # router, the shared expert
    mlp = 3 * d * 10
    head = d * 50
    macs = tok * (2 * attn + moe + mlp + head) + core + pairs * 3 * d * 12
    assert ref.step_flops(net) == pytest.approx(macs * 6)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    # the hidden width out of: the embedding, 2 attentions, the MLP, the
    # expert layer, the last norm (6); the head's 50
    assert ref.step_min_bytes(net) == (
        tok * (6 * d + 50) * 2 * 5.0 + params * 4 * 8.0)
    assert sorted(net.pshapes) == [0, 1, 2, 3, 4, 5, 6]
    assert net.pshapes[1]["postnorm"] == net.pshapes[4]["postnorm"] == (8,)


def test_the_published_size_is_what_the_issue_reckoned(config, ref):
    text = run.net_text(config, dict(config["args"]), "tpu")
    net = ref.describe(text, 1)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    assert params == 504_147_712                     # x 16 B = 8.07 GB
    # 0.5 held pairs a token a layer: 1024 a held expert
    assert ref.expected_pairs(net) == 4 * 16384 * 8 * 8 / 128
    assert ref.expected_pairs(net) / 4 / 8 == 1024.0
    assert 38.0e12 < ref.step_flops(net) < 39.5e12
    # the row one document: 32 heads x 256 a pair; four layers under the
    # window, one under the diagonal
    near, full = ref.row_pairs(16384, 2048), ref.row_pairs(16384)
    assert (near, full) == (2048 * 2049 / 2 + 14336 * 2048,
                            16384 * 16385 / 2)
    assert ref.attn_core_flops(net, near, full) == pytest.approx(
        12.782e12, rel=1e-3)
    # step_flops credits the windowed layers their window only
    assert ref.step_flops(net) < ref.attn_core_flops(net, full, full) + \
        26.5e12
    assert net.pshapes[12]["wmat"] == (25024, 2048)
    assert net.pshapes[1]["wmat"] == (2 * 4096 + 2 * 512, 2048)


# ----------------------------------------------------------------------
# each new reader on a fixture record
EVENTS = [
    # (HLO name, ns, scope) — two traced steps
    ("%fusion.1", 4000, "jit(step)/while/body/jvp(l1_attn0)/dot_general"),
    ("%flash_fwd.1", 6000, "jit(step)/while/body/jvp(l1_attn0)/core_window/"
     "flash_fwd/pallas_call"),
    ("%flash_dkv.1", 10000, "jit(step)/while/body/transpose(jvp(l1_attn0))/"
     "jvp(l1_attn0)/checkpoint/rematted_computation/core_window/flash_dkv/"
     "pallas_call"),
    ("%fusion.4", 1000, "jit(step)/while/body/jvp(l1_attn0)/rotary/mul"),
    ("%fusion.5", 2500, "jit(step)/while/body/jvp(l2_moe1)/dispatch/sort"),
    ("%ragged-dot-none", 9000, "ragged-dot-none"),
    ("%flash_fwd.2", 8000, "jit(step)/while/body/jvp(l3_attn1)/core_full/"
     "flash_fwd/pallas_call"),
    ("%fusion.6", 900, "jit(step)/while/body/jvp(l3_attn1)/qk_norm/mul"),
    ("%fusion.7", 2000, "jit(step)/while/body/jvp(l6_head)/dot_general"),
    ("%fusion.8", 7000, "jit(step)/while/body/update_adam/sqrt"),
    ("%while.1", 99999, "jit(step)/while"),
    ("%copy.1", 100, None),
]
ATTN_CFG = """  nhead = 4
  nkvhead = 2
  head_dim = 3
  qk_norm = 1
  out_gate = 1
  causal = 1
  no_bias = 1
"""
CONF = ("""netconfig = start
layer[0->h0] = embedding:embed
  nvocab = 50
  nhidden = 8
layer[h0,0->x0] = attention:attn0
  window = 6
  rotary_dim = 3
""" + ATTN_CFG + """layer[x0->h1] = routed_experts:moe1
  nexpert = 8
  topk = 2
  nhidden = 12
  nheld = 4
layer[h1,0->x1] = attention:attn1
""" + ATTN_CFG + """layer[x1->h2] = gated_mlp:mlp1
  nhidden = 10
layer[h2->nf] = rms_norm:norm_f
layer[nf->logits] = lm_head:head
  nhidden = 50
layer[logits->logits] = softmax
netconfig = end
input_shape = 1,1,32
""")


@pytest.fixture()
def record(tmp_path):
    out = str(tmp_path)
    with open(os.path.join(out, "cell.conf"), "w") as f:
        f.write(CONF)
    got = scopes.reduce_events(EVENTS)
    text, layers = scopes.conf_layers(out)
    got.update(conf=text, out=out,
               types={i: k for i, (k, _) in enumerate(layers)})
    scopes._CACHE[out] = got
    stage_scopes._CACHE[out] = stage_scopes.reduce_parts(EVENTS)
    yield {"out": out, "workload": CELL,
           "trace": {"steps": 2, "busy_s": 1.0}, "batch": 3,
           "chips": 1, "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "telemetry": [
               {"steps": 24, "counters": {"attn_pairs": 24 * 3 * 400,
                                          "attn_window_pairs": 24 * 3 * 150,
                                          "tokens": 24 * 96}},
               {"steps": 24, "counters": {"attn_pairs": 24 * 3 * 440,
                                          "attn_window_pairs": 24 * 3 * 170,
                                          "tokens": 24 * 96}}]}
    scopes._CACHE.pop(out, None)
    stage_scopes._CACHE.pop(out, None)


@pytest.mark.parametrize("name,want", [
    ("attn_window_core_ms_step", (6000 + 10000) / 1e6 / 2),
    ("attn_full_core_ms_step", 8000 / 1e6 / 2),
    ("attn_window_pairs_pct", 100.0 * 320 / 840),
    # the readers that were there select by conf type
    ("attention_ms_step", (4000 + 6000 + 10000 + 1000 + 8000 + 900)
     / 1e6 / 2),
    ("moe_ms_step", (2500 + 9000) / 1e6 / 2),
    ("head_loss_ms_step", 2000 / 1e6 / 2),
    ("adam_update_ms_step", 7000 / 1e6 / 2),
])
def test_a_reader_reads_its_scope_or_its_counters(record, name, want):
    mod = run.load_metric(name)
    assert mod.read(record) == pytest.approx(want)
    assert mod.MOVES == "train_samples_s_chip"


def test_the_core_s_roofline_share_is_counted_on_the_run_s_own_pairs(
        record, ref, config):
    net = ref.describe(CONF, 3)
    near, full = 3 * 160.0, 3 * 420.0         # a step: a counter / steps
    flops = ref.attn_core_flops(net, near, full)
    assert flops == (near + full) * 4 * 2 * 3 * 6
    mod = run.load_metric("attn_core_roofline_pct")
    # found through the cell's configuration, not a path in the reader
    assert mod.reference_path(record) == config["reference"] == \
        "benchmarks/references/afmoe.py"
    assert "references/" not in open(mod.__file__).read().split('"""')[2]
    got = mod.read(record)
    assert got == pytest.approx(100.0 * (flops / 197e12) / (0.012 / 1e3))
    # no windowed counter (the parent commit's program), no share
    bare = dict(record, telemetry=[{"steps": 24, "counters": {
        "tokens": 1, "attn_pairs": 99}}])
    assert mod.read(bare) is None
    assert run.load_metric("attn_window_pairs_pct").read(bare) is None
    # a cell whose reference brings no attn_core_flops: none, no raise
    other = dict(record, workload="joyai_llm_flash_train_packed8k")
    assert mod.reference_path(other) == \
        "benchmarks/references/joyai_llm_flash.py"
    assert mod.read(dict(record, workload="no_such_cell")) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name, tmp_path):
    """An untraced run, a run whose directory is not there, a program
    that counts nothing (the parent commit): ``None``, never an
    exception."""
    mod = run.load_metric(name)
    bare = {"out": str(tmp_path / "absent"), "trace": None, "batch": 1,
            "chips": 1, "peaks": None, "telemetry": [{"steps": 8}]}
    assert mod.read(bare) is None
    traced = dict(bare, trace={"steps": 16, "busy_s": 1.0})
    assert mod.read(traced) is None
    counted = dict(traced, telemetry=[{"steps": 8, "counters": {
        "tokens": 8 * 8192, "attn_pairs": 8 * 9000000}}])
    assert mod.read(counted) is None


def test_a_traced_run_of_the_parent_s_program_reads_no_core_scope(tmp_path):
    """The same conf traced on a program without the two scopes (the
    parent's attention layers name no ``core_*``): the layer's total is
    read, the new readers find nothing."""
    out = str(tmp_path)
    with open(os.path.join(out, "cell.conf"), "w") as f:
        f.write(CONF)
    events = [("%flash_fwd.1", 4000,
               "jit(step)/while/body/jvp(l1_attn0)/flash_fwd/pallas_call")]
    got = scopes.reduce_events(events)
    text, layers = scopes.conf_layers(out)
    got.update(conf=text, out=out,
               types={i: k for i, (k, _) in enumerate(layers)})
    scopes._CACHE[out] = got
    stage_scopes._CACHE[out] = stage_scopes.reduce_parts(events)
    rec = {"out": out, "workload": CELL,
           "trace": {"steps": 2, "busy_s": 1.0}, "batch": 1,
           "chips": 1, "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "telemetry": [{"steps": 8, "counters": {"attn_pairs": 99}}]}
    try:
        for name in NEW_METRICS:
            assert run.load_metric(name).read(rec) is None
        assert run.load_metric("attention_ms_step").read(rec) == 0.002
    finally:
        scopes._CACHE.pop(out, None)
        stage_scopes._CACHE.pop(out, None)
