"""The configuration of ISSUE 46 (``smallthinker_21b_a3b``), its cell,
reference and metric reader, on the CPU: the file against the catalog
row key by key, ``reduced`` and ``assumed`` complete, the memory rule's
two compiles, the cell the one the issue names, picked up by files alone
and rehearsed ``correct``, the float8 control failing a limit, the new
reader on a fixture record, the counting functions against a hand
count."""

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

CELL = "smallthinker_21b_a3b_train_packed16k"
CONFIG = "smallthinker_21b_a3b"
MIX = "train_packed16k"
NEW_METRICS = ["moe_route_ms_step"]
SHARED = ["device_step_ms", "compiles_in_window", "device_mfu_pct",
          "device_idle_pct", "peak_hbm_gib", "xla_compile_s",
          "jax_trace_lower_s", "iter_init_s", "loop_next_ms_step",
          "loop_copy_ms_step", "loop_stack_ms_step", "loop_self_ms_step",
          "h2d_enqueue_ms_step", "scan_dispatch_ms_step",
          "device_wait_ms_step", "chunk_recycled_pct", "chunk_overlap_pct",
          "loop_device_step_ms", "loop_device_idle_pct", "round_head_ms_step",
          "h2d_tail_ms_step", "chunk_starved_pct", "attention_ms_step",
          "head_loss_ms_step", "adam_update_ms_step", "tokens_per_step",
          "packed_docs_per_seq", "moe_ms_step", "moe_route_dispatch_ms_step",
          "expert_matmul_ms_step", "expert_pairs_per_expert",
          "expert_load_max_over_mean", "expert_pairs_dropped",
          "expert_dispatch_compact_pct", "attn_flash_pct",
          "attn_window_core_ms_step", "attn_full_core_ms_step",
          "attn_window_pairs_pct", "attn_core_roofline_pct",
          "attn_unmasked_blocks_pct", "attn_fwd_runs_per_bwd"]
PERIOD = [0, 1, 1, 1]
#: the catalog row's ``config``
#: (/opt/skills/guides/model-configs/architectures.jsonl,
#: SmallThinker-21BA3B-Instruct)
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}


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
                       if r["name"] == "SmallThinker-21BA3B-Instruct")
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 18992)
    assert config["published"] == {k: PUBLISHED[k] for k in differs}
    assert sorted(config["reduced_why"]) == differs
    assert 8 * config["vocab_size"] == PUBLISHED["vocab_size"]
    assert 4 * config["moe_num_primary_experts"] == \
        PUBLISHED["moe_num_primary_experts"]
    # no width among the cuts
    for key in differs:
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size"
    # the kept layers are one whole period in its published order, and
    # the builder's two layouts say the same
    kept = config["kept_layers"]
    a = config["args"]
    assert kept == [0, 1, 2, 3]
    assert [PUBLISHED["sliding_window_layout"][i] for i in kept] == \
        a["sliding_window_layout"] == PERIOD
    assert [PUBLISHED["rope_layout"][i] for i in kept] == \
        a["rope_layout"] == PERIOD
    assert len(a["rope_layout"]) == config["num_hidden_layers"]
    assert (a["hidden"], a["vocab"], a["num_experts"], a["experts_held"],
            a["experts_per_tok"], a["expert_hidden"], a["first_expert"]) == (
        2560, 18992, 64, 16, 6, 768, 0)
    assert (a["attn_heads"], a["attn_kv_heads"], a["head_dim"],
            a["sliding_window"], a["rope_theta"], a["eps"]) == (
        28, 4, 128, 4096, 1500000.0, 1e-6)
    assert (a["seq_len"], a["batch_size"], a["scan_steps"],
            a["compute_dtype"], a["eta"]) == (16384, 1, 8, "bfloat16", 3e-4)
    # what neither config.json nor described_as settles, with provenance
    assumed = config["assumed"]
    for key in ("router_input", "no_biases_no_qk_norms"):
        assert assumed[key].startswith("NOT SETTLED")
    assert "no models/smallthinker" in assumed["provenance"]
    for key in ("secondary_experts", "softmax_then_topk", "window_edge",
                "positions", "router_gradient", "init", "documents",
                "optimizer", "norm"):
        assert assumed[key]
    assert "rank 0" in config["deployment"] and "FOLDED" in \
        config["deployment"]
    assert "4 chips share each layer" in config["deployment"]
    assert "a stage is 8 chips" in config["deployment"]
    text = run.net_text(config, dict(a), "tpu")
    assert text.count("= attention:") == 4
    assert text.count("  window = 4096\n") == 3
    assert text.count("  rotary_dim = 128\n") == 3
    assert text.count("= routed_experts:") == 4
    assert text.count("  route_norm = attn") == 4
    assert text.count("  expert_act = reglu\n") == 4
    for absent in ("gated_mlp", "qk_norm", "shared_hidden", "postnorm",
                   "out_gate", "select_bias"):
        assert absent not in text


def test_the_memory_rule_kept_sixteen_held_experts(config):
    """ISSUE 46: 16 held unless the compiled step is live above 14.4 GB;
    it is not (12.09), so 16 are held; the floor's compile beside it."""
    mem = config["memory_analysis_v5e"]
    live = lambda m: (m["argument_size_in_bytes"]  # noqa: E731
                      + m["output_size_in_bytes"] - m["alias_size_in_bytes"]
                      + m["temp_size_in_bytes"])
    assert live(mem["held8"]) < live(mem["b1_t16384_scan8"]) <= 14.4e9
    assert live(mem["b1_t16384_scan8"]) == 12_089_216_000
    assert live(mem["held8"]) == 9_832_704_512
    assert config["args"]["experts_held"] == 16
    assert "12.09" in config["reduced_why"]["moe_num_primary_experts"]
    # weights and adam's two moments: 12 B a parameter
    assert abs(mem["b1_t16384_scan8"]["argument_size_in_bytes"]
               - 559_290_880 * 12) < 2e6


def test_the_cell_is_the_one_the_issue_names():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and "1536 pairs" in cell["why"]
    assert "over due" in cell["why"] and "attention" in cell["why"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 7 and len(bench["configs"]) >= 8
    entry = bench["configs"][7]
    assert entry["name"] == CONFIG and entry["reduced"] == [
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size"]
    config = run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] and "PowerInfer" in \
        entry["source"]
    assert entry["reduced"] == config["reduced"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW_METRICS[0])
    assert at == 63
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_samples_s_chip"
        assert by_name[name]["better"] == "lower"
        mod = run.load_metric(name)
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            by_name[name]["unit"], by_name[name]["source"],
            by_name[name]["layer"], by_name[name]["moves"])
    for name in SHARED:
        assert by_name[name]["workloads"].index(CELL) == len(
            [w for w in by_name[name]["workloads"]
             if names.index(w) < 7]), name
    # no dense MLP, no scan, no delta rule, no latent, no module; and no
    # twin of expert_matmul_roofline_pct (its reader names one reference)
    for name in ("mlp_ms_step", "expert_matmul_roofline_pct",
                 "ssd_scan_ms_step", "gdn_scan_ms_step", "mla_core_ms_step",
                 "mtp_ms_step", "train_metric_ms_step",
                 "dispatch_gap_ms_step", "moe_latent_proj_ms_step",
                 "latent_expert_matmul_roofline_pct"):
        assert CELL not in by_name[name]["workloads"]
    assert sum(CELL in m.get("workloads", [])
               for m in bench["per_layer"][:at + 1]) == \
        len(SHARED) + len(NEW_METRICS)


def test_the_mix_is_trinity_s_as_it_stands(config):
    """No new mix: the 16384-token rows of long documents, told this
    configuration's window through the same ``{sliding_window}``."""
    mix = run.load_json(os.path.join(BENCH, "traffic", MIX + ".json"))
    assert "  attn_window = {sliding_window}" in mix["conf"]
    assert config["args"]["sliding_window"] == 4096
    assert mix["documents"] == {"median": 4096, "sigma": 1.2, "min": 16}
    # what assumed.documents says of them under a window of 4096
    gen = run.load_generator(mix)
    raw = gen.stream(64 * 16384, 16384, 18992, mix["documents"], 7)
    lens = np.diff(np.flatnonzero(raw == 0), prepend=-1)
    assert 0.4 < (lens > 4096).mean() < 0.6
    assert lens[lens > 4096].sum() / lens.sum() > 0.8
    assert raw.max() < 18992 and raw.dtype == np.dtype("<u2")


# ----------------------------------------------------------------------
# picked up by files alone: the cell as BENCHMARK.json has it, rehearsed
@pytest.fixture(scope="module")
def rehearsal():
    res = helpers.run_cell_in_child(
        BENCH, ["--workload", CELL, "--seed", "4600000642", "--seconds", "6",
                "--trace", "1", "--cpu-rehearsal"])
    out = os.path.join(ROOT, "bench_out", CELL, "seed4600000642_trace1")
    with open(os.path.join(out, "compare.json")) as f:
        return res, json.load(f), out


def test_the_program_s_first_chunk_is_the_reference_s(rehearsal):
    """``--cpu-rehearsal`` walks to its end: the CLI trains the conf the
    builder writes and the harness holds its first chunk against
    ``references/smallthinker.py``, float32 on both sides."""
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
    assert conf.count("= routed_experts:") == 3
    assert conf.count("  route_norm = attn") == 3 and "nhead = 7" in conf


def test_the_counters_reach_the_line_and_device_metrics_stay_out(rehearsal):
    res, _, out = rehearsal
    with open(os.path.join(out, "telemetry.jsonl")) as f:
        rounds = [json.loads(line) for line in f if line.strip()]
    assert rounds
    for r in rounds:
        c, steps = r["counters"], r["steps"]
        assert c["tokens"] == steps * 128
        assert steps * 128 < c["attn_window_pairs"] < c["attn_pairs"]
        assert c["attn_tokens"] == steps * 128 * 3
        assert c["expert_pairs_dropped"] == 0
        # 3 picks of 16 over 3 layers, a quarter of the experts held
        assert 0.4 < c["expert_pairs"] / (steps * 128 * 3 * 3 / 4) < 1.6
    m = res["metrics"]
    if "tokens_per_step" in m:  # a whole round fell inside the window
        assert m["tokens_per_step"]["value"] == 128.0
        assert m["expert_pairs_dropped"]["value"] == 0.0
        assert 10.0 < m["attn_window_pairs_pct"]["value"] < 100.0
        assert m["attn_flash_pct"]["value"] == 0.0     # the CPU: mha's rows
    # a CPU trace holds no device plane: nothing to read, left out
    for name in NEW_METRICS + ["attn_window_core_ms_step", "moe_ms_step",
                               "attn_core_roofline_pct", "device_step_ms",
                               "attention_ms_step", "mlp_ms_step"]:
        assert name not in m
    assert "device_wait_ms_step" in m and "chunk_overlap_pct" in m


# ----------------------------------------------------------------------
# the control: the reference one precision down must come out apart
@pytest.mark.parametrize("seed", [31])
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
        if name == "loss_gap" and r.get("control_smallest") is None:
            continue   # where precision hardly moves the loss: the why
        assert r["sound_largest"] < lim[name] < r["control_smallest"]
    assert lim["feed_gap_levels"] == 0
    assert got["how"]


def test_seeded_chunk_draws_the_mix_s_long_documents(ref, config):
    net = ref.describe(run.net_text(config, dict(config["args"]), "tpu"), 1)
    data, labels = ref.seeded_chunk(net, 3, 2)
    assert data.shape == labels.shape == (2, 1, 16384)
    assert data.max() < 18992 and (data == 0).sum() < 40
    np.testing.assert_array_equal(data[0, 0, 1:], labels[0, 0, :-1])
    assert ref.DOC_MEDIAN == 4096


# ----------------------------------------------------------------------
# the counting functions, at one small shape, against a hand count
def test_flops_and_bytes_match_a_hand_count(ref):
    from cxxnet_tpu.models import smallthinker_conf

    text = smallthinker_conf(
        vocab=50, seq_len=32, hidden=8, sliding_window_layout=[0, 1],
        rope_layout=[0, 1], sliding_window=6, attn_heads=4, attn_kv_heads=2,
        head_dim=3, num_experts=8, experts_per_tok=2, expert_hidden=12,
        experts_held=4, batch_size=3, dev="cpu")
    net = ref.describe(text, 3)
    tok, d, h, dh = 3 * 32, 8, 4, 3
    pairs = 2 * tok * 2 * 4 / 8        # two expert layers, 2 picks, half held
    assert ref.expected_pairs(net) == pairs
    assert ref.expert_flops(net, 10) == 10 * 3 * d * 12 * 2 * 3
    held = 2 * 4 * 3 * d * 12
    assert ref.expert_min_bytes(net, 10) == 2 * (3 * held + 5 * 10 * d)
    # a pair: 4 heads x (3 for the score + 3 for the value) multiply-adds;
    # the windowed layer at the windowed pairs, the full one at all
    assert ref.attn_core_flops(net, 100, 1000) == (100 + 1000) * h * 2 * dh * 6
    assert ref.row_pairs(32) == 32 * 33 / 2
    assert ref.row_pairs(32, 6) == 21 + 26 * 6
    attn = d * (h * dh + 2 * 2 * dh) + h * dh * d         # q, k, v; out
    core = h * 2 * dh * (ref.row_pairs(32, 6) + ref.row_pairs(32)) * 3
    router = 8 * d
    head = d * 50
    macs = tok * (2 * attn + 2 * router + head) + core + pairs * 3 * d * 12
    assert ref.step_flops(net) == pytest.approx(macs * 6)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    # the hidden width out of: the embedding, 2 attentions, 2 expert
    # layers, the last norm (6); the head's 50
    assert ref.step_min_bytes(net) == (
        tok * (6 * d + 50) * 2 * 5.0 + params * 4 * 8.0)
    assert sorted(net.pshapes) == [0, 1, 2, 3, 4, 5, 6]
    assert set(net.pshapes[2]) == {"wgate", "wmat", "wproj", "norm"}


def test_the_published_size_is_what_the_issue_reckoned(config, ref):
    text = run.net_text(config, dict(config["args"]), "tpu")
    net = ref.describe(text, 1)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    assert params == 559_290_880                     # x 16 B = 8.95 GB
    # 1.5 held pairs a token a layer: 1536 a held expert
    assert ref.expected_pairs(net) == 4 * 16384 * 6 * 16 / 64
    assert ref.expected_pairs(net) / 4 / 16 == 1536.0
    # the row one document: 28 heads x 256 a pair; three layers under the
    # window, one under the diagonal
    near, full = ref.row_pairs(16384, 4096), ref.row_pairs(16384)
    assert (near, full) == (4096 * 4097 / 2 + 12288 * 4096,
                            16384 * 16385 / 2)
    assert ref.attn_core_flops(net, near, full) == pytest.approx(
        28 * 256 * (3 * near + full) * 6)
    # step_flops credits the windowed layers their window only
    dense = 16384 * (4 * (2560 * 4608 + 3584 * 2560 + 64 * 2560)
                     + 18992 * 2560)
    assert ref.step_flops(net) == pytest.approx(6 * (
        dense + 28 * 256 * (3 * near + full)
        + ref.expected_pairs(net) * 3 * 2560 * 768))
    assert 29.5e12 < ref.step_flops(net) < 30.5e12
    assert net.pshapes[10]["wmat"] == (18992, 2560)
    assert net.pshapes[1]["wmat"] == (3584 + 2 * 512, 2560)
    assert net.pshapes[2]["wmat"] == (16, 2560, 1536)


# ----------------------------------------------------------------------
# the new reader on a fixture record
EVENTS = [
    # (HLO name, ns, scope) — two traced steps
    ("%fusion.1", 4000, "jit(step)/while/body/jvp(l1_attn0)/dot_general"),
    ("%flash_fwd.1", 8000, "jit(step)/while/body/jvp(l1_attn0)/core_full/"
     "flash_fwd/pallas_call"),
    ("%fusion.2", 700, "jit(step)/while/body/jvp(l2_moe0)/route/rsqrt"),
    ("%fusion.3", 1300, "jit(step)/while/body/jvp(l2_moe0)/route/"
     "dot_general"),
    ("%fusion.4", 500, "jit(step)/while/body/transpose(jvp(l2_moe0))/"
     "jvp(l2_moe0)/checkpoint/rematted_computation/route/top_k"),
    ("%fusion.5", 2500, "jit(step)/while/body/jvp(l2_moe0)/dispatch/sort"),
    ("%fusion.6", 1500, "jit(step)/while/body/jvp(l2_moe0)/combine/add"),
    ("%ragged-dot-none", 9000, "ragged-dot-none"),
    ("%flash_fwd.2", 6000, "jit(step)/while/body/jvp(l3_attn1)/core_window/"
     "flash_fwd/pallas_call"),
    ("%fusion.7", 900, "jit(step)/while/body/jvp(l4_moe1)/route/reduce_max"),
    ("%fusion.8", 2000, "jit(step)/while/body/jvp(l6_head)/dot_general"),
    ("%fusion.9", 7000, "jit(step)/while/body/update_adam/sqrt"),
    ("%while.1", 99999, "jit(step)/while"),
    ("%copy.1", 100, None),
]


@pytest.fixture()
def record(tmp_path):
    from cxxnet_tpu.models import smallthinker_conf

    out = str(tmp_path)
    conf = smallthinker_conf(
        vocab=50, seq_len=32, hidden=8, sliding_window_layout=[0, 1],
        rope_layout=[0, 1], sliding_window=6, attn_heads=4, attn_kv_heads=2,
        head_dim=3, num_experts=8, experts_per_tok=2, expert_hidden=12,
        experts_held=4, batch_size=3, dev="cpu")
    with open(os.path.join(out, "cell.conf"), "w") as f:
        f.write(conf)
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
                                          "tokens": 24 * 96}}]}
    scopes._CACHE.pop(out, None)
    stage_scopes._CACHE.pop(out, None)


@pytest.mark.parametrize("name,want", [
    # the route scope alone: the second input's norm, the product, the
    # top-k, forward and recomputed, both expert layers
    ("moe_route_ms_step", (700 + 1300 + 500 + 900) / 1e6 / 2),
    # the readers that were there read the new cell's scopes unchanged
    ("moe_route_dispatch_ms_step", (700 + 1300 + 500 + 900 + 2500 + 1500)
     / 1e6 / 2),
    ("moe_ms_step", (700 + 1300 + 500 + 2500 + 1500 + 900 + 9000) / 1e6 / 2),
    ("attn_full_core_ms_step", 8000 / 1e6 / 2),
    ("attn_window_core_ms_step", 6000 / 1e6 / 2),
    ("attention_ms_step", (4000 + 8000 + 6000) / 1e6 / 2),
    ("head_loss_ms_step", 2000 / 1e6 / 2),
    ("adam_update_ms_step", 7000 / 1e6 / 2),
])
def test_a_reader_reads_its_scope(record, name, want):
    mod = run.load_metric(name)
    assert mod.read(record) == pytest.approx(want)
    assert mod.MOVES == "train_samples_s_chip"


def test_the_core_s_roofline_share_finds_this_reference(record, ref, config):
    """``attn_core_roofline_pct`` finds a cell's reference through its
    configuration file: the new cell needs no twin of the reader."""
    mod = run.load_metric("attn_core_roofline_pct")
    assert mod.reference_path(record) == config["reference"] == \
        "benchmarks/references/smallthinker.py"
    net = ref.describe(open(os.path.join(record["out"], "cell.conf")).read(),
                       3)
    flops = ref.attn_core_flops(net, 3 * 150.0, 3 * 400.0)
    assert flops == (3 * 150 + 3 * 400) * 4 * 2 * 3 * 6
    assert mod.read(record) == pytest.approx(
        100.0 * (flops / 197e12) / (0.007 / 1e3))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name, tmp_path):
    """An untraced run, a run whose directory is not there, a traced run
    of a program without the scope: ``None``, never an exception."""
    mod = run.load_metric(name)
    bare = {"out": str(tmp_path / "absent"), "trace": None, "batch": 1,
            "chips": 1, "peaks": None, "telemetry": [{"steps": 8}]}
    assert mod.read(bare) is None
    assert mod.read(dict(bare, trace={"steps": 16, "busy_s": 1.0})) is None
    out = str(tmp_path)
    with open(os.path.join(out, "cell.conf"), "w") as f:
        f.write("netconfig = start\nlayer[0->h0] = embedding:embed\n"
                "layer[h0->h1] = routed_experts:moe0\nnetconfig = end\n")
    events = [("%fusion.1", 4000,
               "jit(step)/while/body/jvp(l1_moe0)/dispatch/sort")]
    got = scopes.reduce_events(events)
    text, layers = scopes.conf_layers(out)
    got.update(conf=text, out=out,
               types={i: k for i, (k, _) in enumerate(layers)})
    scopes._CACHE[out] = got
    stage_scopes._CACHE[out] = stage_scopes.reduce_parts(events)
    try:
        rec = dict(bare, out=out, trace={"steps": 2, "busy_s": 1.0})
        assert not mod.read(rec)
        assert run.load_metric("moe_ms_step").read(rec) == 0.002
    finally:
        scopes._CACHE.pop(out, None)
        stage_scopes._CACHE.pop(out, None)
