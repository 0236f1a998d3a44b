"""The configuration of ISSUE 49 (``ling_3_0_flash``), its cell, reference
and metric readers, on the CPU: the file against the catalog row key by
key, ``reduced`` and ``assumed`` complete, the memory rule's compiles,
the cell the one the issue names, picked up by files alone and rehearsed
``correct``, the float8 control failing a limit, the new readers on a
fixture record, the counting functions against a hand count."""

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

CELL = "ling_3_0_flash_train_packed8k"
CONFIG = "ling_3_0_flash"
MIX = "train_packed8k"
NEW_METRICS = ["kda_mixer_ms_step", "kda_scan_ms_step",
               "kda_scan_roofline_pct", "kda_scan_fused_pct",
               "kda_fwd_runs_per_bwd"]
SHARED = ["device_step_ms", "compiles_in_window", "device_mfu_pct",
          "device_idle_pct", "peak_hbm_gib", "xla_compile_s",
          "jax_trace_lower_s", "iter_init_s", "loop_next_ms_step",
          "loop_copy_ms_step", "loop_stack_ms_step", "loop_self_ms_step",
          "h2d_enqueue_ms_step", "scan_dispatch_ms_step",
          "device_wait_ms_step", "chunk_recycled_pct", "chunk_overlap_pct",
          "loop_device_step_ms", "loop_device_idle_pct", "round_head_ms_step",
          "h2d_tail_ms_step", "chunk_starved_pct", "mlp_ms_step",
          "head_loss_ms_step", "adam_update_ms_step", "tokens_per_step",
          "packed_docs_per_seq", "moe_ms_step", "moe_route_ms_step",
          "moe_route_dispatch_ms_step", "expert_matmul_ms_step",
          "expert_pairs_per_expert", "expert_load_max_over_mean",
          "expert_pairs_dropped", "expert_dispatch_compact_pct",
          "mla_ms_step", "mla_core_ms_step", "attn_flash_pct",
          "attn_unmasked_blocks_pct", "attn_bwd_fused_pct"]
REDUCED = {"num_hidden_layers": 6, "first_k_dense_replace": 1,
           "num_experts": 8, "vocab_size": 19648,
           "num_nextn_predict_layers": 0}


def catalog_row():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return None
    with open(catalog) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "Ling-3.0-flash")


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
    row = catalog_row()
    published = dict(config["published"])
    if row is not None:
        assert row["source_url"] == config["source"]
        assert set(row["config"]) <= set(config)
        differs = sorted(k for k, v in row["config"].items()
                         if config[k] != v)
        assert differs == sorted(config["reduced"])
        assert published == {k: row["config"][k] for k in differs}
    assert sorted(config["reduced"]) == sorted(REDUCED) == sorted(
        config["reduced_why"]) == sorted(published)
    assert {k: config[k] for k in REDUCED} == REDUCED
    assert published == {"num_hidden_layers": 42, "first_k_dense_replace": 2,
                         "num_experts": 512, "vocab_size": 157184,
                         "num_nextn_predict_layers": 1}
    assert 8 * config["vocab_size"] == published["vocab_size"]
    # no width among the cuts
    for key in REDUCED:
        assert not key.endswith(("_dim", "_rank", "_size")) or \
            key == "vocab_size"
    # the widths, as published
    assert (config["hidden_size"], config["num_attention_heads"],
            config["head_dim"], config["kv_lora_rank"], config["q_lora_rank"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["n_group"], config["topk_group"],
            config["kda_lower_bound"], config["short_conv_kernel_size"],
            config["layer_group_size"]) == (
        2560, 32, 128, 512, None, 128, 64, 128, 6144, 768, 8, 8, 4, -5, 4, 6)
    # the clamp's lists whole: 0 on every kept layer
    assert len(config["expert_swiglu_limit_list"]) == 42 == len(
        config["share_expert_swiglu_limit_list"])
    kept = config["kept_layers"]
    assert kept == [0, 1, 2, 3, 4, 5]
    a = config["args"]
    for mine, theirs in (("expert_swiglu_limits", "expert_swiglu_limit_list"),
                         ("shared_swiglu_limits",
                          "share_expert_swiglu_limit_list")):
        assert a[mine] == [config[theirs][i] for i in kept] == [0] * 6
    assert (a["hidden"], a["vocab"], a["num_layers"], a["layer_group_size"],
            a["first_k_dense"], a["attn_heads"], a["head_dim"]) == (
        2560, 19648, 6, 6, 1, 32, 128)
    assert (a["num_experts"], a["experts_held"], a["experts_per_tok"],
            a["n_group"], a["topk_group"], a["expert_hidden"],
            a["shared_hidden"], a["routed_scaling_factor"],
            a["first_expert"]) == (512, 8, 8, 8, 4, 768, 768, 2.5, 0)
    assert (a["kv_lora_rank"], a["qk_nope_head_dim"], a["qk_rope_head_dim"],
            a["v_head_dim"], a["rope_theta"], a["eps"], a["mlp_hidden"],
            a["kda_lower_bound"], a["short_conv_kernel_size"]) == (
        512, 128, 64, 128, 6e6, 1e-6, 6144, -5.0, 4)
    assert (a["seq_len"], a["batch_size"], a["scan_steps"],
            a["compute_dtype"], a["eta"]) == (8192, 1, 8, "bfloat16", 3e-4)
    # every inference of ISSUE 49's section 1, with what it was read from
    assumed = config["assumed"]
    for key in ("provenance", "kda_projections", "kda_gate", "kda_heads",
                "kda_output", "kda_voided_keys", "kda_init", "mla",
                "group_limit", "swiglu_limits", "router_gradient",
                "score_bias", "partial_sum", "init", "norm", "optimizer",
                "documents"):
        assert assumed[key], key
    for key, named in (("kda_projections", "no_kda_lora"),
                       ("kda_gate", "kda_lower_bound"),
                       ("kda_heads", "num_kv_heads_for_linear_attn"),
                       ("kda_output", "group_norm_size"),
                       ("mla", "q_lora_rank null"),
                       ("swiglu_limits", "REFUSES")):
        assert named in assumed[key], key
    assert "rank 0 of stage 0" in config["deployment"]
    assert "64 chips share each layer" in config["deployment"]
    assert "FOLDED" in config["deployment"]
    text = run.net_text(config, dict(a), "tpu")
    assert text.count("= kimi_delta:") == 5
    assert text.count("= latent_attention:") == 1 == text.count(
        "  q_rank = 0\n") == text.count("  out_gate = head\n")
    assert text.count("= routed_experts:") == 5 == text.count(
        "  n_group = 8\n  topk_group = 4\n")
    assert text.count("= gated_mlp:") == 1
    for absent in ("mtp_", "postnorm", "route_norm", "window"):
        assert absent not in text


def test_the_memory_rule_kept_thirty_two_heads_and_nothing_of_the_kernels(
        config):
    """ISSUE 49: this cut unless the compiled step is live above 14.4
    GB; with what the forward kernels wrote kept it is (16.41, 14.74),
    with nothing kept it is not (14.35): 32 heads, no fallback."""
    mem = config["memory_analysis_v5e"]
    live = lambda m: (m["argument_size_in_bytes"]  # noqa: E731
                      + m["output_size_in_bytes"] - m["alias_size_in_bytes"]
                      + m["temp_size_in_bytes"])
    assert live(mem["b1_t8192_scan8"]) == 14_345_591_296 <= 14.4e9
    assert 14.4e9 < live(mem["solve_kept"]) < live(mem["all_kept"])
    assert config["args"]["attn_heads"] == 32
    assert "num_attention_heads" not in config["reduced"]
    assert "14.35" in config["reduced_why"]["vocab_size"]
    # weights and adam's two moments: 12 B a parameter
    assert abs(mem["b1_t8192_scan8"]["argument_size_in_bytes"]
               - 767_009_056 * 12) < 2e6


def test_the_cell_is_the_one_the_issue_names():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert len(cell["why"]) <= 200 and "128 pairs" in cell["why"]
    assert "over their due" in cell["why"] and "5 KDA scans" in cell["why"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 8 and len(bench["configs"]) >= 9
    assert all(w["chips"] == 1 for w in bench["workloads"][:9])
    entry = bench["configs"][8]
    assert entry["name"] == CONFIG and entry["reduced"] == list(REDUCED)
    config = run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] and "inclusionAI" in \
        entry["source"]
    assert entry["reduced"] == config["reduced"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW_METRICS[0])
    assert listed[at:at + 5] == NEW_METRICS and at == 66
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_samples_s_chip"
        mod = run.load_metric(name)
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            by_name[name]["unit"], by_name[name]["source"],
            by_name[name]["layer"], by_name[name]["moves"])
    assert by_name["kda_scan_roofline_pct"]["unit"] == "%"
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL, name
    # not on the list of a metric it cannot report: the silent one, the
    # rooflines bound to another configuration's reference by a literal
    # path, other families' mechanisms
    for name in ("attn_fwd_runs_per_bwd", "gdn_scan_roofline_pct",
                 "expert_matmul_roofline_pct", "mla_core_roofline_pct",
                 "attention_ms_step", "attn_core_roofline_pct",
                 "ssd_scan_ms_step", "gdn_scan_ms_step", "gdn_mixer_ms_step",
                 "gdn_fwd_runs_per_bwd", "mtp_ms_step",
                 "train_metric_ms_step", "dispatch_gap_ms_step",
                 "moe_latent_proj_ms_step", "attn_window_core_ms_step"):
        assert CELL not in by_name[name]["workloads"], name
    assert sum(CELL in m.get("workloads", []) for m in bench["per_layer"]
               ) == len(SHARED) + len(NEW_METRICS)


def test_the_mix_is_the_one_that_is_there(config):
    mix = run.load_json(os.path.join(BENCH, "traffic", MIX + ".json"))
    assert mix["documents"] == {"median": 1024, "sigma": 1.2, "min": 16}
    gen = run.load_generator(mix)
    raw = gen.stream(64 * 8192, 8192, 19648, mix["documents"], 7)
    assert raw.max() < 19648 and raw.dtype == np.dtype("<u2")


# ----------------------------------------------------------------------
# picked up by files alone: the cell as BENCHMARK.json has it, rehearsed
@pytest.fixture(scope="module")
def rehearsal():
    res = helpers.run_cell_in_child(
        BENCH, ["--workload", CELL, "--seed", "4900000642", "--seconds", "6",
                "--trace", "1", "--cpu-rehearsal"])
    out = os.path.join(ROOT, "bench_out", CELL, "seed4900000642_trace1")
    with open(os.path.join(out, "compare.json")) as f:
        return res, json.load(f), out


def test_the_program_s_first_chunk_is_the_reference_s(rehearsal):
    """``--cpu-rehearsal`` walks to its end: the CLI trains the conf the
    builder writes and the harness holds its first chunk against
    ``references/bailing_hybrid.py``, float32 on both sides."""
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
    assert conf.count("= kimi_delta:") == 2
    assert conf.count("= latent_attention:") == 1
    assert conf.count("= routed_experts:") == 2
    assert conf.count("  n_group = 4\n  topk_group = 2\n") == 2


def test_the_counters_reach_the_line_and_device_metrics_stay_out(rehearsal):
    res, _, out = rehearsal
    with open(os.path.join(out, "telemetry.jsonl")) as f:
        rounds = [json.loads(line) for line in f if line.strip()]
    assert rounds
    for r in rounds:
        c, steps = r["counters"], r["steps"]
        assert c["tokens"] == steps * 128
        assert c["kda_scan_tokens"] == steps * 128 * 2
        assert c.get("kda_scan_tokens_fused", 0) == 0     # the CPU
        assert c["attn_tokens"] == steps * 128
        assert c["expert_pairs_dropped"] == 0
        # 3 picks of 16 over 2 layers, a quarter of the experts held
        assert 0.3 < c["expert_pairs"] / (steps * 128 * 3 * 2 / 4) < 1.7
    m = res["metrics"]
    if "tokens_per_step" in m:  # a whole round fell inside the window
        assert m["tokens_per_step"]["value"] == 128.0
        assert m["expert_pairs_dropped"]["value"] == 0.0
        assert m["kda_scan_fused_pct"]["value"] == 0.0   # the jax.numpy form
        assert m["attn_flash_pct"]["value"] == 0.0
    # a CPU trace holds no device plane: nothing to read, left out
    for name in ("kda_mixer_ms_step", "kda_scan_ms_step",
                 "kda_scan_roofline_pct", "kda_fwd_runs_per_bwd",
                 "moe_ms_step", "mla_ms_step", "device_step_ms",
                 "mlp_ms_step"):
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
        if name == "loss_gap" and r.get("control_fails") is False:
            # where precision hardly moves the loss (under threefold here)
            # the limit is the accepted cells', over both readings: the why
            assert 4 * r["sound_largest"] < lim[name] == 0.0006
            continue
        assert r["sound_largest"] < lim[name] < r["control_smallest"]
    assert lim["feed_gap_levels"] == 0
    assert got["how"]


def test_seeded_chunk_draws_the_mix_s_documents(ref, config):
    net = ref.describe(run.net_text(config, dict(config["args"]), "tpu"), 1)
    data, labels = ref.seeded_chunk(net, 3, 2)
    assert data.shape == labels.shape == (2, 1, 8192)
    assert data.max() < 19648 and 1 < (data == 0).sum() < 60
    np.testing.assert_array_equal(data[0, 0, 1:], labels[0, 0, :-1])


# ----------------------------------------------------------------------
# the counting functions, at one small shape, against a hand count
SMALL = dict(vocab=50, seq_len=32, hidden=8, num_layers=3, layer_group_size=3,
             first_k_dense=1, attn_heads=2, head_dim=4, kv_lora_rank=6,
             qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=5,
             mlp_hidden=10, num_experts=8, experts_per_tok=2, n_group=2,
             topk_group=1, expert_hidden=12, shared_hidden=7, experts_held=4,
             batch_size=3, dev="cpu")


def test_flops_and_bytes_match_a_hand_count(ref):
    from cxxnet_tpu.models import bailing_hybrid_conf

    net = ref.describe(bailing_hybrid_conf(**SMALL), 3)
    tok, d, h, dk = 3 * 32, 8, 2, 4
    # two KDA layers: 7 Dk Dv a token and head, forward and two gradients
    assert ref.scan_flops(net) == 2 * tok * h * 7 * dk * dk * 3
    ins = 2 * (2 * h * dk + h * dk) + 4 * (h * dk + h)
    out = 2 * h * dk
    assert ref.scan_min_bytes(net) == 2 * tok * ((ins + out) * 2 + ins)
    pairs = 2 * tok * 2 * 4 / 8        # two expert layers, 2 picks, half held
    assert ref.expected_pairs(net) == pairs
    assert ref.expert_flops(net, 10) == 10 * 3 * d * 12 * 2 * 3
    # a pair: 2 heads x (3 + 2 for the score, 5 for the value)
    assert ref.mla_core_flops(net, 100) == 100 * h * 10 * 6
    kda = d * (3 * h * dk + 2 * h * dk) + d * h + h * dk * d   # in, beta, out
    mla = d * h * 5 + d * (6 + 2) + 6 * h * (3 + 5) + h * 5 * d + d * h
    mlp = 3 * d * 10
    moe = 8 * d + 3 * d * 7                          # router, shared expert
    head = d * 50
    core = tok * (32 + 1) / 2 * h * 10
    macs = (tok * (2 * kda + mla + mlp + 2 * moe + head) + core
            + pairs * 3 * d * 12 + ref.scan_flops(net) / 6)
    assert ref.step_flops(net) == pytest.approx(macs * 6)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    # the hidden width out of: the embedding, 3 mixers, 3 feed-forwards,
    # the last norm (8); the head's 50
    assert ref.step_min_bytes(net) == (
        tok * (8 * d + 50) * 2 * 5.0 + params * 4 * 8.0)
    assert set(net.pshapes[1]) == {"wmat", "wbeta", "conv", "a_log",
                                   "dt_bias", "gate_norm", "wproj", "norm"}
    assert set(net.pshapes[5]) == {"wq", "wkva", "kv_norm", "wkvb", "wgate",
                                   "wproj", "norm"}


def test_the_published_size_is_what_the_issue_reckoned(config, ref):
    text = run.net_text(config, dict(config["args"]), "tpu")
    net = ref.describe(text, 1)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    assert params == 767_009_056                     # x 16 B = 12.27 GB
    count = lambda i: sum(int(np.prod(v))  # noqa: E731
                          for v in net.pshapes[i].values())
    # ISSUE 49's table (each with its layer's pre-norm)
    assert count(1) == 63_049_888 + 2560             # a KDA mixer
    assert count(11) == 31_965_696 + 2560            # the latent attention
    assert count(2) == 47_185_920 + 2560             # the dense MLP
    assert count(4) == 54_395_392 + 2560             # routed part, 8 held
    assert count(0) + count(14) == 100_597_760       # embedding + head
    # 8192 x 8 / 512 = 128 pairs a held expert a step
    assert ref.expected_pairs(net) == 5 * 8192 * 8 * 8 / 512
    assert ref.expected_pairs(net) / 5 / 8 == 128.0
    # the rule itself: 4.5e11 operations, 5.7 GB a step
    assert ref.scan_flops(net) == 5 * 8192 * 32 * 7 * 128 * 128 * 3
    assert ref.scan_min_bytes(net) == 5 * 8192 * 139_648
    assert 26.0e12 < ref.step_flops(net) < 26.6e12      # 26.3 TFLOP


# ----------------------------------------------------------------------
# the new readers on a fixture record
EVENTS = [
    # (HLO name, ns, scope) — two traced steps
    ("%fusion.1", 4000, "jit(step)/while/body/jvp(l1_kda0)/in_proj/"
     "dot_general"),
    ("%fusion.2", 600, "jit(step)/while/body/jvp(l1_kda0)/conv/mul"),
    ("%kda_solve.1", 3000, "jit(step)/while/body/jvp(l1_kda0)/scan/cond/"
     "branch_0_fun/jit(_solve)/kda_solve/pallas_call:"),
    ("%kda_scan.1", 2000, "jit(step)/while/body/jvp(l1_kda0)/scan/cond/"
     "branch_0_fun/jit(_scan)/kda_scan/pallas_call:"),
    ("%kda_solve.2", 3000, "jit(step)/while/body/transpose(jvp(l1_kda0))/"
     "jvp(l1_kda0)/checkpoint/rematted_computation/scan/cond/branch_0_fun/"
     "jit(_solve)/kda_solve/pallas_call:"),
    ("%kda_scan.2", 2000, "jit(step)/while/body/transpose(jvp(l1_kda0))/"
     "jvp(l1_kda0)/checkpoint/rematted_computation/scan/cond/branch_0_fun/"
     "jit(_scan)/kda_scan/pallas_call:"),
    ("%kda_scan_bwd.1", 5000, "jit(step)/while/body/transpose(jvp(l1_kda0))/"
     "scan/cond/branch_0_fun/jit(_scan_bwd)/kda_scan_bwd/pallas_call:"),
    ("%fusion.3", 400, "jit(step)/while/body/jvp(l1_kda0)/scan/exp"),
    ("%fusion.4", 1000, "jit(step)/while/body/jvp(l2_mlp0)/dot_general"),
    ("%fusion.5", 1500, "jit(step)/while/body/jvp(l5_mla2)/gate/logistic"),
    ("%flash_fwd.1", 2500, "jit(step)/while/body/jvp(l5_mla2)/core/"
     "flash_fwd/pallas_call:"),
    ("%fusion.6", 700, "jit(step)/while/body/jvp(l4_moe1)/route/"
     "group_limit/top_k"),
    ("%fusion.7", 2000, "jit(step)/while/body/jvp(l8_head)/dot_general"),
    ("%fusion.8", 7000, "jit(step)/while/body/update_adam/sqrt"),
    ("%while.1", 99999, "jit(step)/while"),
    ("%copy.1", 100, None),
]


@pytest.fixture()
def record(tmp_path):
    from cxxnet_tpu.models import bailing_hybrid_conf

    out = str(tmp_path)
    with open(os.path.join(out, "cell.conf"), "w") as f:
        f.write(bailing_hybrid_conf(**SMALL))
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
               {"steps": 24, "counters": {"kda_scan_tokens": 24 * 96 * 2,
                                          "kda_scan_tokens_fused": 24 * 96,
                                          "tokens": 24 * 96}}]}
    scopes._CACHE.pop(out, None)
    stage_scopes._CACHE.pop(out, None)


SCAN_NS = 3000 + 2000 + 3000 + 2000 + 5000 + 400


@pytest.mark.parametrize("name,want", [
    ("kda_mixer_ms_step", (4000 + 600 + SCAN_NS) / 1e6 / 2),
    ("kda_scan_ms_step", SCAN_NS / 1e6 / 2),
    ("kda_scan_fused_pct", 50.0),
    # the readers that were there read the new cell's scopes unchanged
    ("mlp_ms_step", 1000 / 1e6 / 2),
    ("mla_ms_step", (1500 + 2500) / 1e6 / 2),
    ("mla_core_ms_step", 2500 / 1e6 / 2),
    ("moe_route_ms_step", 700 / 1e6 / 2),
    ("head_loss_ms_step", 2000 / 1e6 / 2),
    ("adam_update_ms_step", 7000 / 1e6 / 2),
])
def test_a_reader_reads_its_scope(record, name, want):
    mod = run.load_metric(name)
    assert mod.read(record) == pytest.approx(want)
    assert mod.MOVES == "train_samples_s_chip"


def test_the_scan_s_roofline_share_finds_this_reference(record, ref, config):
    """The reference is found through the cell's configuration file, as
    ``attn_core_roofline_pct`` finds its own: no path in the reader."""
    mod = run.load_metric("kda_scan_roofline_pct")
    assert "REFERENCE" not in vars(mod)
    assert config["reference"] == "benchmarks/references/bailing_hybrid.py"
    net = ref.describe(open(os.path.join(record["out"], "cell.conf")).read(),
                       3)
    least = max(ref.scan_flops(net) / 197e12,
                ref.scan_min_bytes(net) / 819e9)
    assert least == ref.scan_min_bytes(net) / 819e9     # bound by bytes
    assert mod.read(record) == pytest.approx(
        100.0 * least / (SCAN_NS / 1e9 / 2))


def test_the_forward_kernels_are_counted_by_operation():
    mod = run.load_metric("kda_fwd_runs_per_bwd")
    assert mod.runs_per_bwd(EVENTS) == 2.0        # both run again
    kept = [e for e in EVENTS if e[0] not in ("%kda_solve.2",)]
    assert mod.runs_per_bwd(kept) == 1.5          # solve's output kept
    once = [e for e in kept if e[0] != "%kda_scan.2"]
    assert mod.runs_per_bwd(once) == 1.0
    assert mod.runs_per_bwd([e for e in EVENTS if "kda_" not in e[0]]) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name, tmp_path):
    """An untraced run, a run whose directory is not there, a traced run
    of a program without the layer (the parent commit under this PR's
    benchmark files): ``None``, never an exception."""
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
        rec = dict(bare, out=out, trace={"steps": 2, "busy_s": 1.0},
                   peaks={"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
                   workload="qwen3_next_80b_a3b_train_packed8k")
        assert mod.read(rec) is None
        assert run.load_metric("moe_ms_step").read(rec) == 0.002
    finally:
        scopes._CACHE.pop(out, None)
        stage_scopes._CACHE.pop(out, None)
