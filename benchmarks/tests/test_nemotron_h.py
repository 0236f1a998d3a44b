"""The configuration of ISSUE 40 (``nemotron_3_super_120b_a12b``), its
cell, reference and metric readers, on the CPU: picked up by files
alone, the program's first chunk against the reference at rehearsal size
(whole model and share), the float8 control failing a limit there with
the router and its bias left in float32, the selection bias changing
the chosen experts, each new reader on a fixture record, the counting
functions against a hand count."""

from __future__ import annotations

import json
import os
import re
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

CELL = "nemotron_3_super_120b_a12b_train_packed8k"
CONFIG = "nemotron_3_super_120b_a12b"
NEW_METRICS = ["moe_latent_proj_ms_step", "latent_expert_matmul_roofline_pct",
               "ssd_scan_grouped_roofline_pct"]
SHARED = ["device_step_ms", "compiles_in_window", "device_mfu_pct",
          "device_idle_pct", "peak_hbm_gib", "xla_compile_s",
          "jax_trace_lower_s", "iter_init_s", "loop_next_ms_step",
          "loop_copy_ms_step", "loop_stack_ms_step", "loop_self_ms_step",
          "h2d_enqueue_ms_step", "scan_dispatch_ms_step",
          "device_wait_ms_step", "chunk_recycled_pct", "chunk_overlap_pct",
          "ssd_scan_ms_step", "mamba_mixer_ms_step", "attention_ms_step",
          "head_loss_ms_step", "adam_update_ms_step", "tokens_per_step",
          "packed_docs_per_seq", "moe_ms_step", "moe_route_dispatch_ms_step",
          "expert_matmul_ms_step", "expert_pairs_per_expert",
          "expert_load_max_over_mean", "expert_pairs_dropped",
          "expert_dispatch_compact_pct", "attn_flash_pct",
          "loop_device_step_ms", "loop_device_idle_pct",
          "round_head_ms_step", "h2d_tail_ms_step", "chunk_starved_pct"]
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "mamba_num_heads", "n_groups", "num_attention_heads",
           "num_key_value_heads", "num_nextn_predict_layers"]


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
    what ``reduced`` lists differs, and no width is among it: counts
    of layers, experts, ids, heads and groups, and the prediction
    module's depth.  The file states what runs — the shared expert is
    5376 wide in the file AND in the conf the cell trains."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "hybrid_override_pattern":
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
        "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 22, "num_hidden_layers": 88,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    differs = [k for k in REDUCED if config[k] != published[k]]
    assert sorted(k for k, v in published.items() if config[k] != v) == \
        sorted(REDUCED) and differs == config["reduced"] == REDUCED
    assert [config[k] for k in REDUCED] == [11, 8, 16384, 16, 1, 4, 1, 0]
    for key in REDUCED:     # a count, never a width (the contract's list)
        assert not re.search(r"hidden_size|intermediate|latent|state|proj|"
                             r"_dim$|_rank$|expand|per_tok", key)
    assert config["published"] == {k: published[k] for k in REDUCED}
    # an eighth of the vocabulary and of every divided layer's heads,
    # 1 of 8 stages, 8 of 512 experts: the floors
    for key, ranks in (("vocab_size", 8), ("mamba_num_heads", 8),
                       ("n_groups", 8), ("num_attention_heads", 8),
                       ("num_hidden_layers", 8), ("n_routed_experts", 64)):
        assert ranks * config[key] == published[key]
    pattern = published["hybrid_override_pattern"]
    assert len(pattern) == 88
    a = config["args"]
    # the first stage is the published pattern's first 11 letters, and
    # every 11-layer stage holds the same 5 : 1 : 5
    assert a["pattern"] == pattern[:config["num_hidden_layers"]]
    assert all(sorted(pattern[i:i + 11]) == sorted(a["pattern"])
               for i in range(0, 88, 11))
    assert (a["pattern"].count("M"), a["pattern"].count("*"),
            a["pattern"].count("E")) == (5, 1, 5)
    assert a["mtp_pattern"] == config["mtp_hybrid_override_pattern"]
    # the module is cut from the cell (it and the whole shared experts
    # do not fit together) and kept at rehearsal size
    assert a["num_nextn_predict_layers"] == \
        config["num_nextn_predict_layers"] == 0
    assert config["rehearsal_args"]["num_nextn_predict_layers"] == 1
    # every width as published
    assert (a["hidden"], a["mamba_head_dim"], a["mamba_state"],
            a["mamba_conv"], a["mamba_chunk"], a["head_dim"],
            a["latent_hidden"], a["expert_hidden"], a["num_experts"],
            a["experts_per_tok"], a["routed_scaling_factor"], a["eps"]) == (
        4096, 64, 128, 4, 128, 128, 1024, 2688, 512, 22, 5.0, 1e-5)
    for arg, key in (
            ("hidden", "hidden_size"), ("mamba_head_dim", "mamba_head_dim"),
            ("mamba_state", "ssm_state_size"), ("mamba_conv", "conv_kernel"),
            ("mamba_chunk", "chunk_size"), ("head_dim", "head_dim"),
            ("latent_hidden", "moe_latent_size"),
            ("expert_hidden", "moe_intermediate_size"),
            ("experts_per_tok", "num_experts_per_tok"),
            ("routed_scaling_factor", "routed_scaling_factor"),
            ("eps", "norm_eps")):
        assert a[arg] == config[key] == published[key], arg
    assert a["num_experts"] == published["n_routed_experts"]
    # the share, in the layers' own keys
    assert (a["vocab"], a["mamba_heads"], a["mamba_groups"], a["attn_heads"],
            a["attn_kv_heads"], a["experts_held"], a["first_expert"]) == (
        16384, 16, 1, 4, 1, 8, 0)
    # the shared expert runs at the width the file states: whole
    assert a["shared_hidden"] == \
        config["moe_shared_expert_intermediate_size"] == \
        published["moe_shared_expert_intermediate_size"] == 5376
    assert "held_of_published" not in config
    # the mixers' inner width is expand x hidden, an eighth of it here
    assert config["expand"] * config["hidden_size"] == \
        published["mamba_num_heads"] * config["mamba_head_dim"]
    assert "shared expert" in config["assumed"]["replicated"]
    for key in ("positions", "init", "norm", "optimizer", "router_gradient",
                "score_bias", "group_limit", "replicated", "mtp_loss_weight",
                "eh_proj_order", "mtp_block", "documents", "row_order"):
        assert config["assumed"][key]
    assert "rescale_prenorm_residual" in config["assumed"]["init"]
    for word in ("8 pipeline stages", "64 chips", "rank 0", "FOLDED",
                 "PARTIAL SUM"):
        assert word in config["deployment"]
    text = run.net_text(config, dict(a), "tpu")
    assert text.count("= mamba2:") == 5
    assert text.count("= attention:") == 1 and "mtp_" not in text
    assert text.count("= routed_experts:") == 5
    assert "gated_mlp" not in text and "rotary" not in text
    assert text.count("expert_act = relu2") == 5
    assert text.count("shared_hidden = 5376") == 5
    for key in ("rank", "ranks", "tp_", "ep_"):          # no key for the
        assert f"\n  {key}" not in text                  # absent chips


def test_the_cell_is_the_one_the_issue_names():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_packed8k", 1)
    assert len(cell["why"]) <= 200 and "352 pairs" in cell["why"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 5 and len(bench["configs"]) >= 6
    entry = bench["configs"][5]
    assert entry["name"] == CONFIG and entry["reduced"] == REDUCED
    config = run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] and "nvidia" in \
        entry["source"]
    assert config["reference"] == "benchmarks/references/nemotron_h.py"
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"][0] == CELL
        assert by_name[name]["moves"] == "train_samples_s_chip"
        mod = run.load_metric(name)
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            by_name[name]["unit"], by_name[name]["source"],
            by_name[name]["layer"], by_name[name]["moves"])
    for name in SHARED:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["workloads"].index(CELL) == len(
            [w for w in by_name[name]["workloads"]
             if names.index(w) < 5])
    # the two roofline readers that name another family's reference by a
    # literal path cannot read this conf (PERF.md section 7); this net
    # has no MLP, no latent attention, no delta rule, and in the cell no
    # prediction module
    for name in ("ssd_scan_roofline_pct", "expert_matmul_roofline_pct",
                 "mlp_ms_step", "mla_ms_step", "gdn_scan_ms_step",
                 "mtp_ms_step",
                 "train_metric_ms_step", "dispatch_gap_ms_step"):
        assert CELL not in by_name[name]["workloads"]
    # the new entries are the last three
    assert [m["name"] for m in bench["per_layer"]][-3:] == NEW_METRICS


# ----------------------------------------------------------------------
# picked up by files alone: the cell as BENCHMARK.json has it, rehearsed
@pytest.fixture(scope="module")
def rehearsal():
    res = helpers.run_cell_in_child(
        BENCH, ["--workload", CELL, "--seed", "4100000640", "--seconds", "8",
                "--trace", "1", "--cpu-rehearsal"])
    out = os.path.join(ROOT, "bench_out", CELL, "seed4100000640_trace1")
    with open(os.path.join(out, "compare.json")) as f:
        return res, json.load(f), out


def test_the_program_s_first_chunk_is_the_reference_s(rehearsal):
    """``--cpu-rehearsal`` walks to its end: the CLI trains the conf the
    builder writes — a mixer in two groups, attention, two latent expert
    layers, the module, both losses — and the harness holds its first
    chunk against ``references/nemotron_h.py``, float32 on both sides:
    the losses, adam's first moment (the first gradient as the updater
    sees it) and the parameters after."""
    res, nums, out = rehearsal
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert nums["loss_gap"] < 1e-5 and nums["update_norm_gap"] < 1e-4
    assert nums["dparam_norm_gap"] < 1e-3
    assert nums["feed_gap_levels"] == 0 and nums["rows"] == 8
    first = nums["losses_reference"][0]
    assert 1.2 * np.log(64) < first < 1.45 * np.log(64)
    conf = open(os.path.join(out, "cell.conf")).read()
    assert "iter = tokens" in conf and "eval_train = 0" in conf
    assert "updater = adam" in conf and "remat = 1" in conf
    assert "ngroup = 2" in conf and "latent_hidden = 32" in conf
    assert conf.count("= routed_experts:") == 3 and "shared[head]" in conf


def test_the_counters_reach_the_line_and_device_metrics_stay_out(rehearsal):
    res, _, out = rehearsal
    with open(os.path.join(out, "telemetry.jsonl")) as f:
        rounds = [json.loads(line) for line in f if line.strip()]
    assert rounds
    for r in rounds:
        c, steps = r["counters"], r["steps"]
        assert c["tokens"] == steps * 128
        # 128 tokens x 3 picks, 4 of 16 held: 24 pairs an expert, a
        # step and layer (three expert layers) under an even router
        assert 8.0 < c["expert_pairs"] / steps / 3 / 4 < 72.0
        assert c["expert_pairs_dropped"] == 0
        # two attention layers count their tokens; none by the kernels
        assert c["attn_tokens"] == steps * 128 * 2
        assert c.get("attn_tokens_flash", 0) == 0
    m = res["metrics"]
    if "tokens_per_step" in m:  # a whole round fell inside the window
        assert m["tokens_per_step"]["value"] == 128.0
        assert m["expert_pairs_dropped"]["value"] == 0.0
    # a CPU trace holds no device plane: nothing to read, left out
    for name in NEW_METRICS + ["device_step_ms", "moe_ms_step",
                               "ssd_scan_ms_step"]:
        assert name not in m
    assert "device_wait_ms_step" in m and "chunk_overlap_pct" in m


# ----------------------------------------------------------------------
@pytest.mark.parametrize("share", ["whole", "rank"])
def test_a_chunk_through_the_trainer_is_the_reference_s(config, ref, share):
    """The program's own scanned step against the reference on seeded
    weights, in float32, away from the harness: the WHOLE model at a
    small size (every head, group, column and expert) and one rank's
    SHARE of it, which the reference is handed in the same keys."""
    import jax

    from benchmarks.lib import reference
    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.nnet.trainer import NetTrainer

    args = dict(config["args"], **config["rehearsal_args"])
    if share == "whole":
        args.update(experts_held=args["num_experts"])
    else:
        args.update(mamba_heads=2, mamba_groups=1, attn_heads=2,
                    attn_kv_heads=1, shared_hidden=12, first_expert=4)
    text = run.net_text(config, args, "cpu")
    net = ref.describe(text, 1)
    tr = NetTrainer()
    tr.set_params(cfgmod.split_sections(
        cfgmod.parse_pairs(text)).global_entries)
    tr.set_param("silent", "1")
    tr.init_model()
    made = ref.make_weights(net, 40)
    tr.params = {key: {t: made[run.param_index(key)][t] for t in tags}
                 for key, tags in tr.params.items()}
    tr._place_state()
    data, labels = ref.seeded_chunk(net, 41, 4)
    losses = np.asarray(tr.update_scan(data, labels), np.float64)
    prog = {"losses": losses,
            "params": {run.param_index(k): v for k, v in
                       jax.device_get(tr.params).items()},
            "momentum": ref.program_update_state(
                {run.param_index(k): v for k, v in
                 jax.device_get(tr.ustates).items()})}
    start = jax.device_get(ref.make_weights(net, 40))
    l, p, m = ref.train_chunk(net, ref.make_weights(net, 40), data, labels,
                              None)
    nums = reference.compare_chunk(
        prog, {"losses": l, "params": p, "momentum": m}, start)
    assert nums["loss_gap"] < 1e-5, nums
    assert nums["update_norm_gap"] < 1e-4, nums
    assert nums["dparam_norm_gap"] < 1e-3, nums
    # a whole layer's router learns; a share's stays the seed's
    moe = next(lay["index"] for lay in net.layers
               if lay["type"] == "routed_experts")
    moved = not np.array_equal(np.asarray(p[moe]["wgate"]),
                               start[moe]["wgate"])
    assert moved == (share == "whole")


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


def test_the_router_and_its_bias_stay_float32_under_the_control(ref, toy):
    import inspect

    import jax.numpy as jnp

    _, net = toy
    lay = next(l for l in net.layers if l["type"] == "routed_experts")
    w = ref.make_weights(net, 3)[lay["index"]]
    x = jnp.asarray(np.random.RandomState(0).randn(40, net.hidden),
                    jnp.float32)
    wts, idx = ref.router(w, x, lay["cfg"])
    assert idx.shape == (40, 3)
    assert np.allclose(np.asarray(wts).sum(-1), 5.0, atol=1e-5)
    # chosen by score + bias, weighed by the score alone
    s = 1 / (1 + np.exp(-np.asarray(x @ w["wgate"].T, np.float64)))
    chosen = np.argsort(-(s + np.asarray(w["score_bias"])), axis=1,
                        kind="stable")[:, :3]
    assert np.array_equal(np.asarray(idx), chosen)
    picked = np.take_along_axis(s, chosen, axis=1)
    np.testing.assert_allclose(
        wts, 5.0 * picked / picked.sum(1, keepdims=True), rtol=1e-5)
    assert "quant" not in inspect.signature(ref.router).parameters
    assert w["score_bias"].dtype == jnp.float32


def test_the_seed_s_bias_changes_the_chosen_twenty_two(config, ref):
    """At the published router (512 experts, top-22, hidden 4096) and
    the seed's weights the bias drawn from the seed changes the chosen
    22 of nine tokens in ten: a program that drops it is not
    ``correct``."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(5)
    wgate = jax.random.normal(key, (512, 4096), jnp.float32) * 0.02
    bias = jax.random.normal(jax.random.fold_in(key, 1), (512,),
                             jnp.float32) * ref.BIAS_SIGMA
    x = jax.random.normal(jax.random.fold_in(key, 2), (512, 4096),
                          jnp.float32)               # a normed input
    cfg = {"nexpert": "512", "topk": "22", "nhidden": "2688",
           "score_func": "sigmoid", "select_bias": "1",
           "routed_scale": "5.0"}
    p = {"wgate": wgate, "score_bias": bias}
    _, with_bias = ref.router(p, x, cfg)
    _, without = ref.router(dict(p, score_bias=0 * bias), x, cfg)
    changed = (np.sort(np.asarray(with_bias), axis=1)
               != np.sort(np.asarray(without), axis=1)).any(axis=1).mean()
    assert changed > 0.9, changed
    assert "normal(0, 0.01)" in config["assumed"]["score_bias"]
    assert ref.BIAS_SIGMA == 0.01


# ----------------------------------------------------------------------
# the counting functions, at one small shape, against a hand count
def test_flops_and_bytes_match_a_hand_count(ref):
    from cxxnet_tpu.models import nemotron_h_conf

    text = nemotron_h_conf(
        vocab=50, seq_len=32, hidden=8, pattern="M*E", mamba_heads=4,
        mamba_head_dim=3, mamba_groups=2, mamba_state=5, mamba_chunk=8,
        attn_heads=4, attn_kv_heads=2, head_dim=6, num_experts=8,
        experts_per_tok=2, expert_hidden=12, latent_hidden=4,
        shared_hidden=5, experts_held=4, num_nextn_predict_layers=1,
        batch_size=3, dev="cpu")
    net = ref.describe(text, 3)
    tok = 3 * 32
    d, lat, f = 8, 4, 12
    pairs = 2 * tok * 2 * 4 / 8      # two expert layers, 2 picks, half held
    assert ref.expected_pairs(net) == pairs
    # an ungated expert in the latent: up and down, 2 L F a pair
    assert ref.expert_flops(net, 10) == 10 * 2 * lat * f * 2 * 3
    held = 2 * 4 * 2 * lat * f
    assert ref.expert_min_bytes(net, 10) == 2 * (3 * held + 5 * 10 * lat)
    # the recurrence: 4 heads x 5 x 3 x 5 a token, forward and two
    # gradients; x, both groups' B and C, dt in, y out
    assert ref.scan_flops(net) == tok * 4 * 5 * 3 * 5 * 3
    ins = 12 + 2 * 2 * 5 + 4
    assert ref.scan_min_bytes(net) == tok * 2 * (3 * ins + 2 * 12)
    # in and out projections, and the convolution's 4 taps a column
    mixer = (2 * 12 + 2 * 2 * 5 + 4) * d + d * 12 + (12 + 20) * 4
    attn = (4 + 2 * 2) * 6 * d + d * 4 * 6
    core = (32 + 1) / 2 * 2 * 4 * 6
    moe = 8 * d + 2 * d * lat + 2 * d * 5    # router, latent, shared
    head = d * 50
    macs = (tok * (mixer + 2 * (attn + core) + 2 * moe + 2 * head
                   + 2 * d * d)
            + pairs * 2 * lat * f + ref.scan_flops(net) / 6)
    assert ref.step_flops(net) == pytest.approx(macs * 6)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    # the hidden width out of: 2 embeddings, mixer, 2 attentions, 2
    # expert layers, 4 norms and eh_proj (12); the concat twice that;
    # two heads
    assert ref.step_min_bytes(net) == (
        tok * (12 * d + 2 * d + 2 * 50) * 2 * 5.0 + params * 4 * 8.0)
    # a shared layer owns no parameter
    assert sorted(net.pshapes) == [0, 1, 2, 3, 4, 5, 9, 10, 12, 13, 14, 15]


def test_the_published_size_is_what_the_issue_reckoned(config, ref):
    text = run.net_text(config, dict(config["args"]), "tpu")
    net = ref.describe(text, 1)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    assert params == 700_865_520                     # x 16 B = 11.21 GB
    # 22 x 8 / 512 held pairs a token a layer: 352 an expert
    assert ref.expected_pairs(net) == 5 * 8192 * 22 * 8 / 512
    assert ref.expected_pairs(net) / 5 / 8 == 352.0
    # the products of a step: the five whole shared experts 10.8 TFLOP
    # of it (2 x 8192 x 4096 x 5376 MACs a layer, times 6)
    assert 21e12 < ref.step_flops(net) < 25e12
    # the held experts' two products at the expected pairs: 0.47 TFLOP
    assert ref.expert_flops(net, ref.expected_pairs(net)) == pytest.approx(
        5 * 8 * 352 * 2 * 1024 * 2688 * 6)
    assert ref.scan_flops(net) == 5 * 8192 * 16 * 5 * 64 * 128 * 3
    assert net.pshapes[13]["wmat"] == (16384, 4096)
    assert sorted(net.pshapes) == list(range(14))    # no module
    assert net.pshapes[2]["wmat"] == (8, 1024, 2688)
    assert net.pshapes[2]["latent_in"] == (1024, 4096)
    assert net.pshapes[2]["shared_wmat"] == (5376, 4096)


# ----------------------------------------------------------------------
# each new reader on a fixture record
EVENTS = [
    # (HLO name, ns, scope) — two traced steps
    ("%fusion.1", 4000, "jit(step)/while/body/jvp(l1_mixer0)/in_proj/dot"),
    ("%fusion.2", 6000, "jit(step)/while/body/jvp(l1_mixer0)/scan/"
     "vmap(ssd_scan)/dot_general"),
    ("%fusion.3", 10000, "jit(step)/while/body/transpose(jvp(l1_mixer0))/"
     "jvp(l1_mixer0)/checkpoint/rematted_computation/scan/exp"),
    ("%fusion.4", 2500, "jit(step)/while/body/jvp(l2_moe1)/dispatch/sort"),
    ("%fusion.5", 3000, "jit(step)/while/body/jvp(l2_moe1)/latent_in/dot"),
    ("%fusion.6", 5000, "jit(step)/while/body/transpose(jvp(l2_moe1))/"
     "latent_out/dot_general"),
    ("%fusion.7", 1100, "jit(step)/while/body/jvp(l2_moe1)/experts/square"),
    ("%fusion.8", 900, "jit(step)/while/body/jvp(l2_moe1)/shared/dot"),
    ("%ragged-dot-none", 9000, "ragged-dot-none"),
    ("%fusion.9", 2000, "jit(step)/while/body/jvp(l4_head)/dot_general"),
    ("%fusion.10", 1500, "jit(step)/while/body/jvp(l10_mtp_moe1)/latent_in/"
     "dot"),
    ("%fusion.11", 7000, "jit(step)/while/body/update_adam/sqrt"),
    ("%while.1", 99999, "jit(step)/while"),
    ("%copy.1", 100, None),
]
MIX_CFG = """  nhead = 4
  head_dim = 3
  ngroup = 1
  nstate = 5
"""
MOE_CFG = """  nexpert = 8
  topk = 2
  nhidden = 12
  latent_hidden = 4
  expert_act = relu2
  nheld = 4
"""
CONF = ("""netconfig = start
layer[0->h0] = embedding:embed
  nvocab = 50
  nhidden = 8
layer[h0,0->h1] = mamba2:mixer0
""" + MIX_CFG + "layer[h1->h2] = routed_experts:moe1\n" + MOE_CFG + """\
layer[h2->nf] = rms_norm:norm_f
layer[nf->logits] = lm_head:head
  nhidden = 50
layer[logits->logits] = softmax
layer[0->mtp_ids] = token_shift:mtp_shift
layer[mtp_ids->mtp_e] = shared[embed]
layer[mtp_e,h2->mtp_eh] = concat:mtp_cat
layer[mtp_eh->mtp_h0] = fullc:mtp_eh_proj
  nhidden = 8
  no_bias = 1
layer[mtp_h0->mtp_h1] = routed_experts:mtp_moe1
""" + MOE_CFG + """layer[mtp_h1->mtp_logits] = shared[head]
layer[mtp_logits->mtp_logits] = softmax
  target_shift = 1
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
    yield {"out": out, "trace": {"steps": 2, "busy_s": 1.0}, "batch": 3,
           "chips": 1, "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "telemetry": [
               {"steps": 24, "counters": {"expert_pairs": 24 * 90,
                                          "tokens": 24 * 96}},
               {"steps": 24, "counters": {"expert_pairs": 24 * 110,
                                          "tokens": 24 * 96}}]}
    scopes._CACHE.pop(out, None)
    stage_scopes._CACHE.pop(out, None)


@pytest.mark.parametrize("name,want", [
    # both expert layers' projections, the module's among them, forward
    # and backward
    ("moe_latent_proj_ms_step", (3000 + 5000 + 1500) / 1e6 / 2),
    # the readers that were there: the mixer's scan scope under the
    # vmap a grouped scan adds, the grouped products with the kernels
    ("ssd_scan_ms_step", (6000 + 10000) / 1e6 / 2),
    ("mamba_mixer_ms_step", (4000 + 6000 + 10000) / 1e6 / 2),
    ("expert_matmul_ms_step", (1100 + 9000) / 1e6 / 2),
    ("moe_route_dispatch_ms_step", 2500 / 1e6 / 2),
    ("moe_ms_step", (2500 + 3000 + 5000 + 1100 + 900 + 1500 + 9000)
     / 1e6 / 2),
    ("mtp_ms_step", 1500 / 1e6 / 2),
])
def test_a_reader_reads_its_scope(record, name, want):
    mod = run.load_metric(name)
    assert mod.read(record) == pytest.approx(want)
    assert mod.MOVES == "train_samples_s_chip"


def test_the_two_roofline_shares_count_with_this_family_s_reference(
        record, ref):
    net = ref.describe(CONF, 3)
    pairs = 100.0                          # a step: the counter / steps
    least = max(ref.expert_flops(net, pairs) / 197e12,
                ref.expert_min_bytes(net, pairs) / 819e9)
    got = run.load_metric("latent_expert_matmul_roofline_pct").read(record)
    assert got == pytest.approx(100.0 * least / (0.00505 / 1e3))
    least = max(ref.scan_flops(net) / 197e12,
                ref.scan_min_bytes(net) / 819e9)
    got = run.load_metric("ssd_scan_grouped_roofline_pct").read(record)
    assert got == pytest.approx(100.0 * least / (0.008 / 1e3))
    # no counter (a program that counts no pairs), no share
    bare = dict(record, telemetry=[{"steps": 24, "counters": {"tokens": 1}}])
    assert run.load_metric(
        "latent_expert_matmul_roofline_pct").read(bare) is None
    for name in NEW_METRICS[1:]:
        assert run.load_metric(name).REFERENCE == \
            "benchmarks/references/nemotron_h.py"


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
        "tokens": 8 * 8192, "expert_pairs": 8 * 16000}}])
    assert mod.read(counted) is None


def test_a_traced_run_of_another_family_reads_no_latent_projection(tmp_path):
    """An expert layer without a latent (the two accepted expert cells,
    traced): the scopes are read and nothing is found under the two."""
    out = str(tmp_path)
    with open(os.path.join(out, "cell.conf"), "w") as f:
        f.write("netconfig = start\nlayer[0->h0] = embedding:embed\n"
                "layer[h0->h1] = routed_experts:moe0\n  nheld = 4\n"
                "netconfig = end\n")
    events = [("%fusion.1", 4000,
               "jit(step)/while/body/jvp(l1_moe0)/route/dot")]
    got = scopes.reduce_events(events)
    text, layers = scopes.conf_layers(out)
    got.update(conf=text, out=out,
               types={i: k for i, (k, _) in enumerate(layers)})
    scopes._CACHE[out] = got
    stage_scopes._CACHE[out] = stage_scopes.reduce_parts(events)
    rec = {"out": out, "trace": {"steps": 2, "busy_s": 1.0}, "batch": 1,
           "chips": 1, "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "telemetry": [{"steps": 8, "counters": {"expert_pairs": 99}}]}
    try:
        assert run.load_metric("moe_latent_proj_ms_step").read(rec) is None
        assert run.load_metric(
            "ssd_scan_grouped_roofline_pct").read(rec) is None
        assert run.load_metric("moe_route_dispatch_ms_step").read(
            rec) == 0.002
    finally:
        scopes._CACHE.pop(out, None)
        stage_scopes._CACHE.pop(out, None)
