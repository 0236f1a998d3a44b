"""The configuration of ISSUE 36 (``joyai_llm_flash``), its cell,
reference and metric readers, on the CPU: picked up by files alone,
the program's first chunk against the reference at rehearsal size, the
float8 control failing a limit there with the router and its bias left
in float32, the selection bias changing the chosen experts, each new
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

CELL = "joyai_llm_flash_train_packed8k"
CONFIG = "joyai_llm_flash"
NEW_METRICS = ["mla_ms_step", "mla_core_ms_step", "mla_core_roofline_pct",
               "mtp_ms_step"]
SHARED = ["device_step_ms", "compiles_in_window", "device_mfu_pct",
          "device_idle_pct", "peak_hbm_gib", "xla_compile_s",
          "jax_trace_lower_s", "iter_init_s", "loop_next_ms_step",
          "loop_copy_ms_step", "loop_stack_ms_step", "loop_self_ms_step",
          "h2d_enqueue_ms_step", "scan_dispatch_ms_step",
          "device_wait_ms_step", "chunk_recycled_pct", "chunk_overlap_pct",
          "mlp_ms_step", "head_loss_ms_step", "adam_update_ms_step",
          "tokens_per_step", "packed_docs_per_seq", "moe_ms_step",
          "moe_route_dispatch_ms_step", "expert_matmul_ms_step",
          "expert_pairs_per_expert", "expert_load_max_over_mean",
          "expert_pairs_dropped"]


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
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 16, 16160)
    assert config["published"] == {k: published[k] for k in differs}
    assert 8 * config["vocab_size"] == published["vocab_size"]
    assert 16 * config["n_routed_experts"] == published["n_routed_experts"]
    a = config["args"]
    # the leading dense layer once and four of those that follow, the
    # router at its width, the module there
    assert (a["num_layers"], a["first_k_dense"]) == (
        config["num_hidden_layers"], config["first_k_dense_replace"])
    assert a["num_layers"] - a["first_k_dense"] >= 4
    assert a["num_nextn_predict_layers"] == \
        config["num_nextn_predict_layers"] == 1
    assert (a["hidden"], a["vocab"], a["mlp_hidden"], a["num_experts"],
            a["experts_held"], a["experts_per_tok"], a["expert_hidden"],
            a["shared_hidden"], a["routed_scaling_factor"]) == (
        2048, 16160, 7168, 256, 16, 8, 768, 768, 2.5)
    assert (a["attn_heads"], a["q_lora_rank"], a["kv_lora_rank"],
            a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"],
            a["rope_theta"], a["rope_interleave"], a["eps"]) == (
        32, 1536, 512, 128, 64, 128, 3.2e7, 1, 1e-6)
    assert a["qk_nope_head_dim"] + a["qk_rope_head_dim"] == \
        config["qk_head_dim"]
    for key in ("init", "norm", "optimizer", "router_gradient", "score_bias",
                "group_limit", "mtp_loss_weight", "eh_proj_order",
                "documents", "training_form", "row_order"):
        assert config["assumed"][key]
    assert "16" in config["deployment"] and "rank 0" in config["deployment"]
    assert "FOLDED" in config["deployment"]
    text = run.net_text(config, dict(a), "tpu")
    assert text.count("= latent_attention:") == 6       # the module's too
    assert text.count("= routed_experts:") == 5
    assert text.count("= gated_mlp:") == 1 and "mlp0" in text


def test_the_cell_is_the_one_the_issue_names():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_packed8k", 1)
    assert len(cell["why"]) <= 200 and "256 pairs" in cell["why"]
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 4 and len(bench["configs"]) >= 5
    entry = bench["configs"][4]
    assert entry["name"] == CONFIG and entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    config = run.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["source"] == config["source"] and "jdopensource" in \
        entry["source"]
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
             if names.index(w) < 4])
    # attention_ms_step reads conf type attention, which this net has
    # not; expert_matmul_roofline_pct's reader names qwen3_next's
    # reference, which cannot read this conf (PERF.md section 7)
    for name in ("attention_ms_step", "expert_matmul_roofline_pct",
                 "ssd_scan_ms_step", "gdn_scan_ms_step",
                 "train_metric_ms_step", "dispatch_gap_ms_step"):
        assert CELL not in by_name[name]["workloads"]


# ----------------------------------------------------------------------
# picked up by files alone: the cell as BENCHMARK.json has it, rehearsed
@pytest.fixture(scope="module")
def rehearsal():
    res = helpers.run_cell_in_child(
        BENCH, ["--workload", CELL, "--seed", "4100000636", "--seconds", "8",
                "--trace", "1", "--cpu-rehearsal"])
    out = os.path.join(ROOT, "bench_out", CELL, "seed4100000636_trace1")
    with open(os.path.join(out, "compare.json")) as f:
        return res, json.load(f), out


def test_the_program_s_first_chunk_is_the_reference_s(rehearsal):
    """``--cpu-rehearsal`` walks to its end: the CLI trains the conf the
    builder writes — both losses, the shared embedding and head — and
    the harness holds its first chunk against
    ``references/joyai_llm_flash.py``, float32 on both sides."""
    res, nums, out = rehearsal
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert nums["loss_gap"] < 1e-5 and nums["update_norm_gap"] < 1e-4
    assert nums["dparam_norm_gap"] < 1e-3
    assert nums["feed_gap_levels"] == 0 and nums["rows"] == 8
    # the sum of both loss layers: ln 64 for the main one and 0.3 of a
    # like loss for the module
    first = nums["losses_reference"][0]
    assert 1.2 * np.log(64) < first < 1.45 * np.log(64)
    conf = open(os.path.join(out, "cell.conf")).read()
    assert "iter = tokens" in conf and "eval_train = 0" in conf
    assert "updater = adam" in conf and "remat = 1" in conf
    assert conf.count("= latent_attention:") == 3
    assert conf.count("= routed_experts:") == 2 and "shared[head]" in conf


def test_the_counters_reach_the_line_and_device_metrics_stay_out(rehearsal):
    res, _, out = rehearsal
    with open(os.path.join(out, "telemetry.jsonl")) as f:
        rounds = [json.loads(line) for line in f if line.strip()]
    assert rounds
    for r in rounds:
        c, steps = r["counters"], r["steps"]
        assert c["tokens"] == steps * 128
        # documents of a median of 24 tokens in rows of 128: far fewer
        # pairs than the row as one document has, more than a token each
        assert steps * 128 < c["attn_pairs"] < steps * 128 * 129 // 2
        # 128 tokens x 3 picks, 4 of 16 held: 24 pairs an expert, a
        # step and layer (two expert layers) under an even router
        assert 8.0 < c["expert_pairs"] / steps / 2 / 4 < 72.0
        assert c["expert_pairs_dropped"] == 0
    m = res["metrics"]
    if "tokens_per_step" in m:  # a whole round fell inside the window
        assert m["tokens_per_step"]["value"] == 128.0
        assert m["expert_pairs_dropped"]["value"] == 0.0
    # a CPU trace holds no device plane: nothing to read, left out
    for name in NEW_METRICS + ["device_step_ms", "moe_ms_step",
                               "mlp_ms_step"]:
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
    assert np.allclose(np.asarray(wts).sum(-1), 2.5, atol=1e-5)
    # chosen by score + bias, weighed by the score alone
    s = 1 / (1 + np.exp(-np.asarray(x @ w["wgate"].T, np.float64)))
    chosen = np.argsort(-(s + np.asarray(w["score_bias"])), axis=1,
                        kind="stable")[:, :3]
    assert np.array_equal(np.asarray(idx), chosen)
    picked = np.take_along_axis(s, chosen, axis=1)
    np.testing.assert_allclose(
        wts, 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-5)
    assert "quant" not in inspect.signature(ref.router).parameters
    assert w["score_bias"].dtype == jnp.float32


def test_the_seed_s_bias_changes_the_chosen_eight(config, ref):
    """At the published router (256 experts, top-8, hidden 2048) and
    the seed's weights the bias drawn from the seed changes the chosen
    eight of more than a tenth of the tokens: a program that drops it
    is not ``correct``."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(5)
    wgate = jax.random.normal(key, (256, 2048), jnp.float32) * 0.02
    bias = jax.random.normal(jax.random.fold_in(key, 1), (256,),
                             jnp.float32) * ref.BIAS_SIGMA
    x = jax.random.normal(jax.random.fold_in(key, 2), (512, 2048),
                          jnp.float32)               # a normed input
    cfg = {"nexpert": "256", "topk": "8", "nhidden": "768",
           "score_func": "sigmoid", "select_bias": "1",
           "routed_scale": "2.5"}
    p = {"wgate": wgate, "score_bias": bias}
    _, with_bias = ref.router(p, x, cfg)
    _, without = ref.router(dict(p, score_bias=0 * bias), x, cfg)
    changed = (np.sort(np.asarray(with_bias), axis=1)
               != np.sort(np.asarray(without), axis=1)).any(axis=1).mean()
    assert changed > 0.1, changed
    assert "normal(0, 0.01)" in config["assumed"]["score_bias"]
    assert ref.BIAS_SIGMA == 0.01


# ----------------------------------------------------------------------
# the counting functions, at one small shape, against a hand count
def test_flops_and_bytes_match_a_hand_count(ref):
    from cxxnet_tpu.models import joyai_llm_flash_conf

    text = joyai_llm_flash_conf(
        vocab=50, seq_len=32, hidden=8, num_layers=2, attn_heads=2,
        q_lora_rank=6, kv_lora_rank=4, qk_nope_head_dim=4,
        qk_rope_head_dim=2, v_head_dim=3, mlp_hidden=10, num_experts=8,
        experts_per_tok=2, expert_hidden=12, shared_hidden=5,
        experts_held=4, batch_size=3, dev="cpu")
    net = ref.describe(text, 3)
    tok = 3 * 32
    d, h = 8, 2
    pairs = 2 * tok * 2 * 4 / 8      # two expert layers, 2 picks, half held
    assert ref.expected_pairs(net) == pairs
    assert ref.expert_flops(net, 10) == 10 * 3 * d * 12 * 2 * 3
    held = 2 * 4 * 3 * d * 12
    assert ref.expert_min_bytes(net, 10) == 2 * (3 * held + 5 * 10 * d)
    # three latent-attention layers: a head 4 + 2 for the scores, 3 for
    # the values
    assert ref.mla_core_flops(net, 100) == 100 * 3 * h * 9 * 2 * 3
    mla = d * 6 + 6 * h * 6 + d * (4 + 2) + 4 * h * 7 + h * 3 * d
    core = (32 + 1) / 2 * h * 9
    moe = 8 * d + 3 * d * 5                  # router, the shared expert
    mlp = 3 * d * 10
    head = d * 50
    macs = tok * (3 * (mla + core) + 2 * moe + mlp + 2 * head + 2 * d * d
                  ) + pairs * 3 * d * 12
    assert ref.step_flops(net) == pytest.approx(macs * 6)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    # the hidden width out of: 2 embeddings, 3 mixers, the MLP, 2 expert
    # layers, 4 norms and eh_proj (13); the concat twice that; two heads
    assert ref.step_min_bytes(net) == (
        tok * (13 * d + 2 * d + 2 * 50) * 2 * 5.0 + params * 4 * 8.0)
    # a shared layer owns no parameter
    assert sorted(net.pshapes) == [0, 1, 2, 3, 4, 5, 6, 10, 11, 13, 14, 15,
                                   16]


def test_the_published_size_is_what_the_issue_reckoned(config, ref):
    text = run.net_text(config, dict(config["args"]), "tpu")
    net = ref.describe(text, 1)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    assert params == 680_441_088                     # x 16 B = 10.89 GB
    # 0.5 held pairs a token a layer: 256 an expert
    assert ref.expected_pairs(net) == 5 * 8192 * 8 * 16 / 256
    assert ref.expected_pairs(net) / 5 / 16 == 256.0
    assert 27.5e12 < ref.step_flops(net) < 28.2e12
    # the row one document: 32 heads x 320 x 8192 x 8193 / 2 x 6 layers
    assert ref.mla_core_flops(net, 8192 * 8193 / 2) == pytest.approx(
        12.371e12, rel=1e-3)
    assert net.pshapes[12]["wmat"] == (16160, 2048)
    assert net.pshapes[19]["wmat"] == (2048, 4096)   # eh_proj


# ----------------------------------------------------------------------
# each new reader on a fixture record
EVENTS = [
    # (HLO name, ns, scope) — two traced steps
    ("%fusion.1", 4000, "jit(step)/while/body/jvp(l1_mla0)/q_proj/dot"),
    ("%fusion.2", 6000, "jit(step)/while/body/jvp(l1_mla0)/core/"
     "checkpoint/dot_general"),
    ("%fusion.3", 10000, "jit(step)/while/body/transpose(jvp(l1_mla0))/"
     "jvp(l1_mla0)/checkpoint/rematted_computation/core/exp"),
    ("%fusion.4", 1000, "jit(step)/while/body/jvp(l1_mla0)/rotary/mul"),
    ("%fusion.5", 2500, "jit(step)/while/body/jvp(l2_moe0)/dispatch/sort"),
    ("%ragged-dot-none", 9000, "ragged-dot-none"),
    ("%fusion.6", 2000, "jit(step)/while/body/jvp(l4_head)/dot_general"),
    ("%fusion.7", 300, "jit(step)/while/body/jvp(l6_mtp_shift)/pad"),
    ("%fusion.8", 700, "jit(step)/while/body/transpose(jvp(l7_shared))/"
     "scatter-add"),
    ("%fusion.9", 1200, "jit(step)/while/body/jvp(l9_mtp_eh_proj)/dot"),
    ("%fusion.10", 8000, "jit(step)/while/body/jvp(l10_mtp_mla)/core/dot"),
    ("%fusion.11", 900, "jit(step)/while/body/jvp(l10_mtp_mla)/out_proj/dot"),
    ("%fusion.12", 1500, "jit(step)/while/body/jvp(l11_mtp_moe)/route/top_k"),
    ("%fusion.13", 2200, "jit(step)/while/body/jvp(l12_shared)/dot_general"),
    ("%fusion.14", 7000, "jit(step)/while/body/update_adam/sqrt"),
    ("%while.1", 99999, "jit(step)/while"),
    ("%copy.1", 100, None),
]
MLA_CFG = """  nhead = 2
  q_rank = 6
  kv_rank = 4
  nope_dim = 4
  rope_dim = 2
  v_dim = 3
  causal = 1
"""
MOE_CFG = """  nexpert = 8
  topk = 2
  nhidden = 12
  nheld = 4
"""
CONF = ("""netconfig = start
layer[0->h0] = embedding:embed
  nvocab = 50
  nhidden = 8
layer[h0,0->x0] = latent_attention:mla0
""" + MLA_CFG + "layer[x0->h1] = routed_experts:moe0\n" + MOE_CFG + """\
layer[h1->nf] = rms_norm:norm_f
layer[nf->logits] = lm_head:head
  nhidden = 50
layer[logits->logits] = softmax
layer[0->mtp_ids] = token_shift:mtp_shift
layer[mtp_ids->mtp_e] = shared[embed]
layer[mtp_e,h1->mtp_eh] = concat:mtp_cat
layer[mtp_eh->mtp_h0] = fullc:mtp_eh_proj
  nhidden = 8
  no_bias = 1
layer[mtp_h0,0->mtp_x] = latent_attention:mtp_mla
""" + MLA_CFG + "layer[mtp_x->mtp_h1] = routed_experts:mtp_moe\n" + MOE_CFG
        + """layer[mtp_h1->mtp_logits] = shared[head]
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
               {"steps": 24, "counters": {"attn_pairs": 24 * 3 * 200,
                                          "tokens": 24 * 96}},
               {"steps": 24, "counters": {"attn_pairs": 24 * 3 * 240,
                                          "tokens": 24 * 96}}]}
    scopes._CACHE.pop(out, None)
    stage_scopes._CACHE.pop(out, None)


@pytest.mark.parametrize("name,want", [
    # both latent-attention layers, the module's among them
    ("mla_ms_step", (4000 + 6000 + 10000 + 1000 + 8000 + 900) / 1e6 / 2),
    ("mla_core_ms_step", (6000 + 10000 + 8000) / 1e6 / 2),
    # every layer from mtp_shift on, the nameless shared ones by position
    ("mtp_ms_step", (300 + 700 + 1200 + 8000 + 900 + 1500 + 2200) / 1e6 / 2),
    # the readers that were there select by conf type: the main head
    # alone (a shared head is conf type shared[head]), both expert layers
    ("head_loss_ms_step", 2000 / 1e6 / 2),
    ("moe_ms_step", (2500 + 1500 + 9000) / 1e6 / 2),
    ("adam_update_ms_step", 7000 / 1e6 / 2),
])
def test_a_reader_reads_its_scope(record, name, want):
    mod = run.load_metric(name)
    assert mod.read(record) == pytest.approx(want)
    assert mod.MOVES == "train_samples_s_chip"


def test_the_core_s_roofline_share_is_counted_on_the_documents(record, ref):
    net = ref.describe(CONF, 3)
    pairs = 3 * 220.0                     # a step: the counter / steps
    least = ref.mla_core_flops(net, pairs) / 197e12
    assert ref.mla_core_flops(net, pairs) == pairs * 2 * 2 * 9 * 6
    got = run.load_metric("mla_core_roofline_pct").read(record)
    assert got == pytest.approx(100.0 * least / (0.012 / 1e3))
    # no counter (the parent commit's program), no share
    bare = dict(record, telemetry=[{"steps": 24, "counters": {"tokens": 1}}])
    assert run.load_metric("mla_core_roofline_pct").read(bare) is None


def test_the_module_is_found_by_position_and_absent_without_its_names(
        record):
    text, layers = scopes.conf_layers(record["out"])
    assert layers[6] == ("token_shift", "mtp_shift")
    assert layers[7] == ("shared[embed]", "") and layers[12][1] == ""
    plain = CONF[:CONF.index("layer[0->mtp_ids]")] + "netconfig = end\n"
    with open(os.path.join(record["out"], "cell.conf"), "w") as f:
        f.write(plain)
    assert run.load_metric("mtp_ms_step").read(record) is None


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


def test_a_traced_run_of_another_family_reads_none_of_them(tmp_path):
    """A conf without a latent-attention layer or a module (the other
    token cells, traced): the scopes are read and nothing is found."""
    out = str(tmp_path)
    with open(os.path.join(out, "cell.conf"), "w") as f:
        f.write("netconfig = start\nlayer[0->h0] = embedding:embed\n"
                "layer[h0->h1] = gated_mlp:mlp0\nnetconfig = end\n")
    events = [("%fusion.1", 4000, "jit(step)/while/body/jvp(l1_mlp0)/dot")]
    got = scopes.reduce_events(events)
    text, layers = scopes.conf_layers(out)
    got.update(conf=text, out=out,
               types={i: k for i, (k, _) in enumerate(layers)})
    scopes._CACHE[out] = got
    stage_scopes._CACHE[out] = stage_scopes.reduce_parts(events)
    rec = {"out": out, "trace": {"steps": 2, "busy_s": 1.0}, "batch": 1,
           "chips": 1, "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "telemetry": [{"steps": 8, "counters": {"attn_pairs": 99}}]}
    try:
        for name in NEW_METRICS:
            assert run.load_metric(name).read(rec) is None
        assert run.load_metric("mlp_ms_step").read(rec) == 0.002
    finally:
        scopes._CACHE.pop(out, None)
        stage_scopes._CACHE.pop(out, None)
