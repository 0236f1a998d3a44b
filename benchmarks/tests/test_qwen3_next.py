"""The configuration of ISSUE 33 (``qwen3_next_80b_a3b``), its cell,
reference and metric readers, on the CPU: picked up by files alone,
the program's first chunk against the reference at rehearsal size, the
float8 control failing a limit there, each reader on a fixture record,
the counting functions against a hand count."""

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

CELL = "qwen3_next_80b_a3b_train_packed8k"
CONFIG = "qwen3_next_80b_a3b"
NEW_METRICS = ["gdn_mixer_ms_step", "gdn_scan_ms_step",
               "gdn_scan_roofline_pct", "moe_ms_step",
               "moe_route_dispatch_ms_step", "expert_matmul_ms_step",
               "expert_matmul_roofline_pct", "expert_pairs_per_expert",
               "expert_load_max_over_mean", "expert_pairs_dropped"]
SHARED = ["device_step_ms", "compiles_in_window", "device_mfu_pct",
          "device_idle_pct", "peak_hbm_gib", "xla_compile_s",
          "loop_next_ms_step", "loop_copy_ms_step", "loop_stack_ms_step",
          "loop_self_ms_step", "h2d_enqueue_ms_step",
          "scan_dispatch_ms_step", "device_wait_ms_step", "iter_init_s",
          "jax_trace_lower_s", "chunk_recycled_pct", "chunk_overlap_pct",
          "attention_ms_step", "head_loss_ms_step", "adam_update_ms_step",
          "tokens_per_step", "packed_docs_per_seq"]


@pytest.fixture(scope="module")
def config():
    return run.load_json(os.path.join(BENCH, "configs", CONFIG + ".json"))


@pytest.fixture(scope="module")
def ref(config):
    return run.load_reference(config)


@pytest.fixture(scope="module")
def toy(config, ref):
    """(conf text, the reference's reading of it) at rehearsal size."""
    # (the rehearsal below measures for 8 s, not granite's 2: four
    # workers share the cores and a toy chunk can then take 3 s)
    args = dict(config["args"], **config["rehearsal_args"])
    text = run.net_text(config, args, "cpu")
    return text, ref.describe(text, int(args["batch_size"]))


# ----------------------------------------------------------------------
def test_the_configuration_keeps_every_published_number(config):
    """Every key of the catalog's ``config`` under its own name; only
    what ``reduced`` lists differs, and no width is among it."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 32, 18992)
    assert config["published"] == {k: published[k] for k in differs}
    assert 8 * config["vocab_size"] == published["vocab_size"]
    assert 16 * config["num_experts"] == published["num_experts"]
    a = config["args"]
    # one whole period in the published order, the router at its width
    assert a["layer_types"] == "lllf" and len(a["layer_types"]) == \
        config["full_attention_interval"] == config["num_hidden_layers"]
    assert (a["hidden"], a["vocab"], a["num_experts"], a["experts_held"],
            a["experts_per_tok"], a["expert_hidden"], a["shared_hidden"]) == (
        2048, 18992, 512, 32, 10, 512, 512)
    assert (a["linear_key_heads"], a["linear_value_heads"],
            a["linear_key_dim"], a["linear_value_dim"], a["linear_conv"]) == (
        16, 32, 128, 128, 4)
    assert (a["attn_heads"], a["attn_kv_heads"], a["head_dim"],
            a["partial_rotary_factor"], a["rope_theta"], a["eps"]) == (
        16, 2, 256, 0.25, 1e7, 1e-6)
    for key in ("init", "norm", "optimizer", "auxiliary_loss", "documents",
                "row_order"):
        assert config["assumed"][key]
    assert "16" in config["deployment"] and "rank 0" in config["deployment"]


def test_the_cell_is_the_one_the_issue_names():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_packed8k", 1)
    assert len(bench["workloads"]) == 4 and len(bench["configs"]) == 4
    assert all(w["chips"] == 1 for w in bench["workloads"])
    entry = bench["configs"][-1]
    assert entry["name"] == CONFIG and entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_samples_s_chip"
        mod = run.load_metric(name)
        assert (mod.UNIT, mod.SOURCE, mod.LAYER) == (
            by_name[name]["unit"], by_name[name]["source"],
            by_name[name]["layer"])
    for name in SHARED:
        assert by_name[name]["workloads"][-1] == CELL
    for name in ("mlp_ms_step", "ssd_scan_ms_step", "mamba_mixer_ms_step",
                 "train_metric_ms_step", "dispatch_gap_ms_step"):
        assert CELL not in by_name[name]["workloads"]


# ----------------------------------------------------------------------
# picked up by files alone: the cell as BENCHMARK.json has it, rehearsed
@pytest.fixture(scope="module")
def rehearsal():
    res = helpers.run_cell_in_child(
        BENCH, ["--workload", CELL, "--seed", "4100000556", "--seconds", "8",
                "--trace", "1", "--cpu-rehearsal"])
    out = os.path.join(ROOT, "bench_out", CELL, "seed4100000556_trace1")
    with open(os.path.join(out, "compare.json")) as f:
        return res, json.load(f), out


def test_the_program_s_first_chunk_is_the_reference_s(rehearsal):
    """``--cpu-rehearsal`` walks to its end: the CLI trains the conf the
    builder writes, the harness holds its first chunk against
    ``references/qwen3_next.py``, float32 on both sides."""
    res, nums, out = rehearsal
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert nums["loss_gap"] < 1e-5 and nums["update_norm_gap"] < 1e-4
    assert nums["dparam_norm_gap"] < 1e-3
    assert nums["feed_gap_levels"] == 0 and nums["rows"] == 8
    conf = open(os.path.join(out, "cell.conf")).read()
    assert "iter = tokens" in conf and "eval_train = 0" in conf
    assert "updater = adam" in conf and "remat = 1" in conf
    assert conf.count("= routed_experts:") == 2 and "tied" not in conf


def test_the_counters_reach_the_line_and_device_metrics_stay_out(rehearsal):
    res, _, out = rehearsal
    with open(os.path.join(out, "telemetry.jsonl")) as f:
        rounds = [json.loads(line) for line in f if line.strip()]
    assert rounds
    for r in rounds:
        c, steps = r["counters"], r["steps"]
        assert c["tokens"] == steps * 128
        # 128 tokens x 3 picks, 4 of 16 held: 24 pairs an expert, a
        # step and layer (two expert layers) under an even router; a
        # toy router at the seed's weights is not even
        assert 8.0 < c["expert_pairs"] / steps / 2 / 4 < 72.0
        assert 1.0 <= c["expert_pairs_max"] * 4 / c["expert_pairs"] < 4.0
        assert c["expert_pairs_dropped"] == 0
    m = res["metrics"]
    if "tokens_per_step" in m:  # a whole round fell inside the window
        assert m["tokens_per_step"]["value"] == 128.0
        assert m["expert_pairs_dropped"]["value"] == 0.0
    # a CPU trace holds no device plane: nothing to read, left out
    for name in NEW_METRICS[:7] + ["device_step_ms", "attention_ms_step"]:
        assert name not in m
    assert "device_wait_ms_step" in m and "chunk_overlap_pct" in m


# ----------------------------------------------------------------------
# the control: the reference one precision down must come out apart
@pytest.mark.parametrize("seed", [11, 12])
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


def test_the_reference_s_router_is_left_in_float32_by_the_control(ref, toy):
    import jax.numpy as jnp

    _, net = toy
    lay = next(l for l in net.layers if l["type"] == "routed_experts")
    w = ref.make_weights(net, 3)[lay["index"]]
    x = jnp.asarray(np.random.RandomState(0).randn(40, net.hidden),
                    jnp.float32)
    wts, idx = ref.router(w, x, lay["cfg"])
    assert idx.shape == (40, 3) and np.allclose(wts.sum(-1), 1.0, atol=1e-6)
    assert (np.diff(np.asarray(wts), axis=1) <= 0).all()   # largest first
    import inspect
    assert "quant" not in inspect.signature(ref.router).parameters


# ----------------------------------------------------------------------
# the counting functions, at one small shape, against a hand count
def test_flops_and_bytes_match_a_hand_count(ref):
    from cxxnet_tpu.models import qwen3_next_conf

    text = qwen3_next_conf(
        vocab=50, seq_len=32, hidden=8, layer_types="lf", linear_key_heads=1,
        linear_value_heads=2, linear_key_dim=4, linear_value_dim=6,
        attn_heads=2, attn_kv_heads=1, head_dim=10, num_experts=8,
        experts_per_tok=2, expert_hidden=12, shared_hidden=5, experts_held=4,
        batch_size=3, dev="cpu")
    net = ref.describe(text, 3)
    tok = 3 * 32
    d, hk, hv, dk, dv = 8, 1, 2, 4, 6
    scan = tok * hv * 7 * dk * dv * 3
    assert ref.scan_flops(net) == scan
    ins = 2 * hk * dk + hv * dv + 2 * hv
    assert ref.scan_min_bytes(net) == tok * 2 * (
        (ins + hv * dv) * 2 + ins)
    pairs = 2 * tok * 2 * 4 / 8            # two layers, 2 picks, half held
    assert ref.expected_pairs(net) == pairs
    assert ref.expert_flops(net, 10) == 10 * 3 * d * 12 * 2 * 3
    held = 2 * 4 * 3 * d * 12
    assert ref.expert_min_bytes(net, 10) == 2 * (3 * held + 5 * 10 * d)
    gdn = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv + hv * dv * d
    attn = d * (2 * 20 + 2 * 10) + 20 * d + 2 * (32 + 1) / 2 * 20
    moe = 2 * (8 * d + 3 * d * 5 + d)      # router, shared expert, its gate
    head = d * 50
    macs = tok * (gdn + attn + moe + head) + pairs * 3 * d * 12
    assert ref.step_flops(net) == pytest.approx(macs * 6 + scan)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    # embedding, 2 mixers, 2 expert layers and the last norm put out the
    # hidden width, the head the vocabulary
    assert ref.step_min_bytes(net) == (
        tok * (6 * d + 50) * 2 * 5.0 + params * 4 * 8.0)


def test_the_published_size_is_what_the_issue_reckoned(config, ref):
    text = run.net_text(config, dict(config["args"]), "tpu")
    net = ref.describe(text, 1)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    assert round(params / 1e6, 1) == 625.7           # x 16 B = 10.01 GB
    # 0.625 held pairs a token a layer: 160 an expert
    assert ref.expected_pairs(net) == 4 * 8192 * 10 * 32 / 512
    assert ref.expected_pairs(net) / 4 / 32 == 160.0
    assert 10e12 < ref.step_flops(net) < 20e12
    assert net.pshapes[net.layers[-2]["index"]]["wmat"] == (18992, 2048)


# ----------------------------------------------------------------------
# each new reader on a fixture record
EVENTS = [
    # (HLO name, ns, scope) — two traced steps
    ("%fusion.1", 4000, "jit(step)/while/body/jvp(l1_gdn0)/in_proj/dot"),
    ("%fusion.2", 6000, "jit(step)/while/body/jvp(l1_gdn0)/scan/mul"),
    ("%fusion.3", 10000, "jit(step)/while/body/transpose(jvp(l1_gdn0))/"
     "jvp(l1_gdn0)/checkpoint/rematted_computation/scan/dot_general"),
    ("%fusion.4", 3000, "jit(step)/while/body/jvp(l2_moe0)/route/top_k"),
    ("%fusion.5", 2500, "jit(step)/while/body/jvp(l2_moe0)/dispatch/sort"),
    ("%fusion.6", 1500, "jit(step)/while/body/jvp(l2_moe0)/experts/mul"),
    ("%fusion.7", 500, "jit(step)/while/body/transpose(jvp(l2_moe0))/"
     "combine/gather"),
    ("%fusion.8", 700, "jit(step)/while/body/jvp(l2_moe0)/shared/dot"),
    ("%ragged-dot-none", 9000, "ragged-dot-none"),
    ("%ragged-dot-metadata", 100, "ragged-dot-metadata"),
    ("%fusion.9", 5000, "jit(step)/while/body/jvp(l3_attn1)/rotary/mul"),
    ("%fusion.10", 2000, "jit(step)/while/body/jvp(l6_head)/dot_general"),
    ("%fusion.11", 7000, "jit(step)/while/body/update_adam/sqrt"),
    ("%while.1", 99999, "jit(step)/while"),
    ("%copy.1", 100, None),
]
CONF = """netconfig = start
layer[0->h0] = embedding:embed
  nvocab = 50
  nhidden = 8
layer[h0,0->x0] = gated_deltanet:gdn0
  nkhead = 1
  nvhead = 2
  key_dim = 4
  value_dim = 6
layer[x0->h1] = routed_experts:moe0
  nexpert = 8
  topk = 2
  nhidden = 12
  nheld = 4
layer[h1,0->x1] = attention:attn1
  nhead = 2
layer[x1->h2] = routed_experts:moe1
  nexpert = 8
  topk = 2
  nhidden = 12
  nheld = 4
layer[h2->nf] = rms_norm:norm_f
layer[nf->logits] = lm_head:head
  nhidden = 50
layer[logits->logits] = softmax
netconfig = end
input_shape = 1,1,32
"""


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
               {"steps": 24, "counters": {
                   "expert_pairs": 24 * 2 * 4 * 30, "expert_pairs_max":
                   24 * 2 * 45, "expert_pairs_dropped": 0}},
               {"steps": 24, "counters": {
                   "expert_pairs": 24 * 2 * 4 * 34, "expert_pairs_max":
                   24 * 2 * 51, "expert_pairs_dropped": 0}}]}
    scopes._CACHE.pop(out, None)
    stage_scopes._CACHE.pop(out, None)


@pytest.mark.parametrize("name,want", [
    ("gdn_mixer_ms_step", (4000 + 6000 + 10000) / 1e6 / 2),
    ("gdn_scan_ms_step", (6000 + 10000) / 1e6 / 2),
    ("moe_ms_step", (3000 + 2500 + 1500 + 500 + 700 + 9100) / 1e6 / 2),
    ("moe_route_dispatch_ms_step", (3000 + 2500 + 500) / 1e6 / 2),
    ("expert_matmul_ms_step", (1500 + 9100) / 1e6 / 2),
    ("attention_ms_step", 5000 / 1e6 / 2),
    ("head_loss_ms_step", 2000 / 1e6 / 2),
    ("adam_update_ms_step", 7000 / 1e6 / 2),
    ("expert_pairs_per_expert", 32.0),
    ("expert_load_max_over_mean", 1.5),
    ("expert_pairs_dropped", 0.0),
])
def test_a_reader_reads_its_scope_or_counter(record, name, want):
    mod = run.load_metric(name)
    assert mod.read(record) == pytest.approx(want)
    assert mod.MOVES == "train_samples_s_chip"


def test_the_roofline_shares_are_least_time_over_measured_time(record, ref):
    net = ref.describe(CONF, 3)
    least = max(ref.scan_flops(net) / 197e12,
                ref.scan_min_bytes(net) / 819e9)
    got = run.load_metric("gdn_scan_roofline_pct").read(record)
    assert got == pytest.approx(100.0 * least / (0.008 / 1e3))
    pairs = 2 * 4 * 32.0                   # a step, both layers
    least = max(ref.expert_flops(net, pairs) / 197e12,
                ref.expert_min_bytes(net, pairs) / 819e9)
    got = run.load_metric("expert_matmul_roofline_pct").read(record)
    assert got == pytest.approx(100.0 * least / (0.0053 / 1e3))


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
        "tokens": 8 * 8192}}])
    assert mod.read(counted) is None


def test_a_grouped_product_is_read_though_its_scope_is_dropped():
    got = stage_scopes.reduce_parts(EVENTS)
    assert got["ragged_ns"] == 9100
    assert got["parts"][(2, "route")] == 3000
    assert got["parts"][(1, "scan")] == 16000
    assert got["parts"][(3, "rotary")] == 5000
    assert (2, "while") not in got["parts"]
