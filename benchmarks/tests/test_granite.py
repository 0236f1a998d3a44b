"""The token-model configuration of ISSUE 29 (``granite_4_0_h_micro``),
its mix, generator, reference and metric readers, on the CPU: picked up
by files alone, the control fails, each reader on a fixture record, the
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
from benchmarks.lib import scopes  # noqa: E402
from benchmarks.tests import helpers  # noqa: E402

CELL = "granite_4_0_h_micro_train_packed8k"
CONFIG = "granite_4_0_h_micro"
NEW_METRICS = ["ssd_scan_ms_step", "mamba_mixer_ms_step", "attention_ms_step",
               "mlp_ms_step", "head_loss_ms_step", "adam_update_ms_step",
               "ssd_scan_roofline_pct", "packed_docs_per_seq",
               "tokens_per_step"]


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
    """Every number of the source's config.json under its own key;
    only what ``reduced`` lists differs, and no width is among it."""
    published = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192,
        "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64,
        "max_position_embeddings": 131072, "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "shared_intermediate_size": 8192,
        "vocab_size": 100352}
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert config["num_hidden_layers"] == 10 and config["vocab_size"] == 12544
    # one whole period, in the published order
    assert config["layer_types"][:10] == [
        "mamba"] * 5 + ["attention"] + ["mamba"] * 4
    a = config["args"]
    assert a["layer_types"] == "".join(
        t[0] for t in config["layer_types"][:config["num_hidden_layers"]])
    assert (a["hidden"], a["mlp_hidden"], a["vocab"]) == (
        config["hidden_size"], config["intermediate_size"],
        config["vocab_size"])
    assert (a["mamba_heads"], a["mamba_head_dim"], a["mamba_state"],
            a["mamba_conv"], a["mamba_chunk"]) == (
        config["mamba_n_heads"], config["mamba_d_head"],
        config["mamba_d_state"], config["mamba_d_conv"],
        config["mamba_chunk_size"])
    assert (a["attn_heads"], a["attn_kv_heads"]) == (
        config["num_attention_heads"], config["num_key_value_heads"])
    assert (a["embedding_multiplier"], a["attention_multiplier"],
            a["residual_multiplier"], a["logits_scaling"], a["eps"]) == (
        config["embedding_multiplier"], config["attention_multiplier"],
        config["residual_multiplier"], config["logits_scaling"],
        config["rms_norm_eps"])


def test_the_cell_is_the_one_the_issue_names():
    bench = run.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "train_packed8k", 1)
    mix = run.load_json(os.path.join(BENCH, "traffic", "train_packed8k.json"))
    assert mix["chunks_per_round"] == 3 and mix["batch_scale"] == 1
    assert mix["documents"] == {"median": 1024, "sigma": 1.2, "min": 16}
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(NEW_METRICS) <= listed
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_samples_s_chip"


# ----------------------------------------------------------------------
# picked up by files alone: the cell as BENCHMARK.json has it, rehearsed
@pytest.fixture(scope="module")
def rehearsal():
    res = helpers.run_cell_in_child(
        BENCH, ["--workload", CELL, "--seed", "4100000555", "--seconds", "2",
                "--trace", "1", "--cpu-rehearsal"])
    out = os.path.join(ROOT, "bench_out", CELL, "seed4100000555_trace1")
    with open(os.path.join(out, "compare.json")) as f:
        return res, json.load(f), out


def test_the_cell_runs_through_the_cli_from_its_files(rehearsal):
    res, nums, out = rehearsal
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    # float32 on both sides at rehearsal size
    assert nums["loss_gap"] < 1e-5 and nums["update_norm_gap"] < 1e-4
    assert nums["dparam_norm_gap"] < 1e-3
    # id for id: 8 steps of one row
    assert nums["feed_gap_levels"] == 0 and nums["rows"] == 8
    conf = open(os.path.join(out, "cell.conf")).read()
    assert "iter = tokens" in conf and "eval_train = 0" in conf
    assert "updater = adam" in conf and "remat = 1" in conf


def test_the_counters_reach_the_line_and_device_metrics_stay_out(rehearsal):
    res, _, _ = rehearsal
    m = res["metrics"]
    assert m["tokens_per_step"]["value"] == 128.0     # no padding
    assert m["packed_docs_per_seq"]["value"] > 1.0
    # a CPU trace holds no device plane: nothing to read, left out
    for name in NEW_METRICS[:7] + ["device_step_ms"]:
        assert name not in m
    assert m["chunk_recycled_pct"]["value"] > 90.0
    assert "device_wait_ms_step" in m and "scan_dispatch_ms_step" in m


def test_the_generator_packs_documents_from_the_seed(tmp_path):
    gen = run.load_generator(run.load_json(os.path.join(
        BENCH, "traffic", "train_packed8k.json")))
    docs = {"median": 1024, "sigma": 1.2, "min": 16}
    a = gen.stream(24 * 8192 + 1, 8192, 12544, docs, 4100000555)
    b = gen.stream(24 * 8192 + 1, 8192, 12544, docs, 4100000555)
    c = gen.stream(24 * 8192 + 1, 8192, 12544, docs, 4100000556)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.dtype == np.dtype("<u2") and a.max() == 12543
    ends = np.flatnonzero(a == 0)
    lens = np.diff(np.concatenate([[-1], ends]))
    assert lens.min() >= 16 and lens.max() <= 8192
    assert 500 < np.median(lens) < 2000
    # ids inside a document are never the separator
    assert (a != 0).sum() == a.size - len(ends)
    fill = {"seq_len": 64, "vocab": 100, "nsample": 6, "seed": 9,
            "out": str(tmp_path)}
    mix = {"documents": {"median": 20, "sigma": 1.0, "min": 4}}
    made = gen.make(mix, fill, str(tmp_path))
    raw = np.fromfile(made["token_file"], "<u2")
    assert len(raw) == 6 * 64 + 1
    rows = raw[:128].reshape(2, 64).astype(np.float32)
    labs = raw[1:129].reshape(2, 64).astype(np.float32)
    assert gen.check_feed(mix, fill, rows, labs) == {
        "feed_gap_levels": 0.0, "rows": 2}
    rows[1, 3] += 1
    assert gen.check_feed(mix, fill, rows, labs)["feed_gap_levels"] == 1.0
    assert gen.check_feed(mix, fill, rows[::-1], labs)["feed_gap_levels"] > 0


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
    # limits between the two readings, as PERF.md section 2 sets them:
    # three times the bfloat16 run's gap holds it, and not the control
    limits = {k: 3 * sound[k] for k in
              ("loss_gap", "update_norm_gap", "dparam_norm_gap")}
    assert run.held_to_limits(sound, limits)
    assert not run.held_to_limits(control, limits)
    assert control["update_norm_gap"] > 3 * sound["update_norm_gap"]


# ----------------------------------------------------------------------
# the counting functions, at one small shape, against a hand count
def test_flops_and_bytes_match_a_hand_count(ref):
    from cxxnet_tpu.models import granite_h_conf

    text = granite_h_conf(
        vocab=50, seq_len=32, hidden=8, layer_types="ma", mamba_heads=2,
        mamba_head_dim=8, mamba_state=4, attn_heads=2, attn_kv_heads=1,
        mlp_hidden=12, batch_size=3, dev="cpu")
    net = ref.describe(text, 3)
    tok = 3 * 32
    e, s, h, d = 16, 4, 2, 8
    scan = tok * h * 5 * 8 * s * 3          # 5 P S a token and head, x3
    assert ref.scan_flops(net) == scan
    ins = e + 2 * s + h
    assert ref.scan_min_bytes(net) == tok * 2 * ((ins + e) * 2 + ins)
    mamba = d * (2 * e + 2 * s + h) + e * d
    attn = d * (d + 2 * 1 * 4) + d * d + 2 * (32 + 1) / 2 * d
    mlp = 2 * (2 * 12 * d + d * 12)
    head = d * 50
    macs = tok * (mamba + attn + mlp + head)
    assert ref.step_flops(net) == pytest.approx(macs * 6 + scan)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    assert params == (50 * d + (2 * e + 2 * s + h) * d + (e + 2 * s) * 4
                      + (e + 2 * s) + 3 * h + e + d * e + d
                      + (d + 8) * d + d * d + d
                      + 2 * (24 * d + d * 12 + d) + d)
    # embedding, 2 mixers-or-attention, 2 MLPs and the last norm put out
    # the hidden width, the head the vocabulary
    assert ref.step_min_bytes(net) == (
        tok * (6 * d + 50) * 2 * 5.0 + params * 4 * 8.0)


def test_the_published_size_is_what_the_issue_reckoned(config, ref):
    text = run.net_text(config, dict(config["args"]), "tpu")
    net = ref.describe(text, 1)
    params = sum(int(np.prod(v)) for t in net.pshapes.values()
                 for v in t.values())
    assert round(params / 1e6, 1) == 772.2           # x 16 B = 12.36 GB
    # 6 x parameters x tokens, the embedding's lookup left out and the
    # attention products and the recurrence added
    assert 36e12 < ref.step_flops(net) < 41e12


# ----------------------------------------------------------------------
# each new reader on a fixture record
EVENTS = [
    # (HLO name, ns, scope) — two traced steps
    ("%fusion.1", 4000, "jit(step)/while/body/jvp(l1_mixer0)/in_proj/dot"),
    ("%fusion.2", 6000, "jit(step)/while/body/jvp(l1_mixer0)/scan/mul"),
    ("%fusion.3", 10000, "jit(step)/while/body/transpose(jvp(l1_mixer0))/"
     "jvp(l1_mixer0)/checkpoint/rematted_computation/scan/dot_general"),
    ("%fusion.4", 3000, "jit(step)/while/body/jvp(l2_mlp0)/dot_general"),
    ("%fusion.5", 5000, "jit(step)/while/body/jvp(l3_attn1)/checkpoint/mul"),
    ("%fusion.6", 2000, "jit(step)/while/body/jvp(l6_head)/dot_general"),
    ("%fusion.7", 1000, "jit(step)/while/body/jvp(l0_embed)/jit(_take)"),
    ("%fusion.8", 7000, "jit(step)/while/body/update_adam/sqrt"),
    ("%fusion.9", 900, "jit(step)/while/body/closed_call"),
    ("%while.1", 99999, "jit(step)/while"),
    ("%copy.1", 100, None),
]
CONF = """netconfig = start
layer[0->h0] = embedding:embed
layer[h0,0->x0] = mamba2:mixer0
  nhead = 2
  head_dim = 8
  nstate = 4
layer[x0->h1] = gated_mlp:mlp0
  nhidden = 12
layer[h1,0->x1] = attention:attn1
  nhead = 2
layer[x1->h2] = gated_mlp:mlp1
  nhidden = 12
layer[h2->nf] = rms_norm:norm_f
layer[nf->logits] = lm_head:head
  tied = embed
  nhidden = 50
layer[logits->logits] = softmax
netconfig = end
input_shape = 1,1,32
"""


@pytest.fixture()
def record(tmp_path):
    out = str(tmp_path)
    with open(os.path.join(out, "cell.conf"), "w") as f:
        f.write(CONF.replace("layer[0->h0] = embedding:embed",
                             "layer[0->h0] = embedding:embed\n  nvocab = 50"
                             "\n  nhidden = 8"))
    got = scopes.reduce_events(EVENTS)
    text, layers = scopes.conf_layers(out)
    got.update(conf=text, out=out,
               types={i: k for i, (k, _) in enumerate(layers)})
    scopes._CACHE[out] = got
    yield {"out": out, "trace": {"steps": 2, "busy_s": 1.0}, "batch": 3,
           "chips": 1, "peaks": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
           "telemetry": [
               {"steps": 24, "counters": {"tokens": 24 * 3 * 32, "docs": 90,
                                          "docs_cut": 70}},
               {"steps": 24, "counters": {"tokens": 24 * 3 * 32, "docs": 54,
                                          "docs_cut": 71}}]}
    scopes._CACHE.pop(out, None)


@pytest.mark.parametrize("name,want", [
    ("mamba_mixer_ms_step", (4000 + 6000 + 10000) / 1e6 / 2),
    ("ssd_scan_ms_step", (6000 + 10000) / 1e6 / 2),
    ("attention_ms_step", 5000 / 1e6 / 2),
    ("mlp_ms_step", 3000 / 1e6 / 2),
    ("head_loss_ms_step", (2000 + 1000) / 1e6 / 2),
    ("adam_update_ms_step", 7000 / 1e6 / 2),
    ("tokens_per_step", 96.0),
    ("packed_docs_per_seq", 1.0),
])
def test_a_reader_reads_its_scope_or_counter(record, name, want):
    mod = run.load_metric(name)
    assert mod.read(record) == pytest.approx(want)
    assert mod.MOVES == "train_samples_s_chip"


def test_the_roofline_share_is_least_time_over_measured_time(record, ref):
    net = ref.describe(open(os.path.join(record["out"], "cell.conf")).read(),
                       3)
    least = max(ref.scan_flops(net) / 197e12,
                ref.scan_min_bytes(net) / 819e9)
    got = run.load_metric("ssd_scan_roofline_pct").read(record)
    assert got == pytest.approx(100.0 * least / (0.008 / 1e3))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_has_nothing(name, tmp_path):
    """An untraced run, a run whose directory is not there, a program
    that counts nothing: ``None``, never an exception."""
    mod = run.load_metric(name)
    bare = {"out": str(tmp_path / "absent"), "trace": None, "batch": 1,
            "chips": 1, "peaks": None, "telemetry": [{"steps": 8}]}
    assert mod.read(bare) is None
    traced = dict(bare, trace={"steps": 16, "busy_s": 1.0})
    assert mod.read(traced) is None


def test_a_scope_names_its_layer_and_its_stage():
    assert scopes.classify(None) == (None, None)
    assert scopes.classify("jit(step)/while/body/update_sgd/mul") == (
        None, "update")
    assert scopes.classify(
        "transpose(jvp(l14_mixer6))/jvp(l14_mixer6)/checkpoint/conv/pad"
    ) == (14, "conv")
    assert scopes.classify("jvp(l3_conv1)/conv_general_dilated") == (3, None)
    assert scopes.classify("jit(step)/while/body/closed_call/mul") == (
        None, None)


def test_scopes_are_read_from_a_trace_recorded_on_the_chip():
    """``fixtures/scopes_v5e.xplane.pb``: three calls of a small jitted
    gradient on one TPU v5 lite (PR 29), two products under
    ``l3_mixer3/scan`` and two under ``l3_mixer3/out_proj`` a call, the
    scope in the ``tf_op`` statistic of each event's metadata."""
    path = os.path.join(BENCH, "fixtures", "scopes_v5e.xplane.pb")
    events = list(scopes.device_events(path))
    assert len(events) == 27
    named = [e for e in events if e[2]]
    assert all(e[0].startswith("%") and e[1] > 0 for e in named)
    got = scopes.load(path)
    assert set(got["layers"]) == {3} and got["update_ns"] == 0
    row = got["layers"][3]
    assert row["total"] == row["scan"] + row["out_proj"] == 143838
    assert 0.9 < row["scan"] / row["out_proj"] < 1.1
