"""The benchmark's own tests: CPU only, seconds each.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib import netconf, tracered, window  # noqa: E402
from benchmarks.tests import helpers  # noqa: E402


# ----------------------------------------------------------------------
# window arithmetic on a recorded list of stamps
def _stamps():
    # three rounds of three chunks; a boundary of 1 s between rounds;
    # a round's first chunk starts 0.5 s after the entry
    st = window.Stamps()
    t = 0.0
    for rnd in range(4):
        enter = t
        t += 0.5
        fences = []
        for _ in range(3):
            t += 2.0
            fences.append(t)
        st.rounds.append([enter, t + 0.2])
        st.fences += [(f, rnd, 8) for f in fences]
        t += 1.0
    return st


def test_window_starts_at_first_fence_after_a_whole_round():
    st = _stamps()
    i0 = window.window_start_index(st)
    assert st.fences[i0][1] == 1 and i0 == 3


def test_window_holds_whole_chunks_and_no_round_boundary():
    st = _stamps()
    win = window.reduce_window(st, 9.0, batch=128, chips=1)
    # from fence 3 (t=10): chunks ending at 12, 14 and, across the
    # boundary, round 2's first at 17.5 (period 2.5: entry to fence); the
    # next fence (19.5) is past 10 + 9
    assert win["chunks"] == 3 and win["steps"] == 24
    assert win["periods_s"] == pytest.approx([2.0, 2.0, 2.5])
    assert win["wall_s"] == pytest.approx(7.5)
    assert win["round_boundary_s"] == pytest.approx(1.0)
    assert win["samples_s_chip"] == pytest.approx(24 * 128 / 6.5)


def test_first_chunk_of_a_round_counts_from_the_round_entry():
    st = _stamps()
    per = window.chunk_periods(st)
    assert per[3][1] == st.rounds[1][0] and per[4][1] == per[3][2]


def test_a_run_that_ended_inside_warm_up_is_an_error():
    st = _stamps()
    st.fences = st.fences[:3]
    with pytest.raises(ValueError):
        window.reduce_window(st, 5.0, 128, 1)


# ----------------------------------------------------------------------
# the trace reduction on a small recorded table
@pytest.fixture(scope="module")
def rows():
    with open(os.path.join(BENCH, "fixtures", "trace_events.json")) as f:
        return json.load(f)["rows"]


def test_busy_is_the_union_of_operation_intervals(rows):
    # 1000-5000 (two overlapping ops), 9000-10000, 11000-13000
    assert tracered.busy_seconds(rows) == pytest.approx(7000e-9)


def test_idle_share_and_gap_between_programs(rows):
    gap, mods = tracered.module_gap_seconds(rows)
    assert mods == 2 and gap == pytest.approx(4000e-9)
    span = (0, 14000)
    idle = 1 - tracered.busy_seconds(rows) / ((span[1] - span[0]) / 1e9)
    assert idle == pytest.approx(0.5)


def test_one_session_reduces_to_what_the_readers_take(rows):
    t = tracered.reduce(rows, steps=2)
    assert t["window_s"] == pytest.approx(14000e-9)  # first to last event
    assert 1 - t["busy_s"] / t["window_s"] == pytest.approx(0.5)
    assert (t["steps"], t["modules"]) == (2, 2)
    assert t["top_ops"][0][0] == "fusion.2"
    # idle: 0-1000, 5000-9000, 10000-11000, 13000-14000
    assert sorted(g[1] for g in t["gaps"]) == pytest.approx(
        [1e-6, 1e-6, 1e-6, 4e-6])


def test_idle_gaps_are_named_by_the_innermost_covering_host_event(rows):
    gaps = tracered.idle_gaps(rows, (0, 14000), 3)
    assert gaps[0] == ["cli.py_1502_train_one_round", pytest.approx(4e-6)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert tracered.top_ops(rows, 1)[0] == ["fusion.2", pytest.approx(5e-6)]


@pytest.fixture(scope="module")
def chip_rows():
    with open(os.path.join(BENCH, "fixtures", "trace_events_v5e.json")) as f:
        return json.load(f)["rows"]


def test_a_trace_recorded_on_the_chip_reduces(chip_rows):
    # two chunks: two jit_step programs among eight program events
    steps = [r for r in chip_rows if r[1] == "XLA Modules"
             and r[2].startswith("jit_step")]
    gap, mods = tracered.module_gap_seconds(chip_rows)
    assert len(steps) == 2 and mods == 8
    # the device sat between programs for all of the span from the first
    # program's start to the last one's end, but the programs themselves
    allm = sorted(r for r in chip_rows if r[1] == "XLA Modules")
    span = max(r[3] + r[4] for r in allm) - min(r[3] for r in allm)
    assert gap == pytest.approx((span - sum(r[4] for r in allm)) / 1e9)
    assert 6.0 < gap < 7.0
    # busy is the first chunk's 0.68 s: the sample holds its loop only
    assert tracered.busy_seconds(chip_rows) == pytest.approx(0.679, rel=0.01)
    names = [n for n, _ in tracered.top_ops(chip_rows, 50)]
    assert names and not any(n.startswith("while") for n in names)
    assert all(re.match(r"^[A-Za-z0-9_.\-]+$", n) for n in names)
    lo = min(r[3] for r in chip_rows)
    hi = max(r[3] + r[4] for r in chip_rows)
    named = [n for n, _ in tracered.idle_gaps(chip_rows, (lo, hi), 5)]
    assert "bench.update_scan" in named


def test_operation_names_keep_the_operation_and_what_it_makes():
    text = ("%compare_select_fusion.192 = bf16[256,28,28,192]{0,3,2,1:T(8,128)"
            "(2,1)} fusion(bf16[256,28,28,192]{0,3,2,1} %p), kind=kLoop")
    assert tracered.op_name(text) == \
        "compare_select_fusion.192_bf16_256_28_28_192"


# ----------------------------------------------------------------------
# the FLOP count of both confs against a hand count of two layers each
def _net(builder, batch):
    from cxxnet_tpu import models

    conf = getattr(models, builder)(batch_size=batch, synthetic=False)
    layers, glob = netconf.parse_net(conf)
    shapes = netconf.infer_shapes(layers, batch, (3, 224, 224))
    return layers, shapes


@pytest.mark.parametrize("builder,name,macs", [
    # conv1: 112x112 outputs, 64 channels, 7x7x3 taps
    ("googlenet_conf", "conv1", 112 * 112 * 64 * 7 * 7 * 3),
    # the classifier: 1024 -> 1000
    ("googlenet_conf", "loss3_classifier", 1024 * 1000),
    # stage 0 block 0's 3x3: 56x56, 64 -> 64
    ("resnet50_conf", "s0b0_b_conv", 56 * 56 * 64 * 3 * 3 * 64),
    ("resnet50_conf", "fc1000", 2048 * 1000),
])
def test_flops_of_one_layer_match_a_hand_count(builder, name, macs):
    layers, shapes = _net(builder, 2)
    lay = [l for l in layers if l.name == name]
    assert netconf.step_flops(lay, shapes) == 2 * macs * 2 * 3


@pytest.mark.parametrize("builder,gmacs", [
    ("googlenet_conf", (1.4, 1.7)),   # Szegedy et al.: ~1.5 G multiply-adds
    ("resnet50_conf", (3.8, 4.2)),    # He et al.: 3.8 G (they leave fc out)
])
def test_flops_of_a_whole_step_are_the_papers_counts(builder, gmacs):
    layers, shapes = _net(builder, 1)
    g = netconf.step_flops(layers, shapes) / 6 / 1e9
    assert gmacs[0] < g < gmacs[1]


def test_only_the_norms_that_close_a_residual_branch_start_at_zero():
    layers, _ = _net("resnet50_conf", 1)
    closing = [layers[i].name for i in netconf.residual_branch_norms(layers)]
    assert len(closing) == 16 and all(n.endswith("_c_bn") for n in closing)
    assert netconf.residual_branch_norms(_net("googlenet_conf", 1)[0]) == []


def test_an_unknown_device_kind_raises():
    from benchmarks.lib import peaks

    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


# ----------------------------------------------------------------------
# BENCHMARK.json against the contract's letter
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_and_unit_is_made_of_the_allowed_characters(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    metrics = bench["end_to_end"] + bench["per_layer"]
    names += [m["name"] for m in metrics]
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in metrics)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(m["better"] in ("lower", "higher") for m in metrics)
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in bench["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in bench["end_to_end"])


def test_every_moves_names_an_end_to_end_metric_of_the_same_cells(bench):
    e2e = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        mine = set(m.get("workloads", cells))
        assert mine <= cells and mine <= set(e2e[m["moves"]] or cells)


def test_every_cell_finds_its_files_and_every_metric_its_reader(bench):
    from benchmarks import run

    for c in bench["configs"]:
        cfg = run.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and "limits" in cfg
        assert c["file"].startswith(tuple(bench["paths"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in bench["per_layer"]:
        mod = run.load_metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])


# ----------------------------------------------------------------------
# a whole run at toy size on the CPU, on a copy with two configurations
# (a conv net on the shipped reference; a token model with a reference
# and a feed of its own), their mixes and a metric dropped in as files
FAMILIES = ["tiny_cell", "tiny_lm_cell"]

@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return helpers.copy_with_dropins(str(tmp_path_factory.mktemp("bench")))


def _run(copy, seed, trace=0, cell="tiny_cell", frozen=False):
    return helpers.run_cell_in_child(
        copy, ["--workload", cell, "--seed", str(seed), "--seconds", "2",
               "--trace", str(trace), "--cpu-rehearsal"], frozen=frozen)


@pytest.mark.parametrize("cell", FAMILIES)
def test_dropped_in_files_are_picked_up_and_the_reference_agrees(copy, cell):
    res = _run(copy, 2147483999, trace=1, cell=cell)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    # the dropped-in metric reads; the device-trace ones find no device
    # plane in a CPU trace, return nothing and are left out of the line
    assert res["metrics"]["chunks_in_window"]["value"] == res["attempted"]
    assert "device_step_ms" not in res["metrics"]
    out = os.path.join(os.path.dirname(copy), "bench_out", cell)
    nums = json.load(open(os.path.join(
        out, "seed2147483999_trace1", "compare.json")))
    assert nums["update_norm_gap"] < 1e-3 and nums["loss_gap"] < 1e-4
    if cell == "tiny_lm_cell":
        # float32 on both sides, and every fed row found in the file the
        # mix's own generator made
        assert nums["update_norm_gap"] < 1e-4 and nums["loss_gap"] < 1e-5
        assert nums["rows"] == 64 and nums["feed_gap_levels"] == 0


def test_the_image_mix_packs_feeds_and_checks_its_rows(copy):
    res = _run(copy, 31, cell="tiny_jpeg_cell")
    assert res["correct"] is True
    out = os.path.join(os.path.dirname(copy), "bench_out", "tiny_jpeg_cell")
    nums = json.load(open(os.path.join(out, "seed31_trace0", "compare.json")))
    assert nums["rows"] == 16 and nums["feed_gap_levels"] <= 2


@pytest.mark.parametrize("cell", FAMILIES)
def test_end_to_end_line_has_the_two_metrics(copy, cell):
    res = _run(copy, 5, cell=cell)
    assert set(res["metrics"]) == {"train_samples_s_chip", "setup_s"}
    assert res["metrics"]["train_samples_s_chip"]["value"] > 0


@pytest.mark.parametrize("cell", FAMILIES)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(copy, cell):
    """The harness's look for a chip skipped (rehearsal) and the rest of
    a run driven, with the timed path broken underneath
    (``helpers.CHILD``)."""
    assert _run(copy, 7, cell=cell, frozen=True)["correct"] is False


def test_the_copy_adds_files_and_edits_none(copy):
    for top, _, files in os.walk(BENCH):
        if "__pycache__" in top or top.startswith(HERE):
            continue
        for name in files:
            with open(os.path.join(top, name), "rb") as f, \
                    open(os.path.join(copy, os.path.relpath(top, BENCH),
                                      name), "rb") as g:
                assert f.read() == g.read(), name


@pytest.mark.parametrize("config,lim", [
    ("tiny", helpers.TINY_CONFIG["limits"]),
    ("tiny_lm", helpers.TINY_LM_CONFIG["limits"])], ids=["tiny", "tiny_lm"])
def test_the_limits_tool_walks_the_same_seam(copy, config, lim):
    """``tools/limits.py`` of the copy, on the CPU at toy size: the
    program's chunk, the configuration's reference and its control."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_ENABLE_COMPILATION_CACHE="false")
    out = subprocess.run(
        [sys.executable, os.path.join(copy, "tools", "limits.py"),
         "--config", config, "--cpu-toy", "--seeds", "2", "--control", "1"],
        cwd=os.path.dirname(copy), env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rows = json.load(open(os.path.join(
        os.path.dirname(copy), "chiprun_out", "limits", config + ".json")))
    for row in rows["rows"]:
        assert all(row["sound"][k] <= lim[k] for k in
                   ("loss_gap", "update_norm_gap", "dparam_norm_gap"))
    assert "control" in rows["rows"][0] and "control" not in rows["rows"][1]


# ----------------------------------------------------------------------
# the control: the reference one precision down must come out not correct
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_fp8_control_fails_where_bf16_passes(seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import reference

    with open(os.path.join(HERE, "data", "tiny.conf")) as f:
        conf = f.read().format(num_class=10, input_size=16, batch_size=32,
                               dev="cpu", compute_dtype="bfloat16")
    layers, glob = netconf.parse_net(conf)
    shapes = netconf.infer_shapes(layers, 32, (3, 16, 16))
    pshapes = netconf.param_shapes(layers, shapes)
    rng = np.random.RandomState(seed)
    data = rng.randn(4, 32, 16, 16, 3).astype(np.float32)
    labels = rng.randint(0, 10, (4, 32, 1)).astype(np.float32)
    key = jax.random.PRNGKey(seed)

    def chunk(quant):
        w = reference.make_weights(layers, shapes, pshapes, seed)
        l, p, m = reference.train_chunk(layers, glob, w, data, labels, key,
                                        quant=quant)
        return {"losses": l, "params": p, "momentum": m}

    start = jax.device_get(reference.make_weights(layers, shapes, pshapes,
                                                  seed))
    ref = chunk(None)
    sound = reference.compare_chunk(chunk(jnp.bfloat16), ref, start)
    control = reference.compare_chunk(chunk(jnp.float8_e4m3fn), ref, start)
    assert control["update_norm_gap"] > 3 * sound["update_norm_gap"]


# ----------------------------------------------------------------------
# the seam: a file that names nothing gets the code it got before
def test_no_accepted_file_names_a_reference_or_a_generator(bench):
    from benchmarks import run

    for c in bench["configs"]:
        assert "reference" not in run.load_json(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert "generator" not in run.load_json(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))


def test_the_default_generator_is_the_shipped_one():
    from benchmarks import run

    gen = run.load_generator({})
    assert gen.__file__ == os.path.join(BENCH, "lib", "traffic.py")
    assert gen.check_feed({}, {}, None, None) is None


def test_the_default_reference_hands_the_shipped_code_through(monkeypatch):
    import jax.numpy as jnp

    from benchmarks import run
    from benchmarks.lib import reference

    ref = run.load_reference({"name": "names_none"})
    assert ref.__file__ == os.path.join(BENCH, "references", "conv_sgd.py")
    assert ref.netconf is netconf and ref.reference is reference
    with open(os.path.join(HERE, "data", "tiny.conf")) as f:
        conf = f.read().format(num_class=10, input_size=16, batch_size=4,
                               dev="cpu", compute_dtype="float32")
    net = ref.describe(conf, 4)
    assert tuple(net) == netconf.describe_net(conf, 4)
    assert ref.step_flops(net) == netconf.step_flops(net.layers, net.shapes)
    assert ref.step_min_bytes(net) == netconf.step_min_bytes(net.layers,
                                                             net.shapes)
    seen = []
    monkeypatch.setattr(reference, "make_weights",
                        lambda *a: seen.append(a) or "made")
    monkeypatch.setattr(reference, "train_chunk",
                        lambda *a, **kw: seen.append((a, kw)) or "trained")
    assert ref.make_weights(net, 3) == "made"
    assert seen.pop() == (net.layers, net.shapes, net.pshapes, 3)
    for control, quant in ((None, None), (True, jnp.float8_e4m3fn),
                           ("bfloat16", jnp.bfloat16)):
        assert ref.train_chunk(net, "w", "x", "y", "k",
                               control=control) == "trained"
        assert seen.pop() == ((net.layers, net.glob, "w", "x", "y", "k"),
                              {"quant": quant})
    assert ref.program_update_state({3: {"wmat": {"m": 1, "x": 2}}}) == {
        3: {"wmat": 1}}


def test_a_reference_outside_the_benchmark_is_refused():
    from benchmarks import run

    with pytest.raises(SystemExit):
        run.load_reference({"name": "x", "reference": "cxxnet_tpu/models.py"})


@pytest.mark.parametrize("builder", ["googlenet_conf", "resnet50_conf",
                                     "transformer_lm_conf"])
def test_the_harness_reads_the_global_keys_the_shipped_parse_reads(builder):
    from benchmarks import run
    from cxxnet_tpu import models

    conf = getattr(models, builder)(batch_size=2)
    assert run.conf_globals(conf) == netconf.parse_net(conf)[1]


def test_a_template_takes_every_key_of_the_configurations_args(tmp_path):
    from benchmarks import run

    config = dict(helpers.TINY_LM_CONFIG, args=dict(
        helpers.TINY_LM_CONFIG["args"], seq_len=8))
    mix = dict(helpers.TINY_TEXT_MIX, generator=os.path.relpath(
        os.path.join(HERE, "data", "tiny_text_generator.py"), ROOT))
    conf = run.build_conf(config, mix, 5, str(tmp_path), False)
    text = open(conf["path"]).read()
    assert "  seq_len = 8\n" in text and "input_shape" not in mix["conf"]
    assert f"  filename = {tmp_path}/tokens.bin" in text
    # 8 rows x 8 steps x 3 chunks of 8 bytes, and the last label
    assert os.path.getsize(tmp_path / "tokens.bin") == 8 * 8 * 3 * 8 + 1
    assert "num_class" not in conf["fill"]
    with pytest.raises(SystemExit):
        run.build_conf(config, dict(mix, chunks_per_round=2), 5,
                       str(tmp_path), False)


# ----------------------------------------------------------------------
# the dropped-in token model's two controls: its reference, put in the
# program's place with the causal mask left out, or one precision down,
# must come out not correct on the configuration's own limits
@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("control", ["no_causal_mask", "precision"])
def test_the_token_models_controls_are_not_correct(control, seed, monkeypatch):
    import jax

    from benchmarks import run
    from benchmarks.lib import reference

    cfg = helpers.TINY_LM_CONFIG
    ref = run.load_reference(dict(cfg, reference=os.path.relpath(
        os.path.join(HERE, "data", "tiny_lm_reference.py"), ROOT)))
    net = ref.describe(run.net_text(cfg, dict(cfg["args"]), "cpu"),
                       cfg["args"]["batch_size"])
    data, labels = ref.seeded_chunk(net, seed, 4)
    key = jax.random.PRNGKey(seed)

    def chunk(**kw):
        l, p, m = ref.train_chunk(net, ref.make_weights(net, seed), data,
                                  labels, key, **kw)
        return {"losses": l, "params": p, "momentum": m}

    start = jax.device_get(ref.make_weights(net, seed))
    plain = chunk()
    assert run.held_to_limits(
        reference.compare_chunk(chunk(), plain, start), cfg["limits"])
    if control == "precision":
        broken = chunk(control=True)
    else:
        monkeypatch.setattr(ref, "CAUSAL_MASK", False)
        broken = chunk()
    assert not run.held_to_limits(
        reference.compare_chunk(broken, plain, start), cfg["limits"])
