"""The contracts that keep a device number honest (PR 21).

* a measurement path that finds no chip FAILS — ``chip_smoke.py`` and
  ``bench.py`` exit non-zero on a CPU-only host, name the platform they
  found, and print no pass line and no metric line;
* the persistent compile cache is placed by ONE resolver
  (``utils/compile_cache.py``): ``JAX_COMPILATION_CACHE_DIR`` wins and
  the program then never writes the directory setting itself, a conf
  key comes second, ``<checkout>/.jax_cache`` is the default;
* the ``dev=`` platform word is advisory but its fall-through is said;
* a failed kernel probe is reported and an explicit opt-in raises;
* every Pallas kernel in ``ops/`` LOWERS for the TPU at the shapes the
  smoke runs on the chip — Mosaic lowering needs no chip, so that half
  breaks here in tier-1 and only libtpu's half waits for the chip.
"""

import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, **env):
    """Run python on the CPU; an env value of None unsets the name."""
    e = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    e = {k: v for k, v in e.items() if v is not None}
    return subprocess.run([sys.executable, *args], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=120)


# ----------------------------------------------------------------------
# no chip -> fail, never a CPU number
def test_chip_smoke_refuses_a_cpu_only_host():
    r = _run(["chip_smoke.py"])
    assert r.returncode not in (0, None)
    assert "platform: cpu" in r.stdout
    assert "no accelerator" in r.stderr and "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
    assert "leg " not in r.stdout  # it stopped before any work


def test_chip_smoke_alone_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run(["chip_smoke.py"], cwd=str(tmp_path), PYTHONPATH="")
    assert r.returncode not in (0, None)
    assert "cannot import the program" in r.stderr
    assert '"ok"' not in r.stdout


def test_bench_refuses_a_cpu_only_host():
    r = _run(["bench.py"])
    assert r.returncode == 2
    assert "platform: cpu" in r.stderr and "no accelerator" in r.stderr
    assert r.stdout.strip() == ""  # no metric line, not even a null one


# ----------------------------------------------------------------------
# one resolver for the compile cache
def test_cache_resolver_precedence(monkeypatch, tmp_path):
    from cxxnet_tpu.utils import compile_cache as cc

    monkeypatch.delenv(cc.ENV_DIR, raising=False)
    assert cc.resolve() == os.path.join(REPO, ".jax_cache")
    assert cc.resolve(str(tmp_path / "conf")) == str(tmp_path / "conf")
    monkeypatch.setenv(cc.ENV_DIR, str(tmp_path / "env"))
    assert cc.resolve() == str(tmp_path / "env")
    # the conf key loses to the environment
    assert cc.resolve(str(tmp_path / "conf")) == str(tmp_path / "env")


_ENABLE_SCRIPT = """
import os, sys
import jax, jax.numpy as jnp
wrote = []
real = jax.config.update
def spy(name, val):
    wrote.append(name)
    return real(name, val)
jax.config.update = spy
from cxxnet_tpu.utils import compile_cache
d = compile_cache.configure([("compile_cache_dir", sys.argv[1])])
assert compile_cache.configure([("compile_cache_dir", sys.argv[1])]) == d
jax.jit(lambda x: x * 2 + 1)(jnp.ones(4)).block_until_ready()
print("DIR", d)
print("WROTE_DIR", "jax_compilation_cache_dir" in wrote)
print("ENTRIES", len(os.listdir(d)))
"""


def _enable(tmp_path, cache_env):
    r = _run(["-c", _ENABLE_SCRIPT, str(tmp_path / "conf")],
             cwd=str(tmp_path), PYTHONPATH=REPO,
             JAX_ENABLE_COMPILATION_CACHE="true",
             JAX_COMPILATION_CACHE_DIR=cache_env)
    assert r.returncode == 0, r.stderr[-2000:]
    return dict(line.split(" ", 1) for line in r.stdout.splitlines()
                if line.split(" ", 1)[0] in ("DIR", "WROTE_DIR", "ENTRIES"))


def test_cache_env_dir_is_never_overwritten_by_the_program(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads the directory
    itself: the program caches there and never touches the setting —
    not even for the ``compile_cache_dir`` conf key."""
    out = _enable(tmp_path, str(tmp_path / "env"))
    assert out["DIR"] == str(tmp_path / "env")
    assert out["WROTE_DIR"] == "False"
    assert int(out["ENTRIES"]) > 0
    assert not (tmp_path / "conf").exists()


def test_cache_conf_key_places_it_when_the_env_is_unset(tmp_path):
    out = _enable(tmp_path, None)
    assert out["DIR"] == str(tmp_path / "conf")
    assert out["WROTE_DIR"] == "True"
    assert int(out["ENTRIES"]) > 0


# ----------------------------------------------------------------------
# the advisory platform word never falls through silently
def test_mesh_fall_through_is_said(capsys):
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.parallel import make_mesh

    plan = make_mesh("tpu:0-1")
    line = plan.describe_devices()
    assert plan.platform == "cpu" and line.startswith("cpu (")
    assert "ordinals [0, 1]" in line
    assert "asked for 'tpu'" in line
    assert "asked for" not in make_mesh("cpu").describe_devices()

    tr = NetTrainer()
    tr.set_params([
        ("dev", "tpu"), ("batch_size", "4"), ("input_shape", "1,1,6"),
        ("netconfig", "start"), ("layer[0->1]", "fullc:fc"),
        ("nhidden", "3"), ("layer[1->1]", "softmax"), ("netconfig", "end"),
    ])
    tr.init_model()
    out = capsys.readouterr().out
    assert "devices: cpu (" in out and "asked for 'tpu'" in out
    assert tr.net.exec_backend == "cpu"  # bound from the mesh, not guessed


def test_scan_step_compiles_once_on_a_mesh():
    """Found on four v5e chips (PR 21): the scanned step hands its
    carried rng key back mesh-replicated while the first call's key
    lived on one device; jax 0.9 keys its tracing cache on the mesh an
    argument lives on, so the second round retraced and recompiled the
    whole program."""
    import numpy as np

    from cxxnet_tpu.nnet.trainer import NetTrainer

    tr = NetTrainer()
    tr.set_params([
        ("dev", "cpu:0-3"), ("batch_size", "8"), ("input_shape", "1,1,6"),
        ("eta", "0.1"), ("silent", "1"),
        ("netconfig", "start"), ("layer[0->1]", "fullc:fc"),
        ("nhidden", "4"), ("layer[1->1]", "softmax"), ("netconfig", "end"),
    ])
    tr.eval_train = 0
    tr.init_model()
    rng = np.random.RandomState(0)
    x = rng.randn(3, 8, 6).astype(np.float32)
    y = rng.randint(0, 4, (3, 8, 1)).astype(np.float32)
    for _ in range(3):
        tr.update_scan(x, y)
    assert tr._scan_step_fn(3, True, False).fn._cache_size() == 1


def test_kernel_selector_refuses_to_guess_a_backend():
    from cxxnet_tpu.ops import kernels as klib

    with pytest.raises(ValueError, match="backend"):
        klib.KernelSelector("conv_block").bind("")
    assert not klib.KernelSelector("conv_block").bind("tpu").interpret


# ----------------------------------------------------------------------
# a failed kernel probe is reported; an explicit opt-in raises
def test_failed_flash_probe_is_logged_and_pallas_raises(monkeypatch, capsys):
    """Pose as a chip: the compiled (non-interpreted) flash kernel
    cannot run on the CPU backend, so the probe really fails."""
    from cxxnet_tpu.layers import sequence as seq

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(seq, "_FLASH_PROBE", {})
    monkeypatch.setattr(seq, "_SAID", set())
    q = jnp.ones((1, 1024, 1, 64), jnp.bfloat16)

    lay = seq.AttentionLayer()
    lay.set_param("attn_impl", "pallas")
    with pytest.raises(RuntimeError, match="attn_impl=pallas.*failed"):
        lay._local_attn()(q, q, q)
    err = capsys.readouterr().err
    assert "flash kernel probe failed for T=1024" in err

    auto = seq.AttentionLayer()  # attn_impl = auto, T >= 1024
    out = auto._local_attn()(q, q, q)
    assert out.shape == q.shape
    err = capsys.readouterr().err
    assert "attn_impl=auto at T=1024" in err and "XLA mha path" in err
    # once per geometry, and the probe itself ran once
    auto._local_attn()(q, q, q)
    assert capsys.readouterr().err == ""
    assert len(seq._FLASH_PROBE) == 1


def test_pool_and_lrn_optins_never_fall_back(monkeypatch):
    """``pool_impl = pallas`` / ``lrn_impl = pallas`` on a chip are
    honoured or fail with the compiler's message: a geometry Mosaic
    refuses (stride-2 pooling) raises out of the layer, it is not
    swapped for the XLA path."""
    from cxxnet_tpu.layers.conv import LRNLayer, MaxPoolingLayer

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pool = MaxPoolingLayer()
    for kv in (("kernel_size", "3"), ("stride", "2"),
               ("pool_impl", "pallas")):
        pool.set_param(*kv)
    x = jax.ShapeDtypeStruct((2, 8, 8, 128), jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="gather"):
        jax.export.export(
            jax.jit(lambda x: pool.apply({}, [x])[0]), platforms=["tpu"])(x)
    lrn = LRNLayer()
    lrn.set_param("lrn_impl", "pallas")
    exp = jax.export.export(
        jax.jit(lambda x: lrn.apply({}, [x])[0]), platforms=["tpu"])(x)
    assert "tpu_custom_call" in exp.mlir_module()


# ----------------------------------------------------------------------
# Mosaic lowering of every kernel, at the smoke's on-chip shapes
def _smoke_kernel_cases():
    sys.path.insert(0, REPO)
    import chip_smoke

    return chip_smoke._kernel_cases(
        chip_smoke.Sizes(rehearsal=False, chips=1), interpret=False,
        abstract=True)


@pytest.mark.parametrize(
    "case", _smoke_kernel_cases(), ids=lambda c: c[0].replace(" ", "_"))
def test_pallas_kernel_lowers_for_tpu_at_smoke_shapes(case):
    name, expect, kernel, _reference, args, _tol = case
    if expect == "raises":
        with pytest.raises(NotImplementedError):
            jax.export.export(jax.jit(kernel), platforms=["tpu"])(*args)
        return
    if name.startswith("routed_experts"):
        # no kernel of ours: the grouped products are the compiler's
        # (tests/test_v5e_qwen3_next.py), the slabs after the first a loop;
        # the layer states the kept matrices' layout, a custom call the
        # exporter has to be told of
        text = jax.export.export(
            jax.jit(kernel), platforms=["tpu"], disabled_checks=[
                jax.export.DisabledSafetyCheck.custom_call(
                    "LayoutConstraint")])(*args).mlir_module()
        assert "ragged_dot" in text and "stablehlo.while" in text
        assert "LayoutConstraint" in text
        return
    exp = jax.export.export(jax.jit(kernel), platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exp.mlir_module(), (
        f"{name}: lowered without a Mosaic call")
