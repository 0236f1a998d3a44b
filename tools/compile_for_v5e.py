"""Compile a conf's scanned train step for a DESCRIBED TPU v5e chip,
here, without the chip, and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 tools/compile_for_v5e.py <conf> [k=v ...]

The trainer is built on the CPU from the conf (``dev`` is forced to
``cpu``) with shapes in the place of its weights and updater state; the
function its ``_scan_step_fn`` would jit is taken as it is and lowered
for ``v5e:2x2``'s first device from shapes alone (the
on-chip-measurement guide, section 2).  What the TPU's compiler refuses
(a program that does not fit 16 GB, a kernel it cannot lower) it refuses
here, at no chip time.  Nothing runs: this prints bytes, never a time.
``DUMP_HLO=<path>`` also writes the compiled module's text there (grep
it for what the compiler made of a layer before paying for a chip run).
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def compile_step(text: str):
    """The scanned train step of the conf ``text``, compiled for the
    first chip of a described ``v5e:2x2``.  The trainer's state is
    shapes throughout (``jax.eval_shape`` of the net's own init and of
    the updaters'): no weight is drawn and nothing is placed, so a
    700M-parameter conf costs the compile alone."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from cxxnet_tpu import config as cfgmod
    from cxxnet_tpu.nnet.trainer import NetTrainer

    entries = cfgmod.split_sections(
        cfgmod.parse_pairs(text + "\ndev = cpu\n")).global_entries
    glob = dict(entries)
    tr = NetTrainer()
    tr.set_params(entries)
    tr.set_param("silent", "1")
    tr._build_net()
    tr._build_mesh()
    tr._bind_mesh_to_layers()
    b = tr.batch_size
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(lambda k: tr.net.init_params(k, b), key)

    def updater_states(traced):
        tr.params = traced
        tr._build_updaters()
        return tr.ustates

    tr.ustates = jax.eval_shape(updater_states, params)
    tr.params = params
    tr.aux = jax.eval_shape(lambda: tr.net.init_aux(b))
    k = int(glob.get("scan_steps", 8))
    with_out = bool(int(glob.get("eval_train", 1)))
    taken = {}

    def take(fn, *a, **kw):
        taken["fn"] = fn
        return fn

    tr._jit = take
    tr._scan_step_fn(k, True, with_out)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=chip),
            tree)

    data = jax.ShapeDtypeStruct(
        (k,) + tuple(tr.net.input_node_shape(b)), jnp.float32, sharding=chip)
    lw = sum(hi - lo for lo, hi in tr.graph.label_range[1:]) or 1
    labels = jax.ShapeDtypeStruct((k, b, lw), jnp.float32, sharding=chip)
    args = (shaped(tr.params), shaped(tr.ustates), shaped(tr.aux), data,
            labels, shaped(key), shaped(jnp.asarray(0, jnp.int32)))
    return jax.jit(taken["fn"], donate_argnums=(0, 1, 2)).lower(
        *args).compile()


def live_at_peak_bytes(compiled) -> int:
    """Arguments + outputs that alias none + temporaries: what the
    program holds at its fullest."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def main(argv) -> int:
    with open(argv[0], "r", encoding="utf-8") as f:
        compiled = compile_step(f.read() + "\n" + "\n".join(argv[1:]))
    m = compiled.memory_analysis()
    out = {n: int(getattr(m, n)) for n in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["live_at_peak_bytes"] = live_at_peak_bytes(compiled)
    print(json.dumps(out, indent=1))
    if os.environ.get("DUMP_HLO"):
        with open(os.environ["DUMP_HLO"], "w", encoding="utf-8") as f:
            f.write(compiled.as_text())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
