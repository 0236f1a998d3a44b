"""Device time a training step under the multi-token-prediction module:
every conf layer from the first whose name begins ``mtp_`` to the end of
the netconfig (the builder writes the module last, after the main head
and loss; a ``shared[...]`` line carries no name of its own, hence by
position), forward, recomputed forward and backward.  It overlaps
``mla_ms_step`` and ``moe_ms_step`` by the module's one block and is the
only reader of the shared embedding, ``eh_proj`` and the second head
product; the module's grouped products are not in it (the compiler names
them ``ragged-dot-*`` and drops their layer: ``moe_ms_step`` has them).
The sum of the ``XLA Ops`` events of the traced chunks under those
layers' scopes (``lib/scopes.py``) / the steps traced.  ``None`` without
a trace or where the conf has no such layer."""

from benchmarks.lib import scopes

LAYER = "layers and kernels"
UNIT = "ms/step"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

PREFIX = "mtp_"


def read(run):
    got = scopes.by_scope(run)
    conf = scopes.conf_layers(got["out"]) if got else None
    if conf is None:
        return None
    first = next((i for i, (_, name) in enumerate(conf[1])
                  if name.startswith(PREFIX)), None)
    if first is None:
        return None
    ns = sum(row["total"] for i, row in got["layers"].items() if i >= first)
    return ns / 1e6 / got["steps"] if ns else None
