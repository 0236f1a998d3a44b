"""The latent-attention layers' score and value products' share of
their roofline: the least time the chip could take for them in one
training step — their operations / the bf16 peak, the operations
counted by the configuration's reference module (``mla_core_flops``: 3 x
2 x heads x (nope + rope + value width) a (query, key) pair and layer)
at the pairs the run itself counted: ``attn_pairs`` / steps over the
window's whole rounds, the pairs a causal query of its OWN DOCUMENT may
see in the rows fed (``io/tokens.py``), so that a kernel that skips
other documents' blocks reads the same work — over ``mla_core_ms_step``.
Bound by operations: the products' bytes (q, k, v and o once each way)
are under a tenth of that time.  ``None`` without a trace, without the
scope or without the counter."""

from benchmarks.lib import scopes, stage_scopes

LAYER = "layers and kernels"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "train_samples_s_chip"

KIND = "latent_attention"
REFERENCE = "benchmarks/references/joyai_llm_flash.py"


def read(run):
    ms = stage_scopes.ms_per_step(run, KIND, ("core",))
    pairs = stage_scopes.counter(run, "attn_pairs")
    if not ms or pairs is None or not run.get("peaks"):
        return None
    mod, net = scopes.reference_of(run, REFERENCE)
    least_s = (mod.mla_core_flops(net, pairs[0] / pairs[1])
               / run["peaks"]["bf16_flops"])
    return 100.0 * least_s / run["chips"] / (ms / 1e3)
