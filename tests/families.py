"""The token-model families, said once: one row a family — its builder,
the small keywords its tests build it at, its plain reference under
``benchmarks/references/`` (a file that imports nothing of the program),
what its small conf must hold and count, what its published defaults
come to — and the helpers every family's tests used to write for
themselves.  Not collected: ``tests/test_families.py`` runs what all
families share over this table, a family's own file
(``test_<family>_layers.py``) keeps only its own mechanism, and
``tests/test_v5e_<family>.py`` its compiles for a described chip.

A new family is a row here and one file of its own mechanism
(ROADMAP D0).
"""

import dataclasses
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from cxxnet_tpu import config as cfgmod  # noqa: E402
from cxxnet_tpu import models  # noqa: E402
from cxxnet_tpu.layers import create_layer  # noqa: E402
from cxxnet_tpu.models.builders import (GRANITE_H_PERIOD,  # noqa: E402
                                        NEMOTRON_H_STAGE)
from cxxnet_tpu.nnet.trainer import NetTrainer  # noqa: E402


# ----------------------------------------------------------------------
# helpers
def make(kind, in_shapes, seed=0, **cfg):
    """A layer of ``kind`` at ``cfg``, its seeded parameters and its
    output shapes."""
    lay = create_layer(kind)
    for k, v in cfg.items():
        lay.set_param(k, str(v))
    out = lay.infer_shape(in_shapes)
    return lay, lay.init_params(jax.random.PRNGKey(seed), in_shapes), out


def strs(cfg):
    """A layer's keys as a reference reads them: a conf's strings."""
    return {k: str(v) for k, v in cfg.items()}


def rows_with_documents(seed, n, t, vocab=50):
    """Ids with separators inside every row, none at its first token."""
    r = np.random.RandomState(seed)
    ids = r.randint(1, vocab, (n, t))
    ids[:, t // 3] = 0
    ids[0, t // 2 + 1] = 0
    return ids.astype(np.float32)


def with_bias(p, seed, sigma=0.2):
    """``p`` with its selection bias off 0: seeded normal draws."""
    return dict(p, score_bias=jnp.asarray(
        sigma * np.random.RandomState(seed).randn(*p["score_bias"].shape),
        jnp.float32))


def expert_shares(cfg, p, x, ranks, held):
    """What each of ``ranks`` ranks computes of the ``routed_experts``
    layer ``cfg`` from its ``held`` experts' slices of ``p`` (router, bias
    and the shared expert whole), and the pairs they computed in all."""
    parts, pairs = [], 0
    for first in range(0, ranks * held, held):
        lay, _, _ = make("routed_experts", [x.shape], first_expert=first,
                         nheld=held, **cfg)
        mine = dict(p, wmat=p["wmat"][first:first + held],
                    wproj=p["wproj"][first:first + held])
        # (eagerly: the ranks' layers share every operation's program)
        (y,), state = lay.apply_stateful(mine, lay.init_aux([x.shape]), [x])
        parts.append(np.asarray(y, np.float64))
        pairs += int(state["pairs"])
    return parts, pairs


def held_against(prog, plain, p, x, tags, atol=5e-5, y_atol=None, zero=()):
    """The program's ``prog(p, x)`` against the reference's ``plain``:
    forward (to ``y_atol`` where given), and the gradients of the input
    and of the leaves ``tags`` under ``sum(sin(.))``, none of them zero
    but those of ``zero``, which are zero in both;
    each side compiled once.  Returns the program's output, the
    reference's, and the program's gradients."""
    def both(fn):
        return jax.jit(lambda q, a: (fn(q, a), jax.grad(
            lambda q, a: jnp.sum(jnp.sin(fn(q, a))), argnums=(0, 1))(q, a)))

    with jax.default_matmul_precision("highest"):
        ya, ga = both(prog)(p, x)
        yb, gb = both(plain)(p, x)
    np.testing.assert_allclose(ya, yb, atol=y_atol or atol)
    np.testing.assert_allclose(ga[1], gb[1], atol=atol)
    for tag in tuple(tags) + tuple(zero):
        np.testing.assert_allclose(ga[0][tag], gb[0][tag], atol=atol,
                                   err_msg=tag)
        assert (np.abs(np.asarray(ga[0][tag])).max() == 0) == (
            tag in zero), tag
    return ya, yb, ga


def through_cos(fn, xs):
    """``fn``'s output in float32 and the cotangents of ``xs`` under
    ``cos`` of it — forward and every gradient of a scan — compiled
    once."""
    def run(*a):
        out, back = jax.vjp(lambda *a: fn(*a).astype(jnp.float32), *a)
        return (out,) + back(jnp.cos(out))

    return jax.jit(run)(*xs)


def trainer(text, init=True):
    """The program's trainer of the conf ``text``; ``init = False``
    builds the net and draws no weight."""
    tr = NetTrainer()
    tr.set_params(cfgmod.split_sections(
        cfgmod.parse_pairs(text)).global_entries)
    tr.set_param("silent", "1")
    if init:
        tr.init_model()
    else:
        tr._build_net()
    return tr


@functools.lru_cache(maxsize=None)
def reference(family):
    """The family's plain reference, loaded by its path as
    ``benchmarks/run.py`` loads it."""
    from benchmarks import run

    return run.load_file(os.path.join(
        ROOT, "benchmarks", "references", FAMILIES[family].reference),
        "reference")


def layer_index(key):
    """``l4_moe1`` -> 4: the reference keys a layer by its place."""
    return int(key[1:key.index("_")])


def in_program_s_keys(tr, made, net=None):
    """The reference's weights ``made`` under the trainer's keys; with
    ``net``, the two agree on every leaf's shape first."""
    if net is not None:
        assert {layer_index(k): {t: tuple(v.shape) for t, v in tags.items()}
                for k, tags in tr.params.items()} == {
            i: {t: tuple(s) for t, s in tags.items()}
            for i, tags in net.pshapes.items() if tags}
    return {k: {t: made[layer_index(k)][t] for t in tags}
            for k, tags in tr.params.items()}


def with_reference_weights(text, family, seed, batch=1):
    """``(trainer, net)``: the program's trainer with the reference's
    weights from the seed in its place, as ``benchmarks/run.py`` puts
    them."""
    ref = reference(family)
    net = ref.describe(text, batch)
    tr = trainer(text)
    tr.params = in_program_s_keys(tr, ref.make_weights(net, seed), net)
    tr._place_state()
    return tr, net


def seeded_rows(family, net, seed, scan):
    """The reference's seeded chunk of ``scan`` steps, with separators
    put around a chunk's edge of row 0."""
    data, labels = reference(family).seeded_chunk(net, seed, scan)
    data[0, 0, 5] = data[0, 0, 15] = data[0, -1, 16] = 0
    return data, labels


def parameter_counts(text):
    """``{layer key: parameters}`` of the conf's net, from shapes."""
    tr = trainer(text, init=False)
    shapes = jax.eval_shape(lambda k: tr.net.init_params(k, 1),
                            jax.random.PRNGKey(0))
    return {key: sum(int(np.prod(v.shape)) for v in tags.values())
            for key, tags in shapes.items()}


def chunk_gaps(tr, family, net, seed, data, labels):
    """One ``update_scan`` of the trainer against the reference's
    ``train_chunk`` from the same seeded weights, the widest gap of each
    kind: ``loss``, relative; ``dw`` and ``dm``, of a leaf's CHANGE and of
    adam's first moment, element for element against the largest element
    of the reference's leaf; ``w_abs`` and ``m_abs``, absolute."""
    ref = reference(family)
    start = jax.device_get(ref.make_weights(net, seed))
    with jax.default_matmul_precision("highest"):
        losses = np.asarray(tr.update_scan(data, labels, sync=True),
                            np.float64).reshape(-1)
        # (the reference's chunk donates the weights it is handed)
        rl, rp, rm = ref.train_chunk(net, ref.make_weights(net, seed), data,
                                     labels, None)
    pp = jax.device_get(tr.params)
    m1 = ref.program_update_state(
        {layer_index(k): v for k, v in jax.device_get(tr.ustates).items()})
    rl = np.asarray(rl, np.float64).reshape(-1)
    gaps = dict(loss=float((np.abs(losses - rl) / np.abs(rl)).max()),
                dw=0.0, dm=0.0, w_abs=0.0, m_abs=0.0)

    def widen(name, gap):
        gaps[name] = max(gaps[name], float(gap))

    for key, tags in pp.items():
        i = layer_index(key)
        for t, v in tags.items():
            want = rp[i][t] - start[i][t]
            widen("w_abs", np.abs(v - rp[i][t]).max())
            widen("m_abs", np.abs(m1[i][t] - rm[i][t]).max())
            if not np.abs(want).max():   # a share's router: put in both
                assert not np.abs(v - start[i][t]).max(), (key, t)
                continue
            widen("dw", np.abs(v - start[i][t] - want).max()
                  / np.abs(want).max())
            widen("dm", np.abs(m1[i][t] - rm[i][t]).max()
                  / np.abs(rm[i][t]).max())
    return gaps


# ----------------------------------------------------------------------
# the table
@dataclasses.dataclass(frozen=True)
class Family:
    builder: object           # cxxnet_tpu.models.<family>_conf
    tiny: dict                # the widths its tests build it at
    reference: str            # its file under benchmarks/references/
    # -- test_the_builder_s_conf_trains_and_counts_its_pairs
    conf_has: dict = None     # piece of the tiny conf -> how many times
    conf_lacks: tuple = ()
    aux: frozenset = None     # the layers that keep counters
    biased: str = None        # a layer whose selection bias is set first
    first_loss: tuple = None  # bounds of the first step's loss, in ln(vocab)
    counts: dict = None       # counter -> (lo, hi) over two chunks
    unmoved: tuple = ()       # counters two chunks on the CPU leave alone
    also: object = None       # the family's own refusals and variants
    # -- test_the_published_defaults_are_what_the_issue_reckoned
    defaults: dict = None     # builder keywords beside dev = cpu
    layers: dict = None       # layer key -> parameters at the defaults
    total: int = None
    defaults_also: object = None
    # -- the whole small net and the adam chunk against the reference
    whole_net: dict = None    # tiny's keywords for the whole-net case
    grad_tol: dict = None     # atol, or rtol of the leaf's largest
    whole_also: object = None
    chunks: dict = None       # case id -> (keywords, bounds of chunk_gaps)


GRANITE = dict(vocab=64, hidden=64, mamba_heads=4, mamba_head_dim=32,
               mamba_state=16, mamba_chunk=16, attn_heads=4, attn_kv_heads=2,
               mlp_hidden=96, dev="cpu", eta=0.001, scan_steps=4,
               seq_len=48, batch_size=2, compute_dtype="float32")

QWEN3_NEXT = dict(vocab=64, seq_len=64, hidden=32, layer_types="lf",
                  linear_key_heads=2, linear_value_heads=4, linear_key_dim=8,
                  linear_value_dim=8, linear_chunk=16, attn_heads=4,
                  attn_kv_heads=2, head_dim=16, num_experts=16,
                  experts_per_tok=3, expert_hidden=24, shared_hidden=24,
                  experts_held=4, dev="cpu", compute_dtype="float32",
                  scan_steps=4)

JOYAI = dict(vocab=64, seq_len=64, hidden=32, num_layers=2, attn_heads=4,
             q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
             qk_rope_head_dim=4, v_head_dim=8, mlp_hidden=48, num_experts=16,
             experts_per_tok=3, expert_hidden=24, shared_hidden=24,
             experts_held=4, dev="cpu", compute_dtype="float32",
             scan_steps=4)

NEMOTRON_H = dict(vocab=64, seq_len=32, hidden=32, pattern="ME*E",
                  mamba_heads=4, mamba_head_dim=8, mamba_groups=2,
                  mamba_state=8, mamba_chunk=8, attn_heads=4, attn_kv_heads=2,
                  head_dim=16, num_experts=8, experts_per_tok=3,
                  expert_hidden=16, latent_hidden=16, shared_hidden=24,
                  experts_held=4, num_nextn_predict_layers=1, batch_size=2,
                  dev="cpu", scan_steps=2, compute_dtype="float32")

AFMOE = dict(vocab=64, seq_len=64, hidden=32, layer_types="ssf",
             num_dense_layers=1, sliding_window=16, attn_heads=4,
             attn_kv_heads=2, head_dim=16, mlp_hidden=48, num_experts=16,
             experts_per_tok=3, expert_hidden=24, shared_hidden=24,
             experts_held=4, dev="cpu", compute_dtype="float32",
             scan_steps=4)

SMALLTHINKER = dict(vocab=64, seq_len=64, hidden=32,
                    sliding_window_layout=(0, 1), rope_layout=(0, 1),
                    sliding_window=16, attn_heads=6, attn_kv_heads=2,
                    head_dim=16, num_experts=16, experts_per_tok=3,
                    expert_hidden=24, experts_held=4, dev="cpu",
                    compute_dtype="float32", scan_steps=4)

LING = dict(vocab=64, seq_len=64, hidden=32, num_layers=3, layer_group_size=3,
            attn_heads=2, head_dim=16, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, mlp_hidden=48, num_experts=16,
            experts_per_tok=3, n_group=4, topk_group=2, expert_hidden=24,
            shared_hidden=24, experts_held=4, dev="cpu",
            compute_dtype="float32", scan_steps=4)

FLASH_COUNTERS = ("attn_tokens_flash", "attn_blocks", "attn_blocks_unmasked",
                  "attn_tokens_bwd_fused")


def _joyai_also(text):
    # without the module: the main model alone
    bare = models.joyai_llm_flash_conf(**dict(JOYAI,
                                              num_nextn_predict_layers=0))
    assert "mtp_" not in bare and bare.count("= softmax") == 1
    with pytest.raises(ValueError, match="depth of 0 or 1"):
        models.joyai_llm_flash_conf(num_nextn_predict_layers=2)


def _nemotron_also(text):
    """One conf layer a pattern letter, the module last under ``mtp_``
    names with the shared embedding and head, two losses."""
    kinds = re.findall(r"^layer\[[^\]]*\] = (\S+)", text, re.M)
    assert kinds == [
        "embedding:embed", "mamba2:mixer0", "routed_experts:moe1",
        "attention:attn2", "routed_experts:moe3", "rms_norm:norm_f",
        "lm_head:head", "softmax", "token_shift:mtp_shift", "shared[embed]",
        "rms_norm:mtp_enorm", "rms_norm:mtp_hnorm", "concat:mtp_cat",
        "fullc:mtp_eh_proj", "attention:mtp_attn0",
        "routed_experts:mtp_moe1", "rms_norm:mtp_norm_f", "shared[head]",
        "softmax"]


def _afmoe_also(text):
    assert f"multiplier = {32 ** 0.5!r}" in text
    fed = models.afmoe_conf(**dict(AFMOE, token_file="tokens.bin"))
    assert "  attn_window = 16\n" in fed
    assert "attn_window" not in models.afmoe_conf(**dict(
        AFMOE, layer_types="ff", token_file="tokens.bin"))
    with pytest.raises(ValueError, match="string of s and f"):
        models.afmoe_conf(layer_types="sxf")
    with pytest.raises(ValueError, match="num_dense_layers"):
        models.afmoe_conf(layer_types="sf", num_dense_layers=3)


def _smallthinker_also(text):
    # every expert layer reads two nodes: the attention's output for the
    # experts, its input for the router, under the attention's norm
    for i in (0, 1):
        assert (f"layer[x{i},h{i}->h{i + 1}] = routed_experts:moe{i}\n"
                f"  route_norm = attn{i}\n") in text
    fed = models.smallthinker_conf(**dict(SMALLTHINKER,
                                          token_file="tokens.bin"))
    assert "  attn_window = 16\n" in fed
    assert "attn_window" not in models.smallthinker_conf(**dict(
        SMALLTHINKER, sliding_window_layout=(0, 0), token_file="tokens.bin"))
    # the two layouts are the config's own keys, read apart: a windowed
    # layer without positions is written as asked
    odd = models.smallthinker_conf(**dict(SMALLTHINKER,
                                          rope_layout=(1, 0)))
    assert odd.index("rotary_dim") < odd.index("  window = 16")
    with pytest.raises(ValueError, match="one entry a layer each"):
        models.smallthinker_conf(rope_layout=(0, 1, 1))
    with pytest.raises(ValueError, match="list of 0 and 1"):
        models.smallthinker_conf(rope_layout=(0, 2, 1, 1))


def _ling_also(text):
    # a period's last layer is the latent attention, the others the rule
    kinds = re.findall(r"^layer\[[^\]]*\] = (\w+):", text, re.M)
    assert kinds == ["embedding", "kimi_delta", "gated_mlp", "kimi_delta",
                     "routed_experts", "latent_attention", "routed_experts",
                     "rms_norm", "lm_head"]
    assert "q_rank = 0\n  kv_rank = 16" in text
    assert "mtp_" not in text and text.count("= softmax") == 1


def _ling_defaults_also(text, counts):
    """ISSUE 49's table: 63.05M a KDA mixer, 31.97M the latent attention,
    47.19M the dense MLP, 54.40M an expert layer's routed part at 8 held,
    100.60M the vocabulary's two matrices: 767.0M = 12.27 GB."""
    assert round(sum(counts.values()) * 16 / 1e9, 2) == 12.27
    kinds = re.findall(r"^layer\[[^\]]*\] = (\w+):", text, re.M)
    assert kinds.count("kimi_delta") == 5
    assert kinds.index("latent_attention") == 11    # layer 5's mixer
    assert text.count("  n_group = 8\n  topk_group = 4\n") == 5
    assert "label_width = 8192" in text and "nheld = 8" in text
    # a sixteenth expert a layer would not fit: 1003M = 16.0 GB
    assert round((sum(counts.values()) + 5 * 8 * 3 * 2560 * 768) * 16
                 / 1e9, 1) == 16.0


def _ling_whole_also(tr, params, ids, lab, loss, grads):
    assert 0.9 * np.log(64) < float(loss) < 1.6 * np.log(64)
    # whole layers: the routers learn; the bias never does
    for key in ("l4_moe1", "l6_moe2"):
        assert np.abs(np.asarray(grads[key]["wgate"])).max() > 0
        assert np.abs(np.asarray(grads[key]["score_bias"])).max() == 0


def _joyai_defaults_also(text, counts):
    assert round(sum(counts.values()) * 16 / 1e9, 2) == 10.89


def _nemotron_defaults_also(text, counts):
    """ISSUE 40's count from config.json's keys at one rank's share —
    13.7M a mixer, 5.25M the attention — and an expert layer at 8 held
    with its shared expert WHOLE (98.6M, where the issue divided its
    columns by 8 for 60.0M): 700.9M parameters without the prediction
    module, 838.2M with it."""
    cell = models.nemotron_h_conf(dev="cpu")
    assert cell.count("= mamba2:") == NEMOTRON_H_STAGE.count("M") == 5
    assert cell.count("= attention:") == 1 and "mtp_" not in cell
    assert cell.count("= routed_experts:") == 5
    assert text.startswith(cell[:cell.index("netconfig = end")])
    assert text.count("= attention:") == 2
    assert text.count("= routed_experts:") == 6
    module = sum(n for key, n in counts.items() if "_mtp_" in key)
    assert sum(counts.values()) - module == 700_865_520


def _afmoe_defaults_also(text, counts):
    # q | gate, k, v fused; the output projection; q/k norms; the sandwich
    attn = 2048 * (2 * 4096 + 2 * 512) + 4096 * 2048 + 2 * 128 + 2 * 2048
    assert counts["l1_attn0"] == attn
    assert round(sum(counts.values()) * 16 / 1e9, 2) == 8.07
    # what ISSUE 42 reckoned for 16 held: 705.4M, 11.29 GB
    assert sum(counts.values()) + 4 * 8 * 3 * 2048 * 1024 == 705_474_304
    assert text.count("  window = 2048\n") == 4 and "seq_len" not in text
    assert "label_width = 16384" in text and "nheld = 8" in text


def _smallthinker_defaults_also(text, counts):
    # q, k, v fused; the output projection; the norm
    attn = 2560 * (3584 + 2 * 512) + 3584 * 2560 + 2560
    assert counts["l1_attn0"] == counts["l7_attn3"] == attn
    assert round(sum(counts.values()) * 16 / 1e9, 2) == 8.95
    # the guide's floor, 8 held: 370.5M
    assert sum(counts.values()) - 4 * 8 * 3 * 2560 * 768 == 370_547_200
    # a full layer without positions, then three rotary ones under the
    # window; every layer routes on the attention's input
    assert text.count("  window = 4096\n") == 3 == text.count(
        "  rope_theta = 1500000.0\n")
    assert text.index("layer[h1,0->x1]") < text.index("  window = 4096")
    assert text.count("  route_norm = attn") == 4
    assert "label_width = 16384" in text and "nheld = 16" in text


def _granite_whole_also(tr, params, ids, lab, loss, grads):
    """The softmax layer leaves probabilities in the logits' node, so the
    logits are held through them: the mean of -log p[label] is the
    reference's loss."""
    logits_node = tr.net.graph.node_index_of("logits")
    probs = jax.jit(lambda p: tr.net.forward(
        p, jnp.asarray(ids), labels=jnp.asarray(lab),
        train=True)[0][logits_node])(params)
    picked = np.take_along_axis(np.asarray(probs),
                                lab.astype(np.int32)[..., None], axis=-1)
    np.testing.assert_allclose(-np.log(picked).mean(), float(loss),
                               rtol=1e-5)
    assert probs.shape == (2, 32, 64)


def _afmoe_whole_also(tr, params, ids, lab, loss, grads):
    assert 0.9 * np.log(64) < float(loss) < 1.6 * np.log(64)
    # a share's routers and the bias get no gradient; every norm does
    assert np.abs(np.asarray(grads["l4_moe1"]["wgate"])).max() == 0
    assert np.abs(np.asarray(grads["l4_moe1"]["score_bias"])).max() == 0
    for key in ("l1_attn0", "l2_mlp0", "l3_attn1", "l4_moe1"):
        assert np.abs(np.asarray(grads[key]["postnorm"])).max() > 0


def _smallthinker_whole_also(tr, params, ids, lab, loss, grads):
    """A WHOLE layer (nheld = nexpert): the router learns, and its
    gradient reaches the attention's norm weight and the stream before
    the attention (the reference's sum of both uses was held above)."""
    assert 0.9 * np.log(64) < float(loss) < 1.6 * np.log(64)
    for key in ("l2_moe0", "l4_moe1"):
        assert np.abs(np.asarray(grads[key]["wgate"])).max() > 0
    assert np.abs(np.asarray(grads["l1_attn0"]["norm"])).max() > 0


FAMILIES = {
    "granite_h": Family(
        builder=models.granite_h_conf, tiny=GRANITE,
        reference="granite_hybrid.py",
        whole_net=dict(layer_types="mam", seq_len=32), grad_tol=dict(
            rtol=2e-4), whole_also=_granite_whole_also,
        # loss, every weight and every first moment after a 4-step
        # ``update_scan``: the mixer alone, the attention layer alone (4
        # query heads over 2 key/value heads, document mask, the
        # multiplier), and nine mixers around one attention layer
        chunks={name: (dict(layer_types=pattern),
                       dict(loss=1e-5, dw=2e-3, dm=2e-3))
                for name, pattern in (("mixer", "m"), ("attention", "a"),
                                      ("ten_layers", GRANITE_H_PERIOD))}),
    "qwen3_next": Family(
        builder=models.qwen3_next_conf, tiny=QWEN3_NEXT,
        reference="qwen3_next.py",
        # adam at one rate for everything, the routers too: a share's
        # router stays put because its gradient is zero (layers/moe.py)
        conf_has={"= gated_deltanet:": 1, "= routed_experts:": 2,
                  "rotary_dim = 4": 1, "rope_theta = 10000000.0": 1},
        conf_lacks=("tied", "wgate", ":lr"),
        aux=frozenset({"l1_gdn0", "l2_moe0", "l4_moe1", "l3_attn1"}),
        # 8 steps x 64 tokens x 3 picks x 2 layers, a quarter of them held
        counts={"expert_pairs": (0.6 * 768, 1.4 * 768)},
        unmoved=("expert_pairs_dropped",),
        defaults={},
        layers={"l1_gdn0": 33_718_464 + 2048,     # the mixer and its norm
                "l7_attn3": 27_263_488 + 2048,
                "l2_moe0": 104_859_648 + 2048},
        total=625.7),                             # M; x 16 B = 10.01 GB
    "joyai_llm_flash": Family(
        builder=models.joyai_llm_flash_conf, tiny=JOYAI,
        reference="joyai_llm_flash.py",
        conf_has={"= latent_attention:": 3, "= routed_experts:": 2,
                  "= gated_mlp:": 1, "rope_theta = 32000000.0": 3,
                  "routed_scale = 2.5": 2},
        conf_lacks=("tied", "wgate", ":lr"),
        aux=frozenset({"l4_moe1", "l15_mtp_moe", "l1_mla0", "l3_mla1",
                       "l14_mtp_mla"}),
        biased="l4_moe1",
        # 8 steps x 64 tokens x 3 picks x 2 layers, a quarter of them
        # held; the latent layers count their tokens, and none by the
        # kernels off the TPU (so no block of theirs either): x 3 layers
        counts={"expert_pairs": (0.5 * 768, 1.5 * 768),
                "attn_tokens": (8 * 64 * 3, 8 * 64 * 3)},
        unmoved=FLASH_COUNTERS, also=_joyai_also,
        defaults={},
        layers={"l1_mla0": 26_347_520 + 2048,     # the mixer and its norm
                "l2_mlp0": 3 * 2048 * 7168 + 2048,
                # router + bias, 16 held experts, the shared one, the norm
                "l4_moe1": 256 * 2048 + 256 + 17 * 3 * 2048 * 768 + 2048,
                "l19_mtp_eh_proj": 4096 * 2048,
                "l0_embed": 16160 * 2048, "l12_head": 16160 * 2048},
        total=680_441_088,                        # x 16 B = 10.89 GB
        defaults_also=_joyai_defaults_also,
        whole_net={}, grad_tol=dict(atol=2e-6)),
    "nemotron_h": Family(
        builder=models.nemotron_h_conf, tiny=NEMOTRON_H,
        reference="nemotron_h.py",
        conf_has={"= mamba2:": 1, "= routed_experts:": 3},
        conf_lacks=("gated_mlp", "rotary"),
        aux=None,
        # both losses at uniform predictions: (1 + 0.3) ln 64, about
        first_loss=(1.2, 1.5),
        # 4 steps x 64 tokens x 3 picks x 3 layers, half of them held
        counts={"expert_pairs": (0.5 * 1152, 1.5 * 1152)},
        unmoved=("expert_pairs_dropped",), also=_nemotron_also,
        defaults=dict(num_nextn_predict_layers=1),
        layers={"l1_mixer0": 13_708_592,          # 13.70M + its pre-norm
                "l8_attn7": 5_242_880 + 4096,
                "l2_moe1": 98_570_752,            # 44.04M of it the 8 held,
                "l22_mtp_moe1": 98_570_752,       # 44.04M the shared
                "l20_mtp_eh_proj": 2 * 4096 * 4096},
        total=838.2, defaults_also=_nemotron_defaults_also),
    "afmoe": Family(
        builder=models.afmoe_conf, tiny=AFMOE, reference="afmoe.py",
        conf_has={"= attention:": 3, "  window = 16\n": 2,
                  "  rotary_dim = 16": 2, "= routed_experts:": 2,
                  "= gated_mlp:": 1, "postnorm = 1": 6,
                  "routed_scale = 2.826": 2},
        conf_lacks=("tied", "iter = tokens"),
        aux=frozenset({"l1_attn0", "l3_attn1", "l5_attn2", "l4_moe1",
                       "l6_moe2"}),
        # mha's rows computed them off the TPU: no block of the kernels'
        counts={"attn_tokens": (8 * 64 * 3, 8 * 64 * 3)},
        unmoved=("attn_blocks",), also=_afmoe_also,
        defaults={},
        layers={"l1_attn0": 27_267_328, "l9_attn4": 27_267_328,
                "l2_mlp0": 3 * 2048 * 6144 + 2 * 2048,
                # router + bias, 8 held experts and the shared one, two norms
                "l4_moe1": 128 * 2048 + 128 + 9 * 3 * 2048 * 1024 + 2 * 2048,
                "l0_embed": 25024 * 2048, "l12_head": 25024 * 2048},
        total=504_147_712,                        # x 16 B = 8.07 GB
        defaults_also=_afmoe_defaults_also,
        # a sliding layer with the dense MLP, a full one with the experts
        whole_net=dict(layer_types="sf"), grad_tol=dict(atol=3e-6),
        whole_also=_afmoe_whole_also,
        chunks={"": (dict(), dict(loss=2e-5, w_abs=2e-5, m_abs=2e-6))}),
    "smallthinker": Family(
        builder=models.smallthinker_conf, tiny=SMALLTHINKER,
        reference="smallthinker.py",
        conf_has={"= attention:": 2, "  window = 16\n": 1,
                  "  rotary_dim = 16": 1, "= routed_experts:": 2,
                  "  route_norm = attn": 2, "  expert_act = reglu": 2},
        conf_lacks=("tied", "iter = tokens", "gated_mlp", "qk_norm",
                    "shared_hidden", "postnorm"),
        aux=frozenset({"l1_attn0", "l3_attn1", "l2_moe0", "l4_moe1"}),
        # 8 steps x 64 tokens x 3 picks x 2 layers, a quarter of them held
        counts={"expert_pairs": (0.5 * 768, 1.5 * 768),
                "attn_tokens": (8 * 64 * 2, 8 * 64 * 2)},
        unmoved=("attn_blocks", "expert_pairs_dropped"),
        also=_smallthinker_also,
        defaults={},
        layers={"l1_attn0": 20_974_080,
                # router, 16 held experts, the norm
                "l2_moe0": 64 * 2560 + 16 * 3 * 2560 * 768 + 2560,
                "l0_embed": 18992 * 2560, "l10_head": 18992 * 2560},
        total=559_290_880,                        # x 16 B = 8.95 GB
        defaults_also=_smallthinker_defaults_also,
        # whole: the router's gradient reaches n1 and the stream before
        # the attention
        whole_net=dict(experts_held=16), grad_tol=dict(atol=3e-6),
        whole_also=_smallthinker_whole_also,
        chunks={"share": (dict(), dict(loss=2e-5, w_abs=2e-5, m_abs=2e-6)),
                "whole": (dict(experts_held=16),
                          dict(loss=2e-5, w_abs=2e-5, m_abs=2e-6))}),
    "bailing_hybrid": Family(
        builder=models.bailing_hybrid_conf, tiny=LING,
        reference="bailing_hybrid.py",
        conf_has={"= kimi_delta:": 2, "= latent_attention:": 1,
                  "= routed_experts:": 2, "= gated_mlp:": 1,
                  "  lower_bound = -5.0\n": 2, "  out_gate = head\n": 1,
                  "  n_group = 4\n  topk_group = 2\n": 2,
                  "rope_theta = 6000000.0": 1, "routed_scale = 2.5": 2},
        conf_lacks=("tied", "wgate", ":lr", "q_norm"),
        aux=frozenset({"l1_kda0", "l3_kda1", "l5_mla2", "l4_moe1",
                       "l6_moe2"}),
        biased="l4_moe1",
        # 8 steps x 64 tokens x 3 picks x 2 layers, a quarter of them
        # held; two mixers count their tokens through the scan, one the
        # attention's, none by kernels off the TPU
        counts={"expert_pairs": (0.4 * 768, 1.6 * 768),
                "kda_scan_tokens": (8 * 64 * 2, 8 * 64 * 2),
                "attn_tokens": (8 * 64, 8 * 64)},
        unmoved=FLASH_COUNTERS + ("kda_scan_tokens_fused",), also=_ling_also,
        defaults={},
        layers={"l1_kda0": 63_049_888 + 2560,     # the mixer and its norm
                "l11_mla5": 31_965_696 + 2560,
                "l2_mlp0": 3 * 2560 * 6144 + 2560,
                # router + bias, 8 held experts, the shared one, the norm
                "l4_moe1": 512 * 2560 + 512 + 9 * 3 * 2560 * 768 + 2560,
                "l0_embed": 19648 * 2560, "l14_head": 19648 * 2560},
        total=767.0, defaults_also=_ling_defaults_also,
        whole_net=dict(experts_held=16), grad_tol=dict(atol=3e-6),
        whole_also=_ling_whole_also,
        chunks={"share": (dict(), dict(loss=2e-5, w_abs=2e-5, m_abs=2e-6))}),
}
