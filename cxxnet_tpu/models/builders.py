"""Conf-text builders for the model zoo.  See package docstring."""

from __future__ import annotations

import math
from typing import List, Sequence


def _iter_block(
    kind: str, nsample: int, input_shape: str, nclass: int, threadbuffer: bool = False
) -> str:
    """A synthetic data/eval section (benchmarks; real runs swap in
    mnist/imgbin sections with the same keys)."""
    tb = "iter = threadbuffer\n" if threadbuffer else ""
    return (
        f"{kind} = {'train' if kind == 'data' else 'test'}\n"
        "iter = synthetic\n"
        f"  nsample = {nsample}\n"
        f"  input_shape = {input_shape}\n"
        f"  nclass = {nclass}\n"
        "  label_width = 1\n"
        f"{tb}iter = end\n"
    )


def _tail(
    batch_size: int,
    input_shape: str,
    num_round: int,
    eta: float = 0.01,
    extra: str = "",
    dev: str = "tpu",
    scan_steps: int = 8,
) -> str:
    # scan_steps: the CLI runs k batches as ONE device program
    # (doc/tasks.md); the trainer ignores the key in programmatic use
    return (
        f"input_shape = {input_shape}\n"
        f"batch_size = {batch_size}\n"
        f"dev = {dev}\n"
        f"num_round = {num_round}\n"
        f"max_round = {num_round}\n"
        "updater = sgd\n"
        f"eta = {eta}\n"
        "momentum = 0.9\n"
        "wd = 0.0005\n"
        f"scan_steps = {scan_steps}\n"
        "metric = error\n"
        "eval_train = 1\n"
        "print_step = 100\n"
        f"{extra}"
    )


# ---------------------------------------------------------------------------
def mnist_mlp_conf(
    batch_size: int = 100, synthetic: bool = True, dev: str = "tpu"
) -> str:
    """3-layer MLP (MNIST.conf parity: fullc 160 → sigmoid → fullc 10)."""
    data = (
        _iter_block("data", 6400, "1,1,784", 10)
        + _iter_block("eval", 1600, "1,1,784", 10)
        if synthetic
        else ""
    )
    return data + (
        "netconfig = start\n"
        "layer[0->1] = fullc:fc1\n"
        "  nhidden = 160\n"
        "  init_sigma = 0.01\n"
        "layer[1->2] = sigmoid:se1\n"
        "layer[2->3] = fullc:fc2\n"
        "  nhidden = 10\n"
        "  init_sigma = 0.01\n"
        "layer[3->3] = softmax\n"
        "netconfig = end\n"
    ) + _tail(batch_size, "1,1,784", 15, eta=0.1, dev=dev, extra="wd = 0.0\n")


def mnist_conv_conf(
    batch_size: int = 100, synthetic: bool = True, dev: str = "tpu"
) -> str:
    """LeNet-style conv net (MNIST_CONV.conf parity)."""
    data = (
        _iter_block("data", 6400, "1,28,28", 10)
        + _iter_block("eval", 1600, "1,28,28", 10)
        if synthetic
        else ""
    )
    return data + (
        "netconfig = start\n"
        "layer[0->1] = conv:cv1\n"
        "  kernel_size = 3\n"
        "  pad = 1\n"
        "  stride = 2\n"
        "  nchannel = 32\n"
        "  random_type = xavier\n"
        "  no_bias = 0\n"
        "layer[1->2] = max_pooling\n"
        "  kernel_size = 3\n"
        "  stride = 2\n"
        "layer[2->3] = flatten\n"
        "layer[3->3] = dropout\n"
        "  threshold = 0.5\n"
        "layer[3->4] = fullc:fc1\n"
        "  nhidden = 100\n"
        "  init_sigma = 0.01\n"
        "layer[4->5] = sigmoid:se1\n"
        "layer[5->6] = fullc:fc2\n"
        "  nhidden = 10\n"
        "  init_sigma = 0.01\n"
        "layer[6->6] = softmax\n"
        "netconfig = end\n"
    ) + _tail(batch_size, "1,28,28", 15, eta=0.1, dev=dev, extra="wd = 0.0\n")


# ---------------------------------------------------------------------------
def alexnet_conf(
    batch_size: int = 256,
    num_class: int = 1000,
    synthetic: bool = True,
    nsample: int = 0,
    dev: str = "tpu",
    input_size: int = 227,
    compute_dtype: str = "bfloat16",
) -> str:
    """AlexNet (ImageNet.conf parity: grouped convs, LRN, dropout FCs).

    ``input_size`` shrinks the input for CPU-feasible fixtures (ceil-mode
    pooling keeps every stage valid down to ~67px); 227 is the paper/
    reference shape."""
    shape = f"3,{input_size},{input_size}"
    nsample = nsample or batch_size * 4
    data = (
        _iter_block("data", nsample, shape, num_class, threadbuffer=True)
        + _iter_block("eval", batch_size * 2, shape, num_class)
        if synthetic
        else ""
    )
    lrn = (
        "  local_size = 5\n"
        "  alpha = 0.001\n"
        "  beta = 0.75\n"
        "  knorm = 1\n"
    )
    net = (
        "netconfig = start\n"
        "layer[0->1] = conv:conv1\n"
        "  kernel_size = 11\n  stride = 4\n  nchannel = 96\n"
        "layer[1->2] = relu\n"
        "layer[2->3] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
        "layer[3->4] = lrn\n" + lrn +
        "layer[4->5] = conv:conv2\n"
        "  ngroup = 2\n  nchannel = 256\n  kernel_size = 5\n  pad = 2\n"
        "layer[5->6] = relu\n"
        "layer[6->7] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
        "layer[7->8] = lrn\n" + lrn +
        "layer[8->9] = conv:conv3\n"
        "  nchannel = 384\n  kernel_size = 3\n  pad = 1\n"
        "layer[9->10] = relu\n"
        "layer[10->11] = conv:conv4\n"
        "  nchannel = 384\n  ngroup = 2\n  kernel_size = 3\n  pad = 1\n"
        "layer[11->12] = relu\n"
        "layer[12->13] = conv:conv5\n"
        "  nchannel = 256\n  ngroup = 2\n  kernel_size = 3\n  pad = 1\n"
        "  init_bias = 1.0\n"
        "layer[13->14] = relu\n"
        "layer[14->15] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
        "layer[15->16] = flatten\n"
        "layer[16->17] = fullc:fc6\n"
        "  nhidden = 4096\n  init_sigma = 0.005\n  init_bias = 1.0\n"
        "layer[17->18] = relu\n"
        "layer[18->18] = dropout\n  threshold = 0.5\n"
        "layer[18->19] = fullc:fc7\n"
        "  nhidden = 4096\n  init_sigma = 0.005\n  init_bias = 1.0\n"
        "layer[19->20] = relu\n"
        "layer[20->20] = dropout\n  threshold = 0.5\n"
        f"layer[20->21] = fullc:fc8\n  nhidden = {num_class}\n"
        "layer[21->21] = softmax\n"
        "netconfig = end\n"
    )
    extra = (
        "metric = rec@1\nmetric = rec@5\n"
        "wmat:lr = 0.01\nwmat:wd = 0.0005\n"
        "bias:wd = 0.000\nbias:lr = 0.02\n"
        "lr:schedule = expdecay\nlr:gamma = 0.1\nlr:step = 100000\n"
        f"compute_dtype = {compute_dtype}\n"
    )
    return data + net + _tail(batch_size, shape, 45, eta=0.01, dev=dev, extra=extra)


# ---------------------------------------------------------------------------
def _he_init(cin: int, k: int) -> str:
    """Gaussian init at ``sqrt(2 / fan_in)``, spelled out per conv.

    Every GoogLeNet conv feeds a relu, so He scaling keeps the forward
    signal's second moment through the stack.  The conf grammar's own
    ``random_type = kaiming`` is the reference's rule (param.h) and
    scales by ``nchannel * k * k`` — fan-OUT.  An inception module is
    many-in / few-out 1x1 reduces (192 -> 16 in i3a), where that
    over-scales by up to sqrt(cin / cout): measured on unit-variance
    input, activations doubled per module to ~10^3 at the classifier,
    the first sgd step at the conf's own eta blew the weights up and
    the second gave ``logloss:nan`` (CHANGES.md, PR 21).  With fan-in
    scaling the same probe stays at rms ~3 through all nine modules.
    """
    return ("  random_type = gaussian\n"
            f"  init_sigma = {(2.0 / (cin * k * k)) ** 0.5:.6g}\n")


def _inception(x: str, m: str, cin: int, c1: int, c3r: int, c3: int,
               c5r: int, c5: int, cp: int) -> str:
    """One GoogLeNet inception module over the ``cin``-channel node
    ``x``: 4 branches ch_concat'd to node ``m`` (``c1+c3+c5+cp``
    channels)."""

    def conv(src: str, dst: str, tag: str, k: int, ci: int, ch: int,
             pad: int) -> str:
        return (
            f"layer[{src}->{dst}] = conv:{tag}\n"
            f"  kernel_size = {k}\n  nchannel = {ch}\n  pad = {pad}\n"
            + _he_init(ci, k)
        )

    s = conv(x, f"{m}_c1", f"{m}_1x1", 1, cin, c1, 0)
    s += f"layer[+1:{m}_b1] = relu\n"
    s += conv(x, f"{m}_c3r", f"{m}_3x3r", 1, cin, c3r, 0)
    s += f"layer[+1:{m}_b2r] = relu\n"
    s += conv(f"{m}_b2r", f"{m}_c3", f"{m}_3x3", 3, c3r, c3, 1)
    s += f"layer[+1:{m}_b2] = relu\n"
    s += conv(x, f"{m}_c5r", f"{m}_5x5r", 1, cin, c5r, 0)
    s += f"layer[+1:{m}_b3r] = relu\n"
    s += conv(f"{m}_b3r", f"{m}_c5", f"{m}_5x5", 5, c5r, c5, 2)
    s += f"layer[+1:{m}_b3] = relu\n"
    s += (
        f"layer[{x}->{m}_p] = max_pooling\n"
        "  kernel_size = 3\n  stride = 1\n  pad = 1\n"
    )
    s += conv(f"{m}_p", f"{m}_pp", f"{m}_pool_proj", 1, cin, cp, 0)
    s += f"layer[+1:{m}_b4] = relu\n"
    s += f"layer[{m}_b1,{m}_b2,{m}_b3,{m}_b4->{m}] = ch_concat\n"
    return s


# (module, c1, c3r, c3, c5r, c5, pool_proj) — Szegedy et al. 2014,
# table 1; a None entry is the stride-2 max pool between stages
_GOOGLENET_MODULES = (
    ("i3a", 64, 96, 128, 16, 32, 32),
    ("i3b", 128, 128, 192, 32, 96, 64),
    None,
    ("i4a", 192, 96, 208, 16, 48, 64),
    ("i4b", 160, 112, 224, 24, 64, 64),
    ("i4c", 128, 128, 256, 24, 64, 64),
    ("i4d", 112, 144, 288, 32, 64, 64),
    ("i4e", 256, 160, 320, 32, 128, 128),
    None,
    ("i5a", 256, 160, 320, 32, 128, 128),
    ("i5b", 384, 192, 384, 48, 128, 128),
)


def googlenet_conf(
    batch_size: int = 128,
    num_class: int = 1000,
    input_size: int = 224,
    synthetic: bool = True,
    nsample: int = 0,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
) -> str:
    """GoogLeNet (inception v1) — the BASELINE.json benchmark model.

    Szegedy et al. 2014, table 1; main classifier only (the two auxiliary
    heads exist for vanishing-gradient relief the TPU build doesn't need
    at this depth; they are train-time-only and dropped at inference).
    """
    shape = f"3,{input_size},{input_size}"
    nsample = nsample or batch_size * 4
    data = (
        _iter_block("data", nsample, shape, num_class, threadbuffer=True)
        + _iter_block("eval", batch_size * 2, shape, num_class)
        if synthetic
        else ""
    )
    lrn = (
        "  local_size = 5\n  alpha = 0.0001\n  beta = 0.75\n  knorm = 1\n"
    )
    net = (
        "netconfig = start\n"
        "layer[0->c1] = conv:conv1\n"
        "  kernel_size = 7\n  stride = 2\n  pad = 3\n  nchannel = 64\n"
        + _he_init(3, 7) +
        "layer[+1:c1r] = relu\n"
        "layer[c1r->p1] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
        "layer[p1->n1] = lrn\n" + lrn +
        "layer[n1->c2r] = conv:conv2_reduce\n"
        "  kernel_size = 1\n  nchannel = 64\n" + _he_init(64, 1) +
        "layer[+1:c2rr] = relu\n"
        "layer[c2rr->c2] = conv:conv2\n"
        "  kernel_size = 3\n  pad = 1\n  nchannel = 192\n"
        + _he_init(64, 3) +
        "layer[+1:c2a] = relu\n"
        "layer[c2a->n2] = lrn\n" + lrn +
        "layer[n2->p2] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
    )
    prev, cin, npool = "p2", 192, 2
    for mod in _GOOGLENET_MODULES:
        if mod is None:
            npool += 1
            net += (f"layer[{prev}->p{npool}] = max_pooling\n"
                    "  kernel_size = 3\n  stride = 2\n")
            prev = f"p{npool}"
            continue
        name, c1, c3r, c3, c5r, c5, cp = mod
        net += _inception(prev, name, cin, c1, c3r, c3, c5r, c5, cp)
        prev, cin = name, c1 + c3 + c5 + cp
    net += (
        f"layer[{prev}->pool5] = avg_pooling\n"
        f"  kernel_size = {max(1, input_size // 32)}\n  stride = 1\n"
        "layer[pool5->pool5] = dropout\n  threshold = 0.4\n"
        "layer[pool5->flat] = flatten\n"
        f"layer[flat->fc] = fullc:loss3_classifier\n"
        f"  nhidden = {num_class}\n  random_type = xavier\n"
        "layer[fc->fc] = softmax\n"
        "netconfig = end\n"
    )
    extra = (
        "metric = rec@1\nmetric = rec@5\n"
        "wmat:lr = 0.01\nwmat:wd = 0.0002\n"
        "bias:lr = 0.02\nbias:wd = 0.0\n"
        "lr:schedule = polydecay\nlr:alpha = 0.5\nlr:max_round = 2400000\n"
        f"compute_dtype = {compute_dtype}\n"
    )
    return data + net + _tail(batch_size, shape, 100, eta=0.01, dev=dev, extra=extra)


def _transformer_blocks(
    prev: str,
    nlayer: int,
    nhead: int,
    dim: int,
    causal: int,
    seq_parallel: int,
    attn_impl: str = "auto",
) -> tuple:
    """Shared pre-norm block emission for transformer_conf /
    transformer_lm_conf: layer_norm -> attention -> residual ->
    layer_norm -> 4x MLP -> residual, per block.  Returns
    ``(conf_text, last_node)``."""
    s = ""
    for i in range(nlayer):
        b = f"b{i}"
        s += (
            f"layer[{prev}->{b}_n1] = layer_norm:{b}_ln1\n"
            f"layer[{b}_n1->{b}_a] = attention:{b}_attn\n"
            f"  nhead = {nhead}\n"
            f"  causal = {causal}\n"
            f"  seq_parallel = {seq_parallel}\n"
            f"  attn_impl = {attn_impl}\n"
            "  init_sigma = 0.02\n"
            f"layer[{prev},{b}_a->{b}_r1] = eltwise_sum\n"
            f"layer[{b}_r1->{b}_n2] = layer_norm:{b}_ln2\n"
            f"layer[{b}_n2->{b}_h] = fullc:{b}_fc1\n"
            f"  nhidden = {dim * 4}\n  init_sigma = 0.02\n"
            f"layer[+1:{b}_g] = gelu\n"
            f"layer[{b}_g->{b}_o] = fullc:{b}_fc2\n"
            f"  nhidden = {dim}\n  init_sigma = 0.02\n"
            f"layer[{b}_r1,{b}_o->{b}_r2] = eltwise_sum\n"
        )
        prev = f"{b}_r2"
    return s, prev


def transformer_lm_conf(
    vocab: int = 256,
    seq_len: int = 128,
    dim: int = 128,
    nhead: int = 4,
    nlayer: int = 2,
    text_file: str = "",
    batch_size: int = 16,
    num_round: int = 10,
    seq_parallel: int = 0,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    attn_impl: str = "auto",
    eta: float = 0.003,
) -> str:
    """Byte-level causal transformer language model.

    New TPU-first scope (the reference has no sequence models): the full
    LM pipeline — ``text`` iterator (byte windows + next-byte labels),
    ``embedding`` with learned positions, pre-norm causal blocks (flash
    attention via ``attn_impl``, sequence parallelism via
    ``seq_parallel``), a per-position softmax over the vocabulary, and
    per-token error/logloss metrics.  ``task = generate`` samples from a
    trained checkpoint (cli.py).
    """
    data = ""
    if text_file:
        data = (
            "data = train\n"
            "iter = text\n"
            f"  filename = {text_file}\n"
            f"  seq_len = {seq_len}\n"
            "  shuffle = 1\n"
            "iter = end\n"
        )
    s = (
        "netconfig = start\n"
        "layer[0->emb] = embedding:embed\n"
        f"  nvocab = {vocab}\n"
        f"  nhidden = {dim}\n"
        "  pos = learned\n"
        "  init_sigma = 0.02\n"
    )
    blocks, prev = _transformer_blocks(
        "emb", nlayer, nhead, dim, 1, seq_parallel, attn_impl
    )
    s += blocks
    s += (
        f"layer[{prev}->nf] = layer_norm:ln_f\n"
        f"layer[nf->logits] = fullc:lm_head\n"
        f"  nhidden = {vocab}\n  init_sigma = 0.02\n"
        "layer[logits->logits] = softmax\n"
        # per-token mean: the loss sums over T positions, so scale by
        # 1/T to keep eta in the familiar per-instance range
        f"  grad_scale = {1.0 / seq_len!r}\n"
        "netconfig = end\n"
    )
    extra = (
        f"compute_dtype = {compute_dtype}\n"
        f"label_width = {seq_len}\n"
        f"label_vec[0,{seq_len}) = label\n"
        "metric = logloss\n"
        # transformers want Adam; override _tail's sgd+momentum
        "updater = adam\n"
        "wd = 0.0\n"
    )
    return data + s + _tail(
        batch_size, f"1,1,{seq_len}", num_round, eta=eta, dev=dev,
        extra=extra,
    )


# the published order of one period of granite-4.0-h-micro's stack
# (config.json `layer_types`, layers 0-9): nine Mamba-2 layers around
# one attention layer
GRANITE_H_PERIOD = "mmmmmammmm"


def _packed_lm(net: str, token_file: str, seq_len: int, batch_size: int,
               num_round: int, dev: str, compute_dtype: str, eta: float,
               scan_steps: int, feed: str = "") -> str:
    """The conf of a language model on packed token rows around its
    ``netconfig`` block ``net``: the ``iter = tokens`` feed (where a
    file is named) and the run settings the token builders share — a
    label a position, logloss, adam without decay, ``remat = 1`` and
    ``eval_train = 0`` (written for memory: ``granite_h_conf``).
    ``feed``: further lines of the iterator's block."""
    data = ""
    if token_file:
        data = (
            "data = train\n"
            "iter = tokens\n"
            f"  filename = {token_file}\n"
            f"  seq_len = {seq_len}\n"
            + feed +
            "iter = end\n"
        )
    extra = (
        f"compute_dtype = {compute_dtype}\n"
        f"label_width = {seq_len}\n"
        f"label_vec[0,{seq_len}) = label\n"
        "metric = logloss\n"
        "updater = adam\n"
        "wd = 0.0\n"
        "remat = 1\n"
        "eval_train = 0\n"
    )
    return data + net + _tail(
        batch_size, f"1,1,{seq_len}", num_round, eta=eta, dev=dev,
        extra=extra, scan_steps=scan_steps,
    )


def _mtp_module(last: str, hidden: int, eps: float, block: str, out: str,
                seq_len: int, loss_weight: float) -> str:
    """A multi-token-prediction module of depth 1 (DeepSeek-V3, section
    2.2) behind the main head and loss, its layers named ``mtp_*``: the
    shared embedding of the NEXT token and the node ``last``, each
    normed, joined by ``mtp_eh_proj`` (the embedding's columns first),
    then ``block`` — the conf layers from node ``mtp_h0`` to node
    ``out`` — a last norm, the shared head, and a loss on the token
    after next at ``loss_weight`` (``softmax`` with ``target_shift =
    1`` over the same ``label`` field)."""
    return (
        "layer[0->mtp_ids] = token_shift:mtp_shift\n"
        "layer[mtp_ids->mtp_e] = shared[embed]\n"
        "layer[mtp_e->mtp_en] = rms_norm:mtp_enorm\n"
        f"  eps = {eps!r}\n"
        f"layer[{last}->mtp_hn] = rms_norm:mtp_hnorm\n"
        f"  eps = {eps!r}\n"
        "layer[mtp_en,mtp_hn->mtp_eh] = concat:mtp_cat\n"
        "layer[mtp_eh->mtp_h0] = fullc:mtp_eh_proj\n"
        f"  nhidden = {hidden}\n"
        "  no_bias = 1\n"
        "  init_sigma = 0.02\n"
        + block
        + f"layer[{out}->mtp_nf] = rms_norm:mtp_norm_f\n"
        f"  eps = {eps!r}\n"
        "layer[mtp_nf->mtp_logits] = shared[head]\n"
        "layer[mtp_logits->mtp_logits] = softmax\n"
        "  target_shift = 1\n"
        f"  grad_scale = {loss_weight / seq_len!r}\n"
    )


def granite_h_conf(
    vocab: int = 12544,
    seq_len: int = 8192,
    hidden: int = 2048,
    layer_types: str = GRANITE_H_PERIOD,
    mamba_heads: int = 64,
    mamba_head_dim: int = 64,
    mamba_state: int = 128,
    mamba_conv: int = 4,
    mamba_chunk: int = 256,
    attn_heads: int = 32,
    attn_kv_heads: int = 8,
    mlp_hidden: int = 8192,
    embedding_multiplier: float = 12.0,
    attention_multiplier: float = 0.015625,
    residual_multiplier: float = 0.22,
    logits_scaling: float = 8.0,
    eps: float = 1e-5,
    token_file: str = "",
    batch_size: int = 1,
    num_round: int = 10,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    eta: float = 0.0003,
    scan_steps: int = 8,
) -> str:
    """A Granite-4.0-H style hybrid language model (ibm-granite,
    ``model_type: granitemoehybrid``, dense): per layer a Mamba-2 mixer
    (``m``) or a position-free grouped-query attention (``a``), then a
    gated MLP, every branch pre-normed by ``rms_norm`` and added back
    times ``residual_multiplier``; the embedding scaled by
    ``embedding_multiplier`` and tied to the head, whose logits are
    divided by ``logits_scaling``.  The defaults are the published
    widths of granite-4.0-h-micro, one period of its stack deep, over an
    eighth of its vocabulary: what one 16 GB chip holds under adam.

    Trains on packed token rows (``iter = tokens``) in which a document
    begins after every separator id 0; mixers and attention read the
    starts from the ids (their second input).  Written for memory:
    ``remat = 1`` keeps one ``(N, T, hidden)`` input a branch, and
    ``eval_train = 0`` because 8 steps of ``(T, vocab)`` outputs cannot
    be fetched — chunks then run double-buffered and asynchronous.
    """
    branch = (f"  prenorm = 1\n  eps = {eps!r}\n"
              f"  residual_scale = {residual_multiplier!r}\n"
              "  init_sigma = 0.02\n")
    s = (
        "netconfig = start\n"
        "layer[0->h0] = embedding:embed\n"
        f"  nvocab = {vocab}\n"
        f"  nhidden = {hidden}\n"
        f"  multiplier = {embedding_multiplier!r}\n"
        "  init_sigma = 0.02\n"
    )
    for i, kind in enumerate(layer_types):
        if kind == "m":
            s += (
                f"layer[h{i},0->x{i}] = mamba2:mixer{i}\n"
                f"  nhead = {mamba_heads}\n"
                f"  head_dim = {mamba_head_dim}\n"
                f"  nstate = {mamba_state}\n"
                f"  conv_width = {mamba_conv}\n"
                f"  chunk = {mamba_chunk}\n" + branch
            )
        elif kind == "a":
            s += (
                f"layer[h{i},0->x{i}] = attention:attn{i}\n"
                f"  nhead = {attn_heads}\n"
                f"  nkvhead = {attn_kv_heads}\n"
                f"  score_scale = {attention_multiplier!r}\n"
                "  causal = 1\n  no_bias = 1\n" + branch
            )
        else:
            raise ValueError(
                f"granite_h_conf: layer_types is a string of m and a, "
                f"got {kind!r}")
        s += (
            f"layer[x{i}->h{i + 1}] = gated_mlp:mlp{i}\n"
            f"  nhidden = {mlp_hidden}\n" + branch
        )
    s += (
        f"layer[h{len(layer_types)}->nf] = rms_norm:norm_f\n"
        f"  eps = {eps!r}\n"
        "layer[nf->logits] = lm_head:head\n"
        "  tied = embed\n"
        f"  nhidden = {vocab}\n"
        f"  divisor = {logits_scaling!r}\n"
        "layer[logits->logits] = softmax\n"
        # the mean over all positions: the loss sums over T
        f"  grad_scale = {1.0 / seq_len!r}\n"
        "netconfig = end\n"
    )
    return _packed_lm(s, token_file, seq_len, batch_size, num_round, dev,
                      compute_dtype, eta, scan_steps)


def qwen3_next_conf(
    vocab: int = 18992,
    seq_len: int = 8192,
    hidden: int = 2048,
    layer_types: str = "lllf",
    linear_key_heads: int = 16,
    linear_value_heads: int = 32,
    linear_key_dim: int = 128,
    linear_value_dim: int = 128,
    linear_conv: int = 4,
    linear_chunk: int = 64,
    attn_heads: int = 16,
    attn_kv_heads: int = 2,
    head_dim: int = 256,
    partial_rotary_factor: float = 0.25,
    rope_theta: float = 1e7,
    num_experts: int = 512,
    experts_per_tok: int = 10,
    expert_hidden: int = 512,
    shared_hidden: int = 512,
    first_expert: int = 0,
    experts_held: int = 32,
    eps: float = 1e-6,
    token_file: str = "",
    batch_size: int = 1,
    num_round: int = 10,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    eta: float = 0.0003,
    scan_steps: int = 8,
) -> str:
    """A Qwen3-Next style hybrid mixture-of-experts language model
    (Qwen, ``model_type: qwen3_next``): per layer a Gated DeltaNet
    linear-attention mixer (``l``) or a gated softmax attention with
    q/k norms and partial rotary positions (``f``), then a routed
    feed-forward part — ``num_experts`` SwiGLU experts of width
    ``expert_hidden`` behind a top-``experts_per_tok`` router with
    renormalised weights, plus one shared expert under a sigmoid gate —
    every branch pre-normed by ``rms_norm`` and added back; an untied
    head.  The defaults are the published widths of
    Qwen3-Next-80B-A3B, one period of its stack deep (``lllf``), with
    ONE RANK'S SHARE of a 16-way expert-parallel layout —
    ``experts_held`` = 32 of the 512 experts of every layer, from
    ``first_expert`` on (the router still ranks all 512; the layer adds
    the held experts' terms only, ``layers/moe.py``) — over an eighth
    of the vocabulary: 625.7M parameters, what one 16 GB chip holds
    under adam.

    The published norm is ``x / rms(x) * (1 + w)`` with ``w`` started
    at 0; ``rms_norm``'s weight started at 1 is the same function with
    the same gradients under adam without decay, so it serves as it is.
    ``a_log`` and ``dt_bias`` start as ``mamba2``'s (``layers/gdn.py``).

    Every parameter is under adam at ``eta``, the routers too.  In a
    share a router's gradient is zero all the same: the layer takes the
    routing weights as constants of the backward pass there
    (``layers/moe.py`` says why), so the routers of a share stay where
    they started and the held experts' load stays the seed's.

    Trains on packed token rows (``iter = tokens``) in which a document
    begins after every separator id 0; mixers and attention read the
    starts from the ids (their second input), and rotary positions are
    counted from a document's first token.  Written for memory as
    ``granite_h_conf`` is: ``remat = 1``, ``eval_train = 0``.
    """
    branch = (f"  prenorm = 1\n  eps = {eps!r}\n"
              "  residual_scale = 1.0\n"
              "  init_sigma = 0.02\n")
    rotary_dim = int(head_dim * partial_rotary_factor)
    s = (
        "netconfig = start\n"
        "layer[0->h0] = embedding:embed\n"
        f"  nvocab = {vocab}\n"
        f"  nhidden = {hidden}\n"
        "  init_sigma = 0.02\n"
    )
    for i, kind in enumerate(layer_types):
        if kind == "l":
            s += (
                f"layer[h{i},0->x{i}] = gated_deltanet:gdn{i}\n"
                f"  nkhead = {linear_key_heads}\n"
                f"  nvhead = {linear_value_heads}\n"
                f"  key_dim = {linear_key_dim}\n"
                f"  value_dim = {linear_value_dim}\n"
                f"  conv_width = {linear_conv}\n"
                f"  chunk = {linear_chunk}\n" + branch
            )
        elif kind == "f":
            s += (
                f"layer[h{i},0->x{i}] = attention:attn{i}\n"
                f"  nhead = {attn_heads}\n"
                f"  nkvhead = {attn_kv_heads}\n"
                f"  head_dim = {head_dim}\n"
                "  qk_norm = 1\n"
                f"  rotary_dim = {rotary_dim}\n"
                f"  rope_theta = {rope_theta!r}\n"
                "  out_gate = 1\n"
                "  causal = 1\n  no_bias = 1\n" + branch
            )
        else:
            raise ValueError(
                f"qwen3_next_conf: layer_types is a string of l and f, "
                f"got {kind!r}")
        s += (
            f"layer[x{i}->h{i + 1}] = routed_experts:moe{i}\n"
            f"  nexpert = {num_experts}\n"
            f"  topk = {experts_per_tok}\n"
            f"  nhidden = {expert_hidden}\n"
            f"  first_expert = {first_expert}\n"
            f"  nheld = {experts_held}\n"
            f"  shared_hidden = {shared_hidden}\n"
            "  norm_topk = 1\n" + branch
        )
    s += (
        f"layer[h{len(layer_types)}->nf] = rms_norm:norm_f\n"
        f"  eps = {eps!r}\n"
        "layer[nf->logits] = lm_head:head\n"
        f"  nhidden = {vocab}\n"
        "  init_sigma = 0.02\n"
        "layer[logits->logits] = softmax\n"
        # the mean over all positions: the loss sums over T
        f"  grad_scale = {1.0 / seq_len!r}\n"
        "netconfig = end\n"
    )
    return _packed_lm(s, token_file, seq_len, batch_size, num_round, dev,
                      compute_dtype, eta, scan_steps)


def joyai_llm_flash_conf(
    vocab: int = 16160,
    seq_len: int = 8192,
    hidden: int = 2048,
    num_layers: int = 5,
    first_k_dense: int = 1,
    attn_heads: int = 32,
    q_lora_rank: int = 1536,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    rope_theta: float = 3.2e7,
    rope_interleave: int = 1,
    mlp_hidden: int = 7168,
    num_experts: int = 256,
    experts_per_tok: int = 8,
    expert_hidden: int = 768,
    shared_hidden: int = 768,
    routed_scaling_factor: float = 2.5,
    first_expert: int = 0,
    experts_held: int = 16,
    num_nextn_predict_layers: int = 1,
    mtp_loss_weight: float = 0.3,
    eps: float = 1e-6,
    token_file: str = "",
    batch_size: int = 1,
    num_round: int = 10,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    eta: float = 0.0003,
    scan_steps: int = 8,
) -> str:
    """A JoyAI-LLM-Flash style language model (jdopensource,
    ``model_type: joyai_llm_flash`` — the DeepSeek-V3 layout): every
    layer a multi-head latent attention (``latent_attention``: low-rank
    queries and keys/values, one shared rotary key head, interleaved
    rotary positions); the first ``first_k_dense`` layers a dense gated
    MLP of ``mlp_hidden``, the others ``num_experts`` SwiGLU experts of
    ``expert_hidden`` behind a sigmoid router that chooses its
    top-``experts_per_tok`` by score + bias and weighs them by the
    unbiased scores, renormalised and times ``routed_scaling_factor``,
    plus one ungated shared expert; every branch pre-normed by
    ``rms_norm`` and added back; an untied head.  With
    ``num_nextn_predict_layers = 1`` a multi-token-prediction module
    (DeepSeek-V3, section 2.2) follows the main head and loss, its
    layers named ``mtp_*``: the shared embedding of the NEXT token and
    the last layer's output, each normed, joined by ``mtp_eh_proj``
    (embedding's columns first), one more attention + expert block, a
    last norm, the shared head, and a loss on the token after next at
    ``mtp_loss_weight`` (``softmax`` with ``target_shift = 1`` over the
    same ``label`` field).

    The defaults are the published widths, ``num_layers`` = 5 deep (the
    leading dense layer and four of the 39 that follow), with ONE
    RANK'S SHARE of a 16-way expert-parallel layout — ``experts_held``
    = 16 of the 256 experts of every layer, from ``first_expert`` on
    (the router still ranks all 256; ``layers/moe.py``) — over an
    eighth of the vocabulary: 680.4M parameters with the module.  In a
    share the routing weights are constants of the backward pass, and
    the selection bias gets no gradient anywhere, so routers' choices
    stay the seed's.

    The embedding starts at normal(0, 1), the other matrices at 0.02:
    a freshly drawn attention puts the running mean of its values into
    the stream (0.58 / sqrt(n) an entry at these widths), and under an
    embedding of 0.02 every token of a document then looks the same to
    the routers, which send them all to the same eight experts.

    The out node is the LAST layer's, so with the module it is the
    module's prediction; the module is a training device and is left
    out (``num_nextn_predict_layers = 0``) of a net that predicts.
    Written for memory as ``granite_h_conf`` is: ``remat = 1``,
    ``eval_train = 0``; documents and positions as ``qwen3_next_conf``.
    """
    if num_nextn_predict_layers not in (0, 1):
        raise ValueError("joyai_llm_flash_conf: a multi-token-prediction "
                         "depth of 0 or 1")
    branch = (f"  prenorm = 1\n  eps = {eps!r}\n"
              "  residual_scale = 1.0\n"
              "  init_sigma = 0.02\n")

    def mla(src: str, out: str, name: str) -> str:
        return (
            f"layer[{src},0->{out}] = latent_attention:{name}\n"
            f"  nhead = {attn_heads}\n"
            f"  q_rank = {q_lora_rank}\n"
            f"  kv_rank = {kv_lora_rank}\n"
            f"  nope_dim = {qk_nope_head_dim}\n"
            f"  rope_dim = {qk_rope_head_dim}\n"
            f"  v_dim = {v_head_dim}\n"
            f"  rope_theta = {rope_theta!r}\n"
            f"  rope_interleave = {rope_interleave}\n"
            "  causal = 1\n" + branch
        )

    def moe(src: str, out: str, name: str) -> str:
        return (
            f"layer[{src}->{out}] = routed_experts:{name}\n"
            f"  nexpert = {num_experts}\n"
            f"  topk = {experts_per_tok}\n"
            f"  nhidden = {expert_hidden}\n"
            f"  first_expert = {first_expert}\n"
            f"  nheld = {experts_held}\n"
            f"  shared_hidden = {shared_hidden}\n"
            "  shared_gate = 0\n"
            "  score_func = sigmoid\n"
            "  select_bias = 1\n"
            f"  routed_scale = {routed_scaling_factor!r}\n"
            "  norm_topk = 1\n" + branch
        )

    s = (
        "netconfig = start\n"
        "layer[0->h0] = embedding:embed\n"
        f"  nvocab = {vocab}\n"
        f"  nhidden = {hidden}\n"
        # a token's own row has to stand out of the stream: see above
        "  init_sigma = 1.0\n"
    )
    for i in range(num_layers):
        s += mla(f"h{i}", f"x{i}", f"mla{i}")
        if i < first_k_dense:
            s += (
                f"layer[x{i}->h{i + 1}] = gated_mlp:mlp{i}\n"
                f"  nhidden = {mlp_hidden}\n" + branch
            )
        else:
            s += moe(f"x{i}", f"h{i + 1}", f"moe{i}")
    last = f"h{num_layers}"
    s += (
        f"layer[{last}->nf] = rms_norm:norm_f\n"
        f"  eps = {eps!r}\n"
        "layer[nf->logits] = lm_head:head\n"
        f"  nhidden = {vocab}\n"
        "  init_sigma = 0.02\n"
        "layer[logits->logits] = softmax\n"
        # the mean over all positions: the loss sums over T
        f"  grad_scale = {1.0 / seq_len!r}\n"
    )
    if num_nextn_predict_layers:
        s += _mtp_module(
            last, hidden, eps,
            mla("mtp_h0", "mtp_x", "mtp_mla")
            + moe("mtp_x", "mtp_h1", "mtp_moe"),
            "mtp_h1", seq_len, mtp_loss_weight)
    s += "netconfig = end\n"
    return _packed_lm(s, token_file, seq_len, batch_size, num_round, dev,
                      compute_dtype, eta, scan_steps)


AFMOE_STAGE = "ssssf"


def afmoe_conf(
    vocab: int = 25024,
    seq_len: int = 16384,
    hidden: int = 2048,
    layer_types: str = AFMOE_STAGE,
    num_dense_layers: int = 1,
    sliding_window: int = 2048,
    attn_heads: int = 32,
    attn_kv_heads: int = 4,
    head_dim: int = 128,
    rope_theta: float = 10000.0,
    mlp_hidden: int = 6144,
    num_experts: int = 128,
    experts_per_tok: int = 8,
    expert_hidden: int = 1024,
    shared_hidden: int = 1024,
    route_scale: float = 2.826,
    first_expert: int = 0,
    experts_held: int = 8,
    mup_enabled: int = 1,
    eps: float = 1e-5,
    token_file: str = "",
    batch_size: int = 1,
    num_round: int = 10,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    eta: float = 0.0003,
    scan_steps: int = 8,
) -> str:
    """An AFMoE style language model (arcee-ai, ``model_type: afmoe``,
    the Trinity family): every layer a grouped-query attention with q/k
    norms and an element-wise sigmoid output gate — on a SLIDING layer
    (``s``) under a window of ``sliding_window`` keys with rotate-half
    rotary positions over the whole head, on a FULL layer (``f``) over
    the whole document with no positions at all — then, in the first
    ``num_dense_layers`` layers, a dense gated MLP of ``mlp_hidden`` and
    in the others ``num_experts`` SwiGLU experts of ``expert_hidden``
    behind a sigmoid router that chooses its top-``experts_per_tok`` by
    score + bias and weighs them by the unbiased scores, renormalised
    and times ``route_scale``, plus one ungated shared expert.  Both
    parts are SANDWICHED: ``x + rms_norm(f(rms_norm(x)))``, four norms a
    layer (``prenorm`` and ``postnorm`` of ``sequence.Branch``).  With
    ``mup_enabled`` the embedding's rows are multiplied by
    ``sqrt(hidden)``; an untied head.

    The defaults are the published widths of Trinity-Mini (26B-A3B),
    five layers deep — its layer 0 (dense, sliding) and its layers 4-7
    (experts: sliding, sliding, sliding, full; one whole period of
    ``layer_types``) — with ONE RANK'S SHARE of a 16-way expert-parallel
    layout: ``experts_held`` = 8 of the 128 experts of every layer from
    ``first_expert`` on (the router still ranks all 128;
    ``layers/moe.py``), over an eighth of the vocabulary: 504.1M
    parameters (with 16 held the scanned step compiled to 14.97 GB live
    for a described v5e, over the 14.4 GB the token cells are held to:
    ``benchmarks/configs/trinity_mini.json``).  In a share the routing
    weights are constants of the backward pass and the selection bias
    gets no gradient anywhere, as in ``joyai_llm_flash_conf``; the
    embedding starts at normal(0, 1) as there, the other matrices at
    0.02.

    Rows of ``seq_len`` = 16384 packed tokens (``iter = tokens``); with
    a ``token_file`` the feed is told the window (``attn_window``) and
    counts ``attn_window_pairs`` beside ``attn_pairs``.  Written for
    memory as ``granite_h_conf`` is; documents and positions as
    ``qwen3_next_conf``.
    """
    if not 0 <= num_dense_layers <= len(layer_types):
        raise ValueError("afmoe_conf: num_dense_layers counts leading "
                         "layers of layer_types")
    branch = (f"  prenorm = 1\n  postnorm = 1\n  eps = {eps!r}\n"
              "  residual_scale = 1.0\n"
              "  init_sigma = 0.02\n")
    s = (
        "netconfig = start\n"
        "layer[0->h0] = embedding:embed\n"
        f"  nvocab = {vocab}\n"
        f"  nhidden = {hidden}\n"
        + (f"  multiplier = {math.sqrt(hidden)!r}\n" if mup_enabled else "")
        # a token's own row has to stand out of the stream
        # (joyai_llm_flash_conf)
        + "  init_sigma = 1.0\n"
    )
    for i, kind in enumerate(layer_types):
        if kind not in "sf":
            raise ValueError(
                f"afmoe_conf: layer_types is a string of s and f, got "
                f"{kind!r}")
        s += (
            f"layer[h{i},0->x{i}] = attention:attn{i}\n"
            f"  nhead = {attn_heads}\n"
            f"  nkvhead = {attn_kv_heads}\n"
            f"  head_dim = {head_dim}\n"
            "  qk_norm = 1\n"
            + (f"  window = {sliding_window}\n"
               f"  rotary_dim = {head_dim}\n"
               f"  rope_theta = {rope_theta!r}\n" if kind == "s" else "")
            + "  out_gate = 1\n"
            "  causal = 1\n  no_bias = 1\n" + branch
        )
        if i < num_dense_layers:
            s += (
                f"layer[x{i}->h{i + 1}] = gated_mlp:mlp{i}\n"
                f"  nhidden = {mlp_hidden}\n" + branch
            )
        else:
            s += (
                f"layer[x{i}->h{i + 1}] = routed_experts:moe{i}\n"
                f"  nexpert = {num_experts}\n"
                f"  topk = {experts_per_tok}\n"
                f"  nhidden = {expert_hidden}\n"
                f"  first_expert = {first_expert}\n"
                f"  nheld = {experts_held}\n"
                f"  shared_hidden = {shared_hidden}\n"
                "  shared_gate = 0\n"
                "  score_func = sigmoid\n"
                "  select_bias = 1\n"
                f"  routed_scale = {route_scale!r}\n"
                "  norm_topk = 1\n" + branch
            )
    s += (
        f"layer[h{len(layer_types)}->nf] = rms_norm:norm_f\n"
        f"  eps = {eps!r}\n"
        "layer[nf->logits] = lm_head:head\n"
        f"  nhidden = {vocab}\n"
        "  init_sigma = 0.02\n"
        "layer[logits->logits] = softmax\n"
        # the mean over all positions: the loss sums over T
        f"  grad_scale = {1.0 / seq_len!r}\n"
        "netconfig = end\n"
    )
    feed = (f"  attn_window = {sliding_window}\n"
            if "s" in layer_types else "")
    return _packed_lm(s, token_file, seq_len, batch_size, num_round, dev,
                      compute_dtype, eta, scan_steps, feed=feed)


#: one period of SmallThinker's two layouts: a full layer without
#: positions, then three rotary layers under the window
SMALLTHINKER_PERIOD = (0, 1, 1, 1)


def smallthinker_conf(
    vocab: int = 18992,
    seq_len: int = 16384,
    hidden: int = 2560,
    sliding_window_layout: Sequence[int] = SMALLTHINKER_PERIOD,
    rope_layout: Sequence[int] = SMALLTHINKER_PERIOD,
    sliding_window: int = 4096,
    attn_heads: int = 28,
    attn_kv_heads: int = 4,
    head_dim: int = 128,
    rope_theta: float = 1500000.0,
    num_experts: int = 64,
    experts_per_tok: int = 6,
    expert_hidden: int = 768,
    first_expert: int = 0,
    experts_held: int = 16,
    eps: float = 1e-6,
    token_file: str = "",
    batch_size: int = 1,
    num_round: int = 10,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    eta: float = 0.0003,
    scan_steps: int = 8,
) -> str:
    """A SmallThinker style language model (PowerInfer, arXiv:2507.20984;
    ``model_name: smallthinker_21b_instruct``): every layer a
    grouped-query attention without biases, q/k norms or gate, then
    ``num_experts`` ReGLU experts of ``expert_hidden`` and nothing else —
    no dense layer, no shared expert — in pre-norm residual blocks.  THE
    ROUTER READS THE ATTENTION'S INPUT and the experts its output::

        u = rms_norm(x; n1);  (w, e) = top-k of softmax(u W_r^T), renormed
        x' = x + attention(u);  v = rms_norm(x'; n2)
        x'' = x' + sum_j w_j W_d^e_j (relu(W_g^e_j v) * W_u^e_j v)

    (``routed_experts`` with a second input under ``route_norm =
    attn<i>``: the attention layer's own norm weight, ``layers/moe.py``).
    Layer ``i`` sees a causal window of ``sliding_window`` keys where
    ``sliding_window_layout[i]`` is 1 and its whole document where it is
    0, and rotates its queries and keys (rotate-half over the whole head
    at ``rope_theta``) where ``rope_layout[i]`` is 1; the published
    layouts are one period of four: a full layer with NO positions, then
    three rotary layers under the window.  An untied head.

    The defaults are the published widths of SmallThinker-21BA3B, four
    layers deep — its layers 0-3, one whole period — with ONE RANK'S
    SHARE of a 4-way expert-parallel layout: ``experts_held`` = 16 of the
    64 experts of every layer from ``first_expert`` on (the router still
    ranks all 64), over an eighth of the vocabulary: 559.3M parameters.
    In a share the routing weights are constants of the backward pass, as
    in ``qwen3_next_conf``; the embedding starts at normal(0, 1) as in
    ``joyai_llm_flash_conf``, the other matrices at 0.02.

    Rows of ``seq_len`` = 16384 packed tokens (``iter = tokens``); with
    a ``token_file`` the feed is told the window (``attn_window``) and
    counts ``attn_window_pairs`` beside ``attn_pairs``.  Written for
    memory as ``granite_h_conf`` is; documents and positions as
    ``qwen3_next_conf``.
    """
    if len(sliding_window_layout) != len(rope_layout):
        raise ValueError("smallthinker_conf: sliding_window_layout and "
                         "rope_layout give one entry a layer each")
    if (set(sliding_window_layout) | set(rope_layout)) - {0, 1}:
        raise ValueError("smallthinker_conf: a layout is a list of 0 and 1")
    branch = (f"  prenorm = 1\n  eps = {eps!r}\n  residual_scale = 1.0\n"
              "  init_sigma = 0.02\n")
    s = (
        "netconfig = start\n"
        "layer[0->h0] = embedding:embed\n"
        f"  nvocab = {vocab}\n"
        f"  nhidden = {hidden}\n"
        # a token's own row has to stand out of the stream
        # (joyai_llm_flash_conf)
        "  init_sigma = 1.0\n"
    )
    for i, (near, rotary) in enumerate(zip(sliding_window_layout,
                                           rope_layout)):
        s += (
            f"layer[h{i},0->x{i}] = attention:attn{i}\n"
            f"  nhead = {attn_heads}\n"
            f"  nkvhead = {attn_kv_heads}\n"
            f"  head_dim = {head_dim}\n"
            + (f"  window = {sliding_window}\n" if near else "")
            + (f"  rotary_dim = {head_dim}\n"
               f"  rope_theta = {rope_theta!r}\n" if rotary else "")
            + "  causal = 1\n  no_bias = 1\n" + branch
            # the experts read the attention's output, the router its input
            + f"layer[x{i},h{i}->h{i + 1}] = routed_experts:moe{i}\n"
            f"  route_norm = attn{i}\n"
            f"  nexpert = {num_experts}\n"
            f"  topk = {experts_per_tok}\n"
            f"  nhidden = {expert_hidden}\n"
            f"  first_expert = {first_expert}\n"
            f"  nheld = {experts_held}\n"
            "  expert_act = reglu\n"
            "  norm_topk = 1\n" + branch
        )
    s += (
        f"layer[h{len(rope_layout)}->nf] = rms_norm:norm_f\n"
        f"  eps = {eps!r}\n"
        "layer[nf->logits] = lm_head:head\n"
        f"  nhidden = {vocab}\n"
        "  init_sigma = 0.02\n"
        "layer[logits->logits] = softmax\n"
        # the mean over all positions: the loss sums over T
        f"  grad_scale = {1.0 / seq_len!r}\n"
        "netconfig = end\n"
    )
    feed = (f"  attn_window = {sliding_window}\n"
            if any(sliding_window_layout) else "")
    return _packed_lm(s, token_file, seq_len, batch_size, num_round, dev,
                      compute_dtype, eta, scan_steps, feed=feed)


def bailing_hybrid_conf(
    vocab: int = 19648,
    seq_len: int = 8192,
    hidden: int = 2560,
    num_layers: int = 6,
    layer_group_size: int = 6,
    first_k_dense: int = 1,
    attn_heads: int = 32,
    head_dim: int = 128,
    short_conv_kernel_size: int = 4,
    kda_lower_bound: float = -5.0,
    kv_lora_rank: int = 512,
    qk_nope_head_dim: int = 128,
    qk_rope_head_dim: int = 64,
    v_head_dim: int = 128,
    rope_theta: float = 6e6,
    rope_interleave: int = 1,
    mlp_hidden: int = 6144,
    num_experts: int = 512,
    experts_per_tok: int = 8,
    n_group: int = 8,
    topk_group: int = 4,
    expert_hidden: int = 768,
    shared_hidden: int = 768,
    routed_scaling_factor: float = 2.5,
    first_expert: int = 0,
    experts_held: int = 8,
    expert_swiglu_limits: Sequence[float] = (),
    shared_swiglu_limits: Sequence[float] = (),
    eps: float = 1e-6,
    token_file: str = "",
    batch_size: int = 1,
    num_round: int = 10,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    eta: float = 0.0003,
    scan_steps: int = 8,
) -> str:
    """A ``bailing_hybrid`` style language model (inclusionAI, the
    Ling-3.0 family): layers in periods of ``layer_group_size``, every
    layer of a period a Kimi Delta Attention mixer (``kimi_delta``: the
    delta rule with one decay a head AND key channel under the bounded
    gate ``kda_lower_bound * sigmoid(.)``, ``attn_heads`` heads of
    ``head_dim`` for q, k and v alike, a head-wise sigmoid output gate)
    but the LAST (``(i + 1) % layer_group_size == 0``), which is a
    multi-head latent attention WITHOUT a query latent and with one
    sigmoid gate a head on its output (``latent_attention`` with
    ``q_rank = 0``, ``out_gate = head``); the first ``first_k_dense``
    layers a dense gated MLP of ``mlp_hidden``, the others
    ``num_experts`` SwiGLU experts of ``expert_hidden`` behind
    DeepSeek-V3's group-limited sigmoid router (``n_group`` groups, a
    token keeps its ``topk_group`` best and takes its
    top-``experts_per_tok`` by score + bias among them, weighs them by
    the unbiased scores, renormalised and times
    ``routed_scaling_factor``) plus one ungated shared expert; every
    branch pre-normed by ``rms_norm`` and added back; an untied head.

    The defaults are the published widths of Ling-3.0-flash, one whole
    period deep from layer 0 — the leading dense layer and the five
    that follow — with ONE RANK'S SHARE of a 64-way expert-parallel
    layout: ``experts_held`` = 8 of the 512 experts of every layer from
    ``first_expert`` on (the router still ranks all 512), over an eighth
    of the vocabulary: 767.0M parameters.  Routing weights, selection
    bias and the embedding's start as ``joyai_llm_flash_conf``'s.

    ``expert_swiglu_limits`` / ``shared_swiglu_limits`` (the family's
    ``*_swiglu_limit_list`` for these layers) must be all 0, "no clamp":
    the clamp of an expert's gate and up products is not built, and a
    non-zero limit is refused rather than dropped.  The family's
    prediction module (loss factor 0 as published) is not built here.
    Written for memory as ``granite_h_conf`` is; documents and positions
    as ``qwen3_next_conf``.
    """
    if any(expert_swiglu_limits) or any(shared_swiglu_limits):
        raise ValueError(
            "bailing_hybrid_conf: a non-zero swiglu limit asks for a clamp "
            "of the experts' gate and up products, which routed_experts "
            f"does not have: {list(expert_swiglu_limits)} / "
            f"{list(shared_swiglu_limits)}")
    if layer_group_size < 1:
        raise ValueError("bailing_hybrid_conf: layer_group_size >= 1")
    branch = (f"  prenorm = 1\n  eps = {eps!r}\n"
              "  residual_scale = 1.0\n"
              "  init_sigma = 0.02\n")
    s = (
        "netconfig = start\n"
        "layer[0->h0] = embedding:embed\n"
        f"  nvocab = {vocab}\n"
        f"  nhidden = {hidden}\n"
        # a token's own row has to stand out of the stream
        # (joyai_llm_flash_conf)
        "  init_sigma = 1.0\n"
    )
    for i in range(num_layers):
        if (i + 1) % layer_group_size:
            s += (
                f"layer[h{i},0->x{i}] = kimi_delta:kda{i}\n"
                f"  nhead = {attn_heads}\n"
                f"  key_dim = {head_dim}\n"
                f"  value_dim = {head_dim}\n"
                f"  conv_width = {short_conv_kernel_size}\n"
                f"  lower_bound = {kda_lower_bound!r}\n" + branch
            )
        else:
            s += (
                f"layer[h{i},0->x{i}] = latent_attention:mla{i}\n"
                f"  nhead = {attn_heads}\n"
                "  q_rank = 0\n"
                f"  kv_rank = {kv_lora_rank}\n"
                f"  nope_dim = {qk_nope_head_dim}\n"
                f"  rope_dim = {qk_rope_head_dim}\n"
                f"  v_dim = {v_head_dim}\n"
                f"  rope_theta = {rope_theta!r}\n"
                f"  rope_interleave = {rope_interleave}\n"
                "  out_gate = head\n"
                "  causal = 1\n" + branch
            )
        if i < first_k_dense:
            s += (
                f"layer[x{i}->h{i + 1}] = gated_mlp:mlp{i}\n"
                f"  nhidden = {mlp_hidden}\n" + branch
            )
        else:
            s += (
                f"layer[x{i}->h{i + 1}] = routed_experts:moe{i}\n"
                f"  nexpert = {num_experts}\n"
                f"  topk = {experts_per_tok}\n"
                f"  n_group = {n_group}\n"
                f"  topk_group = {topk_group}\n"
                f"  nhidden = {expert_hidden}\n"
                f"  first_expert = {first_expert}\n"
                f"  nheld = {experts_held}\n"
                f"  shared_hidden = {shared_hidden}\n"
                "  shared_gate = 0\n"
                "  score_func = sigmoid\n"
                "  select_bias = 1\n"
                f"  routed_scale = {routed_scaling_factor!r}\n"
                "  norm_topk = 1\n" + branch
            )
    s += (
        f"layer[h{num_layers}->nf] = rms_norm:norm_f\n"
        f"  eps = {eps!r}\n"
        "layer[nf->logits] = lm_head:head\n"
        f"  nhidden = {vocab}\n"
        "  init_sigma = 0.02\n"
        "layer[logits->logits] = softmax\n"
        # the mean over all positions: the loss sums over T
        f"  grad_scale = {1.0 / seq_len!r}\n"
        "netconfig = end\n"
    )
    return _packed_lm(s, token_file, seq_len, batch_size, num_round, dev,
                      compute_dtype, eta, scan_steps)


NEMOTRON_H_STAGE = "MEMEMEM*EME"


def nemotron_h_conf(
    vocab: int = 16384,
    seq_len: int = 8192,
    hidden: int = 4096,
    pattern: str = NEMOTRON_H_STAGE,
    mamba_heads: int = 16,
    mamba_head_dim: int = 64,
    mamba_groups: int = 1,
    mamba_state: int = 128,
    mamba_conv: int = 4,
    mamba_chunk: int = 128,
    attn_heads: int = 4,
    attn_kv_heads: int = 1,
    head_dim: int = 128,
    num_experts: int = 512,
    experts_per_tok: int = 22,
    expert_hidden: int = 2688,
    latent_hidden: int = 1024,
    shared_hidden: int = 5376,
    routed_scaling_factor: float = 5.0,
    first_expert: int = 0,
    experts_held: int = 8,
    num_nextn_predict_layers: int = 0,
    mtp_pattern: str = "*E",
    mtp_loss_weight: float = 0.3,
    eps: float = 1e-5,
    token_file: str = "",
    batch_size: int = 1,
    num_round: int = 10,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    eta: float = 0.0003,
    scan_steps: int = 8,
) -> str:
    """A Nemotron-H style hybrid language model (NVIDIA, ``model_type:
    nemotron_h``; arXiv:2504.03624): ``pattern`` is the published
    ``hybrid_override_pattern``, ONE conf layer a letter, each alone
    under its pre-norm (``rms_norm``) and residual add — ``M`` a Mamba-2
    mixer with ``mamba_groups`` groups of ``B`` and ``C`` and a gated
    norm a group, ``*`` a position-free grouped-query attention with
    heads of ``head_dim``, ``E`` a LatentMoE layer: ``num_experts``
    ungated ``relu(.)^2`` experts of ``expert_hidden`` that live in a
    ``latent_hidden``-wide latent behind two projections, a sigmoid
    router that chooses its top-``experts_per_tok`` by score + bias and
    weighs them by the unbiased scores, renormalised and times
    ``routed_scaling_factor``, plus one ungated ``relu(.)^2`` shared
    expert on the stream itself.  No MLP follows a mixer.  An untied
    head.  With ``num_nextn_predict_layers = 1`` (the published model has
    one) a prediction module follows the main head and loss, its layers
    named ``mtp_*`` and its block ``mtp_pattern`` (``_mtp_module`` has
    the form).

    The defaults are the published widths of Nemotron-3-Super-120B-A12B
    as ONE RANK of the first of eight pipeline stages holds them: the
    stage's 11 layers, and of each layer what an 8-way tensor-parallel,
    64-way expert-parallel rank has — ``mamba_heads`` 16 of 128 in
    ``mamba_groups`` 1 of 8 (a group, its heads and a gated norm of its
    own: what the groups are for, ``layers/ssm.py``), ``attn_heads`` 4
    of 32 on ``attn_kv_heads`` 1 of 2, ``experts_held`` 8 of 512 from
    ``first_expert`` on, an eighth of the vocabulary.  The shared expert
    is WHOLE (``shared_hidden`` 5376: a feed-forward width is never a
    rank's to cut; every rank computes it alike and a sum over ranks
    counts it once), and the module is left out (0): with both, the step
    compiles to 15.4 GB at its fullest and a 16 GB chip has no room to
    spare.  The share is spelt in the layers' own keys: each branch is
    the partial sum this rank's heads and experts give, and the ranks'
    partial sums add up to the whole layer's (a test does the sum).  The
    whole model is the same builder at 128 / 8, 32 / 2, 512 held, one
    prediction layer.

    Routers, selection bias and documents as ``joyai_llm_flash_conf``;
    written for memory as ``granite_h_conf`` is (``remat = 1``,
    ``eval_train = 0``).
    """
    if num_nextn_predict_layers not in (0, 1):
        raise ValueError("nemotron_h_conf: a prediction depth of 0 or 1")
    branch = (f"  prenorm = 1\n  eps = {eps!r}\n"
              "  residual_scale = 1.0\n"
              "  init_sigma = 0.02\n")

    def layer(kind: str, src: str, out: str, name: str) -> str:
        if kind == "M":
            return (
                f"layer[{src},0->{out}] = mamba2:{name}\n"
                f"  nhead = {mamba_heads}\n"
                f"  head_dim = {mamba_head_dim}\n"
                f"  ngroup = {mamba_groups}\n"
                f"  nstate = {mamba_state}\n"
                f"  conv_width = {mamba_conv}\n"
                f"  chunk = {mamba_chunk}\n" + branch
            )
        if kind == "*":
            return (
                f"layer[{src},0->{out}] = attention:{name}\n"
                f"  nhead = {attn_heads}\n"
                f"  nkvhead = {attn_kv_heads}\n"
                f"  head_dim = {head_dim}\n"
                "  causal = 1\n  no_bias = 1\n" + branch
            )
        if kind == "E":
            return (
                f"layer[{src}->{out}] = routed_experts:{name}\n"
                f"  nexpert = {num_experts}\n"
                f"  topk = {experts_per_tok}\n"
                f"  nhidden = {expert_hidden}\n"
                f"  latent_hidden = {latent_hidden}\n"
                "  expert_act = relu2\n"
                f"  first_expert = {first_expert}\n"
                f"  nheld = {experts_held}\n"
                f"  shared_hidden = {shared_hidden}\n"
                "  shared_gate = 0\n"
                "  score_func = sigmoid\n"
                "  select_bias = 1\n"
                f"  routed_scale = {routed_scaling_factor!r}\n"
                "  norm_topk = 1\n" + branch
            )
        raise ValueError(
            f"nemotron_h_conf: a pattern is a string of M, * and E, got "
            f"{kind!r}")

    names = {"M": "mixer", "*": "attn", "E": "moe"}
    s = (
        "netconfig = start\n"
        "layer[0->h0] = embedding:embed\n"
        f"  nvocab = {vocab}\n"
        f"  nhidden = {hidden}\n"
        # a token's own row has to stand out of the stream, as in
        # joyai_llm_flash_conf
        "  init_sigma = 1.0\n"
    )
    for i, kind in enumerate(pattern):
        s += layer(kind, f"h{i}", f"h{i + 1}", f"{names.get(kind, '')}{i}")
    last = f"h{len(pattern)}"
    s += (
        f"layer[{last}->nf] = rms_norm:norm_f\n"
        f"  eps = {eps!r}\n"
        "layer[nf->logits] = lm_head:head\n"
        f"  nhidden = {vocab}\n"
        "  init_sigma = 0.02\n"
        "layer[logits->logits] = softmax\n"
        # the mean over all positions: the loss sums over T
        f"  grad_scale = {1.0 / seq_len!r}\n"
    )
    if num_nextn_predict_layers:
        block = "".join(
            layer(kind, f"mtp_h{j}", f"mtp_h{j + 1}",
                  f"mtp_{names.get(kind, '')}{j}")
            for j, kind in enumerate(mtp_pattern))
        s += _mtp_module(last, hidden, eps, block,
                         f"mtp_h{len(mtp_pattern)}", seq_len,
                         mtp_loss_weight)
    s += "netconfig = end\n"
    return _packed_lm(s, token_file, seq_len, batch_size, num_round, dev,
                      compute_dtype, eta, scan_steps)


def _res_bottleneck(prev: str, name: str, cin: int, cmid: int, cout: int,
                    stride: int) -> str:
    """Bottleneck residual block: 1x1 reduce -> 3x3 -> 1x1 expand, each
    conv + batch_norm + relu (relu after the residual add), projection
    shortcut when shape changes (He et al. 2015)."""
    s = ""
    def cbr(src, dst, ch, k, st, pad, tag, relu=True):
        t = (
            f"layer[{src}->{dst}_c] = conv:{tag}_conv\n"
            f"  kernel_size = {k}\n  stride = {st}\n  pad = {pad}\n"
            f"  nchannel = {ch}\n  no_bias = 1\n  random_type = kaiming\n"
            f"layer[{dst}_c->{dst}] = batch_norm:{tag}_bn\n"
        )
        if relu:
            t += f"layer[{dst}->{dst}] = relu\n"
        return t

    s += cbr(prev, f"{name}_a", cmid, 1, stride, 0, f"{name}_a")
    s += cbr(f"{name}_a", f"{name}_b", cmid, 3, 1, 1, f"{name}_b")
    s += cbr(f"{name}_b", f"{name}_c", cout, 1, 1, 0, f"{name}_c",
             relu=False)
    if cin != cout or stride != 1:
        s += cbr(prev, f"{name}_p", cout, 1, stride, 0, f"{name}_proj",
                 relu=False)
        short = f"{name}_p"
    else:
        short = prev
    s += (
        f"layer[{short},{name}_c->{name}] = eltwise_sum\n"
        f"layer[{name}->{name}] = relu\n"
    )
    return s


_RESNET_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def resnet101_conf(**kw) -> str:
    """ResNet-101 — the [3, 4, 23, 3] depth of He et al. 2015 table 1."""
    return resnet50_conf(depth=101, **kw)


def resnet152_conf(**kw) -> str:
    """ResNet-152 — the [3, 8, 36, 3] depth of He et al. 2015 table 1."""
    return resnet50_conf(depth=152, **kw)


def resnet50_conf(
    batch_size: int = 128,
    num_class: int = 1000,
    input_size: int = 224,
    synthetic: bool = True,
    nsample: int = 0,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    depth: int = 50,
) -> str:
    """ResNet-50/101/152 (He et al. 2015, table 1) — bottleneck blocks,
    batch-norm everywhere, projection shortcuts at stage boundaries.
    New-scope zoo entry (the reference predates ResNets); built from the
    paper like the GoogLeNet/VGG entries.  ``depth`` picks the stage
    plan (50: [3,4,6,3], 101: [3,4,23,3], 152: [3,8,36,3]).
    """
    if input_size % 32:
        raise ValueError(
            f"resnet50_conf: input_size={input_size} must be a multiple "
            "of 32 (the stage chain downsamples 5x; anything else leaves "
            "the final avg pool non-global)"
        )
    shape = f"3,{input_size},{input_size}"
    nsample = nsample or batch_size * 4
    data = (
        _iter_block("data", nsample, shape, num_class, threadbuffer=True)
        + _iter_block("eval", batch_size * 2, shape, num_class)
        if synthetic
        else ""
    )
    net = (
        "netconfig = start\n"
        "layer[0->c1] = conv:conv1\n"
        "  kernel_size = 7\n  stride = 2\n  pad = 3\n  nchannel = 64\n"
        "  no_bias = 1\n  random_type = kaiming\n"
        "layer[c1->b1] = batch_norm:bn1\n"
        "layer[b1->b1] = relu\n"
        # pad 0: the framework's ceil-shape pooling (reference parity)
        # with pad 1 would give 57x57; unpadded k3 s2 on 112 lands on
        # the paper's 56x56
        "layer[b1->p1] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
    )
    prev, cin = "p1", 64
    if depth not in _RESNET_BLOCKS:
        raise ValueError(
            f"resnet depth must be one of {sorted(_RESNET_BLOCKS)}, "
            f"got {depth}"
        )
    b0, b1, b2, b3 = _RESNET_BLOCKS[depth]
    stages = [(b0, 64, 256, 1), (b1, 128, 512, 2), (b2, 256, 1024, 2),
              (b3, 512, 2048, 2)]
    for si, (blocks, cmid, cout, stride) in enumerate(stages):
        for bi in range(blocks):
            name = f"s{si}b{bi}"
            st = stride if bi == 0 else 1
            net += _res_bottleneck(prev, name, cin, cmid, cout, st)
            prev, cin = name, cout
    net += (
        f"layer[{prev}->pool] = avg_pooling\n"
        f"  kernel_size = {max(1, input_size // 32)}\n  stride = 1\n"
        "layer[pool->flat] = flatten\n"
        f"layer[flat->fc] = fullc:fc1000\n"
        f"  nhidden = {num_class}\n  random_type = xavier\n"
        "layer[fc->fc] = softmax\n"
        "netconfig = end\n"
    )
    extra = (
        "metric = rec@1\nmetric = rec@5\n"
        "wmat:lr = 0.1\nwmat:wd = 0.0001\n"
        # one-pass E[x^2]-E[x]^2 batch-norm statistics: the 53 BNs read
        # their activations once instead of twice (stats in f32 either
        # way); measured 68.3 -> 63.6 ms/step on the v5e b128 step
        # (doc/performance.md ResNet bisection)
        "bn_stats = onepass\n"
        f"compute_dtype = {compute_dtype}\n"
    )
    return data + net + _tail(batch_size, shape, 90, eta=0.1, dev=dev,
                              extra=extra)



# ---------------------------------------------------------------------------
def vgg19_conf(**kw) -> str:
    """VGG-19 (configuration E, Simonyan & Zisserman 2014)."""
    return vgg16_conf(depth=19, **kw)


def vgg16_conf(
    batch_size: int = 64,
    num_class: int = 1000,
    input_size: int = 224,
    synthetic: bool = True,
    nsample: int = 0,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    depth: int = 16,
) -> str:
    """VGG-16/19 (configurations D/E, Simonyan & Zisserman 2014)."""
    shape = f"3,{input_size},{input_size}"
    nsample = nsample or batch_size * 4
    data = (
        _iter_block("data", nsample, shape, num_class, threadbuffer=True)
        + _iter_block("eval", batch_size * 2, shape, num_class)
        if synthetic
        else ""
    )
    blocks: List[str] = []
    node = "0"
    idx = 0
    if depth == 16:
        plan = [(2, 64), (2, 128), (3, 256), (3, 512), (3, 512)]
    elif depth == 19:
        plan = [(2, 64), (2, 128), (4, 256), (4, 512), (4, 512)]
    else:
        raise ValueError(f"vgg depth must be 16 or 19, got {depth}")
    for b, (reps, ch) in enumerate(plan, start=1):
        for r in range(1, reps + 1):
            dst = f"c{b}_{r}"
            blocks.append(
                f"layer[{node}->{dst}] = conv:conv{b}_{r}\n"
                f"  kernel_size = 3\n  pad = 1\n  nchannel = {ch}\n"
                "  random_type = xavier\n"
                f"layer[+1:{dst}r] = relu\n"
            )
            node = f"{dst}r"
            idx += 1
        blocks.append(
            f"layer[{node}->pool{b}] = max_pooling\n"
            "  kernel_size = 2\n  stride = 2\n"
        )
        node = f"pool{b}"
    net = (
        "netconfig = start\n"
        + "".join(blocks)
        + f"layer[{node}->flat] = flatten\n"
        "layer[flat->f6] = fullc:fc6\n"
        "  nhidden = 4096\n  init_sigma = 0.01\n"
        "layer[+1:f6r] = relu\n"
        "layer[f6r->f6r] = dropout\n  threshold = 0.5\n"
        "layer[f6r->f7] = fullc:fc7\n"
        "  nhidden = 4096\n  init_sigma = 0.01\n"
        "layer[+1:f7r] = relu\n"
        "layer[f7r->f7r] = dropout\n  threshold = 0.5\n"
        f"layer[f7r->f8] = fullc:fc8\n  nhidden = {num_class}\n"
        "  init_sigma = 0.01\n"
        "layer[f8->f8] = softmax\n"
        "netconfig = end\n"
    )
    extra = (
        "metric = rec@1\nmetric = rec@5\n"
        f"compute_dtype = {compute_dtype}\n"
    )
    return data + net + _tail(batch_size, shape, 74, eta=0.01, dev=dev, extra=extra)


# ---------------------------------------------------------------------------
def kaggle_bowl_conf(
    batch_size: int = 64, synthetic: bool = True, dev: str = "tpu",
    compute_dtype: str = "float32",
) -> str:
    """NDSB plankton convnet (bowl.conf parity: 40×40×3, 121 classes).

    Default stays f32 (the net is tiny — its 5-minute-GPU-training-run
    claim is the BASELINE target, and logloss parity matters more than
    step time); pass ``compute_dtype="bfloat16"`` for throughput runs.
    """
    shape = "3,40,40"
    data = (
        _iter_block("data", 3200, shape, 121)
        + _iter_block("eval", 640, shape, 121)
        if synthetic
        else ""
    )
    net = (
        "netconfig = start\n"
        "layer[0->1] = conv:conv1\n"
        "  kernel_size = 5\n  pad = 2\n  nchannel = 32\n"
        "  random_type = xavier\n"
        "layer[1->2] = relu\n"
        "layer[2->3] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
        "layer[3->4] = conv:conv2\n"
        "  kernel_size = 3\n  pad = 1\n  nchannel = 64\n"
        "  random_type = xavier\n"
        "layer[4->5] = relu\n"
        "layer[5->6] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
        "layer[6->7] = conv:conv3\n"
        "  kernel_size = 3\n  pad = 1\n  nchannel = 128\n"
        "  random_type = xavier\n"
        "layer[7->8] = relu\n"
        "layer[8->9] = conv:conv4\n"
        "  kernel_size = 3\n  pad = 1\n  nchannel = 128\n"
        "  random_type = xavier\n"
        "layer[9->10] = relu\n"
        "layer[10->11] = max_pooling\n  kernel_size = 3\n  stride = 2\n"
        "layer[11->12] = flatten\n"
        "layer[12->13] = fullc:fc1\n"
        "  nhidden = 512\n  init_sigma = 0.01\n"
        "layer[13->14] = relu\n"
        "layer[14->14] = dropout\n  threshold = 0.5\n"
        "layer[14->15] = fullc:fc2\n"
        "  nhidden = 121\n  init_sigma = 0.01\n"
        "layer[15->15] = softmax\n"
        "netconfig = end\n"
    )
    extra = (
        "metric = logloss\n"
        f"compute_dtype = {compute_dtype}\n"
    )
    return data + net + _tail(batch_size, shape, 100, eta=0.01, dev=dev, extra=extra)


# ---------------------------------------------------------------------------
def transformer_conf(
    batch_size: int = 32,
    seq_len: int = 128,
    dim: int = 128,
    nhead: int = 4,
    nlayer: int = 2,
    num_class: int = 10,
    causal: int = 0,
    seq_parallel: int = 0,
    synthetic: bool = False,
    nsample: int = 0,
    dev: str = "tpu",
    compute_dtype: str = "bfloat16",
    pipeline_parallel: int = 0,
    n_microbatch: int = 4,
    attn_impl: str = "auto",
) -> str:
    """Pre-norm transformer encoder classifier over dense sequences.

    New TPU-first scope (the reference has no sequence models): blocks of
    layer_norm -> attention -> residual -> layer_norm -> mlp -> residual,
    then mean pooling and a softmax head.  ``seq_parallel=1`` runs ring
    attention with the sequence sharded over the mesh model axis
    (``ops/attention.py``).

    ``pipeline_parallel >= 1`` declares the SAME block stack as a
    ``pipe_transformer`` layer (stacked params) so it can run as a GPipe
    pipeline over the mesh model axis; ``pipeline_parallel = 1`` keeps
    pipelining off (plain scanned stack) with identical math — the
    parity pair for tests.
    """
    nsample = nsample or batch_size * 4
    data = ""
    if synthetic:
        for kind, n in (("data", nsample), ("eval", batch_size * 2)):
            data += (
                f"{kind} = {'train' if kind == 'data' else 'test'}\n"
                "iter = synthetic\n"
                f"  nsample = {n}\n"
                f"  input_shape = 1,{seq_len},{dim}\n"
                f"  nclass = {num_class}\n"
                "  layout = seq\n"
                "iter = end\n"
            )
    s = "netconfig = start\n"
    if pipeline_parallel >= 1 and seq_parallel:
        raise ValueError(
            "transformer_conf: seq_parallel (ring attention) and "
            "pipeline_parallel are mutually exclusive — both shard over "
            "the mesh model axis"
        )
    if pipeline_parallel >= 1:
        s += (
            "layer[0->blocks] = pipe_transformer:blocks\n"
            f"  nblock = {nlayer}\n"
            f"  nhead = {nhead}\n"
            f"  causal = {causal}\n"
            f"  ffn_hidden = {dim * 4}\n"
            f"  pipeline_parallel = {1 if pipeline_parallel > 1 else 0}\n"
            f"  n_microbatch = {n_microbatch}\n"
            "  init_sigma = 0.02\n"
        )
        prev = "blocks"
        per_layer_blocks = range(0)
    else:
        prev = "0"
        per_layer_blocks = range(nlayer)
    if len(per_layer_blocks):
        blocks, prev = _transformer_blocks(
            prev, nlayer, nhead, dim, causal, seq_parallel, attn_impl
        )
        s += blocks
    s += (
        f"layer[{prev}->pool] = seq_pool\n"
        f"layer[pool->fc] = fullc:head\n"
        f"  nhidden = {num_class}\n  init_sigma = 0.02\n"
        "layer[fc->fc] = softmax\n"
        "netconfig = end\n"
        "input_layout = seq\n"
    )
    extra = f"compute_dtype = {compute_dtype}\n"
    if pipeline_parallel > 1:
        extra += f"model_parallel = {pipeline_parallel}\n"
    return data + s + _tail(
        batch_size, f"1,{seq_len},{dim}", 10, eta=0.01, dev=dev, extra=extra
    )
