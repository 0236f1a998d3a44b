"""FunctionalNet: a parsed NetGraph compiled into pure JAX functions.

This replaces the reference's mutable ``NeuralNet`` engine
(``/root/reference/src/nnet/neural_net-inl.hpp``): instead of nodes that
double as activation/gradient storage and per-layer hand-written backprop,
the graph is executed as one pure function and ``jax.grad`` differentiates
the summed loss.  XLA sees the whole step and fuses across layer
boundaries — the TPU analog of mshadow's expression fusing, but global.

Semantics preserved:

* node 0 is the input; ``input_shape = C,H,W`` maps to a flat ``(N, W)``
  node when ``C == H == 1`` else an NHWC image node (the reference is
  NCHW; layout is the TPU-native transposition of the same data).
* layers are configured with the global defaults first, then their own
  section (``neural_net-inl.hpp:252-264``).
* self-loop loss layers transform their node in place (downstream sees
  probabilities) and contribute ``grad_scale / (batch_size *
  update_period) * L`` to the total loss
  (``loss_layer_base-inl.hpp:60-63``).
* shared layers reuse the primary layer's parameters.
* label fields: the batch label matrix is sliced by the ``label_vec[a,b)``
  ranges; each loss layer reads its ``target`` field.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..layers import Layer, LossLayer, create_layer
from ..layers.structure import SplitLayer
from ..ops import flash, gdn_fused
from .graph import NetGraph

ConfigEntry = Tuple[str, str]

#: what a layer's ``remat`` keeps across the backward pass: the values its
#: kernels NAME as their own outputs that the backward reads again (the
#: flash forward's ``o`` and ``lse``; what the delta rule's ``solve`` and
#: ``scan`` wrote), so the recompute does not run those kernels a second
#: time.  A layer that names nothing keeps nothing and lowers to the
#: program ``policy=None`` gives
REMAT_POLICY = jax.checkpoint_policies.save_only_these_names(
    *flash.KEPT_NAMES, *gdn_fused.KEPT_NAMES)
_remat = functools.partial(jax.checkpoint, policy=REMAT_POLICY)


def _opsq():
    """Lazy ``ops.quant`` accessor (keeps the quant helpers out of the
    hot import path for nets that never quantize)."""
    from ..ops import quant

    return quant


class FunctionalNet:
    """Executable form of a NetGraph."""

    def __init__(self, graph: NetGraph) -> None:
        self.graph = graph
        self.batch_size = 0
        self.update_period = 1
        self.compute_dtype = jnp.float32
        self.remat = 0
        # sibling-1x1 conv fusion is ON by default: it is mathematically
        # exact (see _sibling_1x1_groups) and measured +4.3% on GoogLeNet
        # b128 on the v5e chip; `fuse_1x1 = 0` opts out
        self.fuse_1x1 = 1
        self._fuse_cache = None
        # branch-embedding fusion (doc/performance.md "Conv
        # efficiency"): merge sibling odd-k stride-1 SAME convs (the
        # inception 3x3/5x5 branches) into ONE block-kernel conv — an
        # adequately-shaped GEMM for ~3.6x more MACs.  Exact (119->92
        # contractions on GoogLeNet).  Default -1 = AUTO: ON for
        # inference program builds (predict/extract/eval — the serve
        # engine's programs) on ACCELERATOR backends, where the trade
        # buys MXU shape; OFF on CPU, where the extra MACs are just
        # extra work (measured 0.14x predict throughput —
        # tools/wino_bf16_ab.py --bembed-only), and OFF for the train
        # step, whose on-chip A/B is still queued
        # (tools/googlenet_bisect.py bembed).  An explicit 0/1 pins
        # every build.
        self.conv_branch_embed = -1
        # the platform this net's programs actually TARGET (the dev=
        # mesh's platform, bound by the trainer after it builds the
        # mesh) — auto branch-embed keys on it, NOT on the process's
        # default backend: dev=cpu on a TPU host must stay unfused
        self.exec_backend: Optional[str] = None
        self._embed_cache = None
        # on-chip kernel library (ops/kernels/): auto | off | name list.
        # `auto` (default) follows the RECORDED per-backend verdicts in
        # ops/kernels/verdicts.json — a Pallas kernel runs only where a
        # committed promote from tools/kernel_ab.py says it pays, the
        # same discipline as conv_branch_embed=-1 above.  A name list
        # pins those kernels ON (interpret mode off-TPU: exact, slow —
        # the parity/test spelling).  Inference builds only: the Pallas
        # calls carry no custom vjp, so the train forward stays stock.
        self.kernel_lib = "auto"
        self._kernel_sel = None
        # instantiate layers (shared layers alias the primary instance)
        self.layer_objs: List[Layer] = []
        self.param_key: List[Optional[str]] = []  # params pytree key per layer
        for i, spec in enumerate(graph.layers):
            if spec.type_name == "shared":
                primary = self.layer_objs[spec.primary]
                self.layer_objs.append(primary)
                self.param_key.append(self.param_key[spec.primary])
                continue
            lay = create_layer(spec.type_name)
            if isinstance(lay, SplitLayer):
                lay.n_split = len(spec.nindex_out)
            self.layer_objs.append(lay)
            tag = spec.name if spec.name else spec.type_name
            self.param_key.append(f"l{i}_{tag}")
        self._configure_layers()
        # a layer that names another by ``tied = <name>`` (lm_head)
        # computes with that layer's parameters: one key, one leaf, one
        # gradient, the sum of both uses
        for i, lay in enumerate(self.layer_objs):
            if getattr(lay, "tied", "") and \
                    graph.layers[i].type_name != "shared":
                self.param_key[i] = self.param_key[
                    graph.layer_index_of(lay.tied)]
        # a layer that BORROWS single leaves of other layers beside its
        # own (``borrows()``: routed_experts' ``route_norm``) is handed
        # them under its own tags: one leaf, one gradient
        self.borrowed: Dict[int, Dict[str, Tuple[str, str]]] = {}
        for i, lay in enumerate(self.layer_objs):
            wants = lay.borrows()
            if wants:
                self.borrowed[i] = {
                    tag: (self.param_key[graph.layer_index_of(name)], src)
                    for tag, (name, src) in wants.items()}
        self.node_shapes: List[Optional[Tuple[int, ...]]] = []
        # params kept in f32 even under mixed precision (norm layers,
        # whose math runs in f32 — a bf16 round-trip would only lose bits)
        from ..layers.conv import BatchNormLayer
        from ..layers.sequence import LayerNormLayer, RMSNormLayer

        self._f32_param_keys = {
            self.param_key[i]
            for i, lay in enumerate(self.layer_objs)
            if isinstance(lay, (BatchNormLayer, LayerNormLayer,
                                RMSNormLayer))
        }
        # per-tag exemptions (e.g. pipe_transformer's stacked LN params)
        self._f32_tag_map = {
            self.param_key[i]: lay.f32_tags
            for i, lay in enumerate(self.layer_objs)
            if lay.f32_tags
        }

    # ------------------------------------------------------------------
    def _configure_layers(self) -> None:
        g = self.graph
        for name, val in g.defcfg:
            if name == "batch_size":
                self.batch_size = int(val)
            elif name == "update_period":
                self.update_period = int(val)
            elif name == "remat":
                # jax.checkpoint each layer: recompute activations in
                # backprop instead of keeping them in HBM (memory for
                # FLOPs — lets bigger batches fit per chip)
                self.remat = int(val)
            elif name == "fuse_1x1":
                # execute sibling 1x1 convs on one input node as ONE
                # concatenated conv (see _sibling_1x1_groups)
                self.fuse_1x1 = int(val)
            elif name == "conv_branch_embed":
                self.conv_branch_embed = int(val)
            elif name == "kernel_lib":
                from ..ops import kernels as _klib

                # canonicalize AND validate: a kernel-name typo must
                # fail the build, not silently serve the stock path
                self.kernel_lib = _klib.parse_mode(val)
                self._kernel_sel = None
            elif name == "compute_dtype":
                if val in ("bfloat16", "bf16"):
                    self.compute_dtype = jnp.bfloat16
                elif val in ("float32", "fp32"):
                    self.compute_dtype = jnp.float32
                else:
                    raise ValueError(
                        f"compute_dtype must be bfloat16 or float32, got {val!r}"
                    )
        for i, spec in enumerate(g.layers):
            if spec.type_name == "shared":
                continue
            lay = self.layer_objs[i]
            for name, val in g.defcfg:
                self._safe_set(lay, name, val)
            for name, val in g.layercfg[i]:
                self._safe_set(lay, name, val)

    @staticmethod
    def _safe_set(lay: Layer, name: str, val: str) -> None:
        """Layer ``set_param`` ignores unknown keys by design (the elif
        chains fall through silently), so any exception here is a real
        parse/value error on a key the layer *does* claim — propagate it.
        A config typo in layer scope must fail loudly, not vanish."""
        try:
            lay.set_param(name, val)
        except Exception as e:
            raise ValueError(
                f"layer {lay.__class__.__name__}: bad value for "
                f"{name!r} = {val!r}: {e}"
            ) from e

    # ------------------------------------------------------------------
    def input_node_shape(self, batch_size: int) -> Tuple[int, ...]:
        c, h, w = self.graph.input_shape
        if self.graph.input_layout == "seq":
            # sequence node: input_shape = 1,T,D -> (N, T, D)
            return (batch_size, h, w)
        if c == 1 and h == 1:
            return (batch_size, w)
        return (batch_size, h, w, c)

    def extra_node_shape(self, k: int, batch_size: int) -> Tuple[int, ...]:
        c, h, w = self.graph.extra_shape[k]
        if c == 1 and h == 1:
            return (batch_size, w)
        return (batch_size, h, w, c)

    def infer_shapes(self, batch_size: int) -> List[Tuple[int, ...]]:
        """Run shape inference over the DAG; returns per-node shapes."""
        g = self.graph
        shapes: List[Optional[Tuple[int, ...]]] = [None] * g.num_nodes
        shapes[0] = self.input_node_shape(batch_size)
        for k in range(g.extra_data_num):
            shapes[k + 1] = self.extra_node_shape(k, batch_size)
        for i, spec in enumerate(g.layers):
            lay = self.layer_objs[i]
            in_shapes = []
            for n in spec.nindex_in:
                if shapes[n] is None:
                    raise ValueError(
                        f"layer {i} ({spec.type_name}) input node "
                        f"{g.node_names[n]!r} has no shape yet"
                    )
                in_shapes.append(shapes[n])
            out_shapes = lay.infer_shape(in_shapes)
            if len(out_shapes) != len(spec.nindex_out):
                raise ValueError(
                    f"layer {i} ({spec.type_name}): produced {len(out_shapes)} "
                    f"outputs for {len(spec.nindex_out)} output nodes"
                )
            for n, s in zip(spec.nindex_out, out_shapes):
                shapes[n] = tuple(s)
        self.node_shapes = shapes
        return shapes  # type: ignore[return-value]

    # ------------------------------------------------------------------
    def init_aux(self, batch_size: int) -> Dict[str, dict]:
        """Non-gradient layer state (e.g. batch-norm running statistics
        with ``bn_eval = running``); empty dict when no layer carries any."""
        shapes = self.infer_shapes(batch_size)
        aux: Dict[str, dict] = {}
        for i, spec in enumerate(self.graph.layers):
            if spec.type_name == "shared":
                continue
            lay = self.layer_objs[i]
            if hasattr(lay, "init_aux"):
                st = lay.init_aux([shapes[n] for n in spec.nindex_in])
                if st:
                    aux[self.param_key[i]] = st
        return aux

    def init_params(self, key: jax.Array, batch_size: int) -> Dict[str, dict]:
        shapes = self.infer_shapes(batch_size)
        params: Dict[str, dict] = {}
        for i, spec in enumerate(self.graph.layers):
            if spec.type_name == "shared":
                continue
            lay = self.layer_objs[i]
            key, sub = jax.random.split(key)
            in_shapes = [shapes[n] for n in spec.nindex_in]
            p = lay.init_params(sub, in_shapes)
            if p:
                params[self.param_key[i]] = p
        for i, wants in self.borrowed.items():
            for tag, (key, src) in wants.items():
                if src not in params.get(key, {}):
                    raise ValueError(
                        f"layer {i} ({self.graph.layers[i].type_name}): "
                        f"{tag} names a layer that has no {src!r} weight")
        return params

    # ------------------------------------------------------------------
    def _graph_versions(self):
        """Declaration-order dataflow scan shared by the fusion
        planners: per-node write counts, per-layer read keys
        ``(node, version-at-read)``, and ``writers[n][v]`` = the layer
        whose write created version ``v+1`` (version 0 = graph input).
        One implementation so the two planners can never disagree
        about graph provenance."""
        g = self.graph
        writes = [0] * g.num_nodes
        for spec in g.layers:
            for n in spec.nindex_out:
                writes[n] += 1
        version = [0] * g.num_nodes
        writers: Dict[int, List[int]] = {}
        in_keys: List[List[Tuple[int, int]]] = []
        for i, spec in enumerate(g.layers):
            in_keys.append([(n, version[n]) for n in spec.nindex_in])
            for n in spec.nindex_out:  # reads happen before writes
                writers.setdefault(n, []).append(i)
                version[n] += 1
        return writes, in_keys, writers

    def _sibling_1x1_groups(self):
        """Groups of distinct 1x1/s1/p0/ungrouped conv layers sharing one
        input node, to be executed as ONE concatenated conv.

        Inception blocks issue 3-4 narrow 1x1 convs on the same tensor
        (GoogLeNet: 16-192 output channels each); the MXU runs one wide
        GEMM far better than several narrow ones (a 128-lane systolic
        array is mostly idle on a 16-channel output), and XLA does not
        merge separate convolutions itself.  Concatenating the HWIO
        kernels on the O axis and splitting the output channels back is
        mathematically exact, and parameters stay per-layer — the
        checkpoint format, weight getters and updater keys are
        untouched.  Default on (measured +4.3% on GoogLeNet b128 v5e);
        ``fuse_1x1 = 0`` opts out.

        Returns ``(groups, member)``: leader layer index -> all member
        indices (declaration order), and member index -> leader.
        """
        if self._fuse_cache is not None:
            return self._fuse_cache
        from ..layers.conv import ConvolutionLayer

        # group key is (node, write-version at read time): a self-loop
        # layer (layer[a->a] = relu) WRITES the shared node between two
        # sibling declarations, so siblings across that write see
        # different values and must not fuse.  Fused members also run
        # EARLY (at the leader's position), so a member must be the sole
        # writer of its output node — otherwise the declaration-order
        # overwrite sequence changes
        writes, in_keys, _writers = self._graph_versions()
        by_input: Dict[Tuple[int, int, int], List[int]] = {}
        for i, spec in enumerate(self.graph.layers):
            is_candidate = False
            if spec.type_name != "shared":  # aliased params: plain path
                lay = self.layer_objs[i]
                if type(lay) is ConvolutionLayer:
                    p = lay.param
                    # any shared stride fuses (the key carries it): the
                    # reference-shaped nets issue stride-2 1x1 sibling
                    # pairs too — ResNet's stage-boundary blocks read
                    # one node with both the bottleneck-reduce and the
                    # projection-shortcut 1x1 s2 convs
                    is_candidate = (
                        (p.kernel_height, p.kernel_width,
                         p.pad_x, p.pad_y, p.num_group)
                        == (1, 1, 0, 0, 1)
                        and len(spec.nindex_in) == 1
                        and len(spec.nindex_out) == 1
                        and spec.nindex_out[0] != spec.nindex_in[0]
                        and writes[spec.nindex_out[0]] == 1
                    )
            if is_candidate:
                n, v = in_keys[i][0]
                by_input.setdefault((n, v, p.stride), []).append(i)
        groups: Dict[int, List[int]] = {}
        member: Dict[int, int] = {}
        for idxs in by_input.values():
            if len(idxs) < 2:
                continue
            groups[idxs[0]] = idxs
            for j in idxs:
                member[j] = idxs[0]
        self._fuse_cache = (groups, member)
        return self._fuse_cache

    @staticmethod
    def _apply_fused_1x1(stride: int, gparams: List[dict], x,
                         kernels=None):
        """One conv for the whole sibling group; per-member outputs.

        The group kernel is assembled by SCATTERING each member into a
        zeros block (``.at[].set``), NOT ``jnp.concatenate``: under a
        model-parallel mesh the member kernels arrive sharded on their
        output-channel axis, and this jaxlib's GSPMD partitioner
        miscompiles concatenate-along-the-sharded-axis feeding a
        convolution (silently wrong values, ~0.5 absolute on unit-scale
        activations; verified jaxlib 0.4.36 CPU, 2- and 4-way model
        axes).  The dynamic-update-slice lowering partitions correctly
        — bit-identical to the unfused path in the mp=1 case and within
        SPMD parity tolerance under TP (tests/test_parallel.py
        ``test_fuse_1x1_matches_under_mesh``)."""
        from jax import lax

        from ..ops import quant as opsq

        ws = [opsq.effective_wmat(d, x.dtype) for d in gparams]
        cin = ws[0].shape[2]
        nout = sum(w.shape[3] for w in ws)
        wk = jnp.zeros((1, 1, cin, nout), x.dtype)
        off = 0
        for w in ws:
            wk = wk.at[:, :, :, off:off + w.shape[3]].set(w)
            off += w.shape[3]
        if kernels is not None and kernels.active("conv_block", x=x,
                                                  wk=wk):
            # the fused Pallas GEMM: conv + every member's bias in one
            # epilogue.  Members without a bias get zeros (x + 0 == x),
            # so slicing the biased block equals per-member bias adds.
            from ..ops.kernels import conv_block as _kcb

            bias = (jnp.concatenate([
                (d["bias"].astype(x.dtype) if "bias" in d
                 else jnp.zeros((w.shape[3],), x.dtype))
                for d, w in zip(gparams, ws)])
                if any("bias" in d for d in gparams) else None)
            y = _kcb.conv1x1_block(x, wk, bias, stride=stride,
                                   interpret=kernels.interpret)
            outs = []
            off = 0
            for w in ws:
                outs.append(lax.slice_in_dim(
                    y, off, off + w.shape[3], axis=3))
                off += w.shape[3]
            return outs
        y = lax.conv_general_dilated(
            x, wk,
            window_strides=(stride, stride), padding=((0, 0), (0, 0)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        outs = []
        off = 0
        for d, w in zip(gparams, ws):
            part = lax.slice_in_dim(y, off, off + w.shape[3], axis=3)
            off += w.shape[3]
            if "bias" in d:
                part = part + d["bias"].astype(x.dtype)
            outs.append(part)
        return outs

    # ------------------------------------------------------------------
    # branch-embedding fusion (doc/performance.md "Conv efficiency"):
    # inception-style sibling branch convs (3x3 + 5x5, stride 1, SAME
    # padding) become ONE conv whose block kernel holds each member's
    # kernel center-embedded in its own (cin, cout) slice, zeros in the
    # cross-slices.  Exact: with SAME padding and stride 1, the k_max
    # conv of a center-embedded smaller kernel equals the smaller conv.
    # The MXU trades ~3.6x more MACs for one adequately-shaped GEMM per
    # module (K = k_max^2 * sum(cin), N = sum(cout)) — the cuDNN-style
    # algorithmic-rewrite analog, opt-in pending the on-chip A/B.

    # elementwise single-in/single-out layers a provenance walk may
    # step through: they preserve spatial dims, so two convs whose
    # walks meet at one (node, version) see identical (H, W)
    _EMBED_WALK_TYPES = frozenset({
        "relu", "sigmoid", "tanh", "softplus", "xelu", "insanity",
        "prelu", "bias", "batch_norm", "dropout",
    })

    def _branch_embed_plan(self):
        """Compute ``(items, groups)``: an execution plan for forward()
        — ``items`` is a list of ``("L", layer_idx)`` / ``("E",
        leader_idx)`` — plus ``leader -> member idxs``.

        Members of a group are odd-k (3/5/7) stride-1 SAME convs whose
        inputs trace back, through elementwise layers and 1x1/s1/p0
        convs, to the SAME (node, write-version) — the inception
        branch shape.  Because declaration order interleaves the
        branches (the 5x5 reduce sits between the 3x3 conv and the 5x5
        conv), the group executes at the LAST member's position and
        layers that consume member outputs inside that window are
        deferred to after the group; the reorder is only applied when
        every node written in the window is single-writer, which makes
        any dependency-respecting order equivalent."""
        if self._embed_cache is not None:
            return self._embed_cache
        from ..layers.conv import ConvolutionLayer

        g = self.graph
        L = len(g.layers)
        writes, in_keys, writers = self._graph_versions()

        def walkable(p: int) -> bool:
            ps = g.layers[p]
            if len(ps.nindex_in) != 1 or len(ps.nindex_out) != 1:
                return False
            if ps.type_name in self._EMBED_WALK_TYPES:
                return True
            if ps.type_name == "conv":
                lp = self.layer_objs[p].param
                return ((lp.kernel_height, lp.kernel_width, lp.stride,
                         lp.pad_y, lp.pad_x, lp.num_group)
                        == (1, 1, 1, 0, 0, 1))
            return False

        def root_of(i: int) -> Tuple[int, int]:
            n, v = in_keys[i][0]
            while v > 0:
                p = writers[n][v - 1]
                if not walkable(p):
                    break
                n, v = in_keys[p][0]
            return n, v

        by_root: Dict[Tuple[int, int], List[int]] = {}
        for i, spec in enumerate(g.layers):
            if spec.type_name == "shared":
                continue
            lay = self.layer_objs[i]
            if type(lay) is not ConvolutionLayer:
                continue
            p = lay.param
            if not (p.stride == 1 and p.num_group == 1
                    and p.kernel_height == p.kernel_width
                    and p.kernel_height in (3, 5, 7)
                    and p.pad_y == (p.kernel_height - 1) // 2
                    and p.pad_x == (p.kernel_width - 1) // 2
                    and len(spec.nindex_in) == 1
                    and len(spec.nindex_out) == 1
                    and spec.nindex_out[0] != spec.nindex_in[0]
                    and writes[spec.nindex_out[0]] == 1):
                continue
            by_root.setdefault(root_of(i), []).append(i)

        fuse_groups, _fuse_member = (
            self._sibling_1x1_groups() if self.fuse_1x1 else ({}, {})
        )
        key_counts: Dict[Optional[str], int] = {}
        for k in self.param_key:
            key_counts[k] = key_counts.get(k, 0) + 1
        groups: List[Tuple[List[int], List[int]]] = []  # (idxs, moved)
        for idxs in by_root.values():
            if len(idxs) < 2:
                continue
            idxs = sorted(idxs)
            first, last = idxs[0], idxs[-1]
            iset = set(idxs)
            dep_nodes: set = set()
            moved: List[int] = []
            for j in range(first, last + 1):
                sj = g.layers[j]
                if j in iset:
                    dep_nodes.update(sj.nindex_out)
                elif any(n in dep_nodes for n in sj.nindex_in):
                    moved.append(j)
                    dep_nodes.update(sj.nindex_out)
            ok = all(
                writes[n] == 1
                for j in range(first, last + 1)
                for n in g.layers[j].nindex_out
            ) and all(writes[in_keys[j][0][0]] <= 1 for j in idxs)
            # (<= 1 above: a member may read the never-written graph
            # input node directly — trivially stable under deferral)
            # a deferred 1x1-fuse leader would shift its whole sibling
            # group past consumers of the other members — skip
            ok = ok and not any(j in fuse_groups for j in moved)
            # a deferred SHARED STATEFUL layer (e.g. a shared batch_norm
            # chaining running stats) would execute after a later
            # occurrence of itself, reversing the documented state-chain
            # order — node dataflow alone can't see aux-state edges
            ok = ok and not any(
                hasattr(self.layer_objs[j], "apply_stateful")
                and key_counts[self.param_key[j]] > 1
                for j in moved
            )
            if ok:
                groups.append((idxs, moved))
        groups.sort(key=lambda t: t[0][0])

        if not groups:
            self._embed_cache = (None, {})
            return self._embed_cache
        items: List[Tuple[str, int]] = []
        gmap: Dict[int, List[int]] = {}
        pos = 0
        for idxs, moved in groups:
            first, last = idxs[0], idxs[-1]
            if first < pos:       # overlapping window: drop this group
                continue
            iset = set(idxs)
            mset = set(moved)
            items.extend(("L", j) for j in range(pos, first))
            items.extend(
                ("L", j) for j in range(first, last + 1)
                if j not in iset and j not in mset
            )
            items.append(("E", idxs[0]))
            items.extend(("L", j) for j in moved)
            gmap[idxs[0]] = idxs
            pos = last + 1
        items.extend(("L", j) for j in range(pos, L))
        self._embed_cache = (items, gmap)
        return self._embed_cache

    @staticmethod
    def _apply_branch_embed(gparams: List[dict], xs):
        """One block-kernel conv for the whole branch group; per-member
        outputs.  Member kernel/channel geometry comes from each
        ``wmat`` (HWIO) — static under trace."""
        from jax import lax

        if not all(xi.shape[:3] == xs[0].shape[:3] for xi in xs):
            # explicit raise (not assert — stripped under python -O): a
            # planner regression must surface as this message, not as an
            # opaque concatenate shape error downstream
            raise ValueError(
                "branch-embed members must share input spatial dims: "
                f"{[tuple(xi.shape) for xi in xs]}"
            )
        from ..ops import quant as opsq

        ws = [opsq.effective_wmat(d, xs[0].dtype) for d in gparams]
        kmax = max(w.shape[0] for w in ws)
        pad = (kmax - 1) // 2
        x = jnp.concatenate(xs, axis=3)
        C = sum(w.shape[2] for w in ws)
        O = sum(w.shape[3] for w in ws)
        wk = jnp.zeros((kmax, kmax, C, O), x.dtype)
        coff = ooff = 0
        for w in ws:
            k, _, cin, cout = w.shape
            d0 = (kmax - k) // 2
            wk = wk.at[d0:d0 + k, d0:d0 + k,
                       coff:coff + cin, ooff:ooff + cout].set(w)
            coff += cin
            ooff += cout
        y = lax.conv_general_dilated(
            x, wk, window_strides=(1, 1),
            padding=((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        outs = []
        ooff = 0
        for w, d in zip(ws, gparams):
            part = lax.slice_in_dim(y, ooff, ooff + w.shape[3], axis=3)
            ooff += w.shape[3]
            if "bias" in d:
                part = part + d["bias"].astype(x.dtype)
            outs.append(part)
        return outs

    # ------------------------------------------------------------------
    def forward(
        self,
        params: Dict[str, dict],
        data: jnp.ndarray,
        *,
        labels: Optional[jnp.ndarray] = None,
        extras: Sequence[jnp.ndarray] = (),
        train: bool = False,
        rng: Optional[jax.Array] = None,
        step: Optional[jnp.ndarray] = None,
        aux: Optional[Dict[str, dict]] = None,
        return_aux: bool = False,
        sample_mask: Optional[jnp.ndarray] = None,
    ):
        """Execute the graph.

        Returns ``(node_values, total_scaled_loss)``.  ``labels`` is the
        batch label matrix ``(N, label_width)`` (may be None at predict
        time — loss is then 0 and loss layers only transform).

        ``sample_mask`` (N,) zero-weights padded rows of a short final
        train batch out of every loss term (see LossLayer.loss_masked).
        Masking is exact for row-independent nets; batch_norm's batch
        statistics still see the padded rows (set ``round_batch=1`` on the
        data iterator, or ``bn_eval=running``, when that matters).
        """
        g = self.graph
        cdt = self.compute_dtype
        if cdt != jnp.float32:
            params = self._cast_params(params)
            if not self._node0_wants_ints():
                # embedding nets keep raw token ids in f32 (exact to
                # 2^24); bf16 would corrupt ids above 256
                data = data.astype(cdt)
            extras = [e.astype(cdt) for e in extras]
        out_idx = self.out_node_index()
        # collect per-layer state updates when the caller threads aux in
        new_aux: Optional[Dict[str, dict]] = (
            dict(aux) if (aux is not None and return_aux) else None
        )
        nodes: List[Optional[jnp.ndarray]] = [None] * g.num_nodes
        nodes[0] = data
        for k, e in enumerate(extras):
            nodes[k + 1] = e
        total_loss = jnp.zeros((), jnp.float32)
        batch = self.batch_size if self.batch_size > 0 else data.shape[0]
        fuse_groups, fuse_member = (
            self._sibling_1x1_groups() if self.fuse_1x1 else ({}, {})
        )
        # Pallas kernel library: inference builds only (no custom vjp on
        # the kernel calls — the train forward must stay differentiable)
        kern_lib = None if train else self.bound_kernels()
        embed_items, embed_groups = (
            self._branch_embed_plan() if self.use_branch_embed(train)
            else (None, {})
        )
        items = (embed_items if embed_items is not None
                 else [("L", i) for i in range(len(g.layers))])
        for kind, i in items:
            # every device operation's metadata carries the layer's
            # scope, so a profiler trace names operations by conf layer
            with jax.named_scope(self.layer_scope(i)):
                spec = g.layers[i]
                if kind == "E":
                    idxs = embed_groups[i]
                    xs = [nodes[g.layers[j].nindex_in[0]] for j in idxs]
                    if any(v is None for v in xs):
                        raise ValueError(
                            f"branch-embed group at layer {i}: "
                            "unset input node")
                    gparams = [params.get(self.param_key[j], {}) for j in idxs]
                    run_f = (
                        _remat(self._apply_branch_embed)
                        if (self.remat and train) else self._apply_branch_embed
                    )
                    for j, out in zip(idxs, run_f(gparams, xs)):
                        nodes[g.layers[j].nindex_out[0]] = out
                    continue
                if i in fuse_member:
                    if fuse_member[i] != i:
                        continue  # output produced by its group leader below
                    idxs = fuse_groups[i]
                    x = nodes[spec.nindex_in[0]]
                    if x is None:
                        raise ValueError(f"layer {i}: unset input node")
                    gparams = [params.get(self.param_key[j], {}) for j in idxs]
                    # stride bound statically (shared by the whole group via
                    # the fusion key); jax.checkpoint must not trace it
                    fused = functools.partial(
                        self._apply_fused_1x1,
                        self.layer_objs[i].param.stride,
                        kernels=kern_lib,
                    )
                    run_f = (
                        _remat(fused)
                        if (self.remat and train) else fused
                    )
                    for j, out in zip(idxs, run_f(gparams, x)):
                        nodes[g.layers[j].nindex_out[0]] = out
                    continue
                lay = self.layer_objs[i]
                inputs = [nodes[n] for n in spec.nindex_in]
                if any(v is None for v in inputs):
                    raise ValueError(f"layer {i}: unset input node")
                lrng = jax.random.fold_in(rng, i) if rng is not None else None
                if isinstance(lay, LossLayer):
                    logits = inputs[0].astype(jnp.float32)
                    if labels is not None:
                        field = self._label_field(labels, lay.target)
                        scale = lay.grad_scale / (batch * self.update_period)
                        total_loss = total_loss + scale * lay.loss_masked(
                            logits, field, sample_mask
                        )
                    # transform is f32 math; only downcast if a downstream
                    # layer consumes it — the terminal node goes to host
                    # metrics in f32
                    out = lay.transform(logits)
                    if spec.nindex_out[0] != out_idx:
                        out = out.astype(cdt)
                    nodes[spec.nindex_out[0]] = out
                else:
                    key = self.param_key[i]
                    lparams = params.get(key, {})
                    if i in self.borrowed:
                        lparams = dict(lparams, **{
                            tag: params[k][src] for tag, (k, src)
                            in self.borrowed[i].items()})
                    if _opsq().is_quantized(lparams):
                        # int8 entry: dequant-free apply (ops/quant.py) —
                        # conv/fullc only, by the exporter's construction
                        nodes[spec.nindex_out[0]] = self._apply_quant_layer(
                            lay, lparams, inputs, kernels=kern_lib
                        )
                        continue
                    # shared stateful layers chain their state: a later
                    # occurrence reads the state the earlier one produced
                    if new_aux is not None:
                        lstate = new_aux.get(key)
                    elif aux is not None:
                        lstate = aux.get(key)
                    else:
                        lstate = None
                    if lstate is not None and hasattr(lay, "apply_stateful"):
                        if self.remat and train:
                            # state outputs are non-differentiable, so
                            # checkpointing the stateful call is safe — a
                            # bn_eval=running net keeps activation recompute
                            def run_st(p, st, xs, lay=lay, lrng=lrng):
                                return lay.apply_stateful(
                                    p, st, xs, train=True, rng=lrng, step=step
                                )

                            outs, new_state = _remat(run_st)(
                                lparams, lstate, inputs
                            )
                        else:
                            outs, new_state = lay.apply_stateful(
                                lparams, lstate, inputs,
                                train=train, rng=lrng, step=step,
                            )
                        if new_aux is not None:
                            new_aux[key] = new_state
                    elif self.remat and train:

                        def run(p, xs, lay=lay, lrng=lrng):
                            return lay.apply(
                                p, xs, train=True, rng=lrng, step=step
                            )

                        outs = _remat(run)(lparams, inputs)
                    else:
                        outs = lay.apply(
                            lparams, inputs, train=train, rng=lrng, step=step
                        )
                    for n, v in zip(spec.nindex_out, outs):
                        nodes[n] = v
        if return_aux:
            return nodes, total_loss, (new_aux if new_aux is not None else {})
        return nodes, total_loss

    def layer_scope(self, i: int) -> str:
        """``l<index>_<conf name, or the type where the conf gives no
        name>``: the ``jax.named_scope`` of layer ``i``'s operations (a
        fused sibling group runs under its leader's).  Spelled like the
        params' keys, but a shared layer keeps its own index."""
        spec = self.graph.layers[i]
        return f"l{i}_{spec.name or spec.type_name}"

    def use_branch_embed(self, train: bool,
                         backend: Optional[str] = None) -> bool:
        """Whether THIS program build fuses inception branches: the
        explicit conf value when set, else auto — on for inference
        builds (exact, fewer contractions) on accelerator backends,
        off on CPU (the block kernel's ~3.6x MACs only pay on the
        MXU; measured 0.14x CPU predict throughput), and off for the
        train step until its on-chip A/B lands (doc/performance.md).
        ``backend`` overrides the backend probe (tests)."""
        if self.conv_branch_embed >= 0:
            return bool(self.conv_branch_embed)
        if train:
            return False
        return (backend or self._backend()) != "cpu"

    def _backend(self) -> str:
        """The platform this net's programs run on: what the trainer
        bound from its mesh, else (a net driven without a trainer) the
        process default.  Never guessed — a wrong answer here would
        interpret a Pallas kernel on a chip or compile one on a CPU."""
        return self.exec_backend or jax.default_backend()

    def bound_kernels(self, backend: Optional[str] = None):
        """The kernel library's selector bound to this net's execution
        backend (``ops/kernels/``): what the forward dispatch sites
        consume.  Resolution mirrors ``use_branch_embed``; ``backend``
        overrides it (tests)."""
        from ..ops import kernels as _klib

        if self._kernel_sel is None:
            self._kernel_sel = _klib.KernelSelector(self.kernel_lib)
        return self._kernel_sel.bind(backend or self._backend())

    def _apply_quant_layer(self, lay, lparams, inputs, kernels=None):
        """Dispatch one int8-quantized layer (doc/performance.md
        "Quantized inference"): the compiled op consumes the RAW codes
        (the weight at rest stays int8) and the per-channel rescale is
        folded into the bias add.  The exporter only quantizes plain
        conv / fullc layers, so anything else here is a plan bug."""
        from ..layers.conv import ConvolutionLayer
        from ..layers.linear import FullConnectLayer

        q = _opsq()
        x = inputs[0]
        if type(lay) is ConvolutionLayer:
            p = lay.param
            return q.conv_apply_q(lparams, x, p.stride, p.pad_y, p.pad_x,
                                  groups=p.num_group, kernels=kernels)
        if type(lay) is FullConnectLayer:
            return q.fc_apply_q(lparams, x, kernels=kernels)
        raise ValueError(
            f"quantized params on unsupported layer "
            f"{type(lay).__name__} — the export plan only covers "
            "conv and fullc"
        )

    def _node0_wants_ints(self) -> bool:
        """True when any consumer of the data node (node 0) declares
        ``integer_input`` (the embedding layer) — keyed to the graph,
        not to declaration order.  If a net mixes an embedding with
        other node-0 consumers, data stays f32 for all of them
        (conservative: correct ids; the other branches simply compute
        their first layer in f32)."""
        for i, spec in enumerate(self.graph.layers):
            if 0 in spec.nindex_in and getattr(
                self.layer_objs[i], "integer_input", False
            ):
                return True
        return False

    def _cast_params(self, params: Dict[str, dict]) -> Dict[str, dict]:
        """Mixed precision: layer math (MXU) in the compute dtype, master
        params and loss in f32 — jax.grad through the cast yields f32
        grads.  Norm params are excluded (whole norm layers, plus any
        tags a layer lists in ``f32_tags``, e.g. pipe_transformer's
        stacked LN scales): their math runs in f32, so rounding
        gamma/beta through bf16 would only lose precision."""
        cdt = self.compute_dtype

        def cast(key, tags):
            if key in self._f32_param_keys:
                return tags
            if _opsq().QKEY in tags:
                # int8 entry: codes stay int8 (casting them would undo
                # the 4x), scales/bias stay f32 (the rescale fold runs
                # in the f32 accumulate)
                return tags
            keep = self._f32_tag_map.get(key, ())
            return {
                t: (v if t in keep else v.astype(cdt))
                for t, v in tags.items()
            }

        return {key: cast(key, tags) for key, tags in params.items()}

    def _label_field(self, labels: jnp.ndarray, target: str) -> jnp.ndarray:
        g = self.graph
        if target not in g.label_name_map:
            raise ValueError(f"LossLayer: unknown target={target!r}")
        a, b = g.label_range[g.label_name_map[target]]
        if labels.ndim == 1:
            labels = labels[:, None]
        return labels[:, a:b]

    # convenience -------------------------------------------------------
    def out_node_index(self) -> int:
        """The final node (prediction output), reference trainer semantics."""
        return self.graph.layers[-1].nindex_out[-1] if self.graph.layers else 0

    def loss_fn(
        self,
        params,
        data,
        labels,
        *,
        train: bool = True,
        rng=None,
        step=None,
        extras=(),
    ) -> jnp.ndarray:
        _, loss = self.forward(
            params, data, labels=labels, extras=extras, train=train, rng=rng, step=step
        )
        return loss
