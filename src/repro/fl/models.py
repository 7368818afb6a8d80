"""Width-scalable FL models (paper Sec. VI-A: CNN / ResNet-ish / RNN).

Every model is described by an ordered dict of ``CompositionSpec``s:
hidden weights use the paper's "square" mode (p^2 blocks from the shared
P^2 counter); boundary layers (first conv / embedding, classifier) use the
anchored modes with their own P-block counter (Flanc's treatment).

Two parameterisations per model:
  * factorized  — params are (basis, coeff-blocks); used by Heroes/Flanc.
  * dense       — params are materialised width-P weights; used by
                  FedAvg/ADP/HeteroFL (pruning slices sub-weights out).

Forward passes are width-polymorphic AND parameterisation-aware: each
layer entry in the weight dict is either a composed ``(ksq, pI, pO)``
array (applied densely — bit-for-bit the historical path) or the raw
``{"basis", "coeff"}`` factors (applied in *rank space* through
:func:`repro.core.composition.apply_factors`, never materialising the
p-width weight).  :meth:`FLModelDef.prepare_weights` builds that dict
from reduced factors under a ``forward_impl`` knob:

  materialize  compose every layer (exactly ``compose_all`` — the
               bitwise reference the seed histories anchor on);
  rank_space   keep factors for every rank-capable layer;
  auto         pick per (layer, width, batch) by the static FLOPs model
               (``apply_flops`` vs ``compose_flops + dense_apply_flops``),
               with per-layer reuse folded into the application count and
               the measured per-host calibration
               (:mod:`repro.core.calibration`) supplying the overheads
               FLOPs cannot see.  Layers that stay weight-shaped may
               still get the internal ``fused_compose`` impl — the
               compose+apply fusion of ``compose_dense_apply`` — when
               the measured gain says it is cheaper than
               compose-then-matmul.

The per-layer apply/compose/FLOPs/hint bundle is the reusable
:class:`ComposedLayer`; model definitions assemble layers with
:meth:`FLModelDef.from_layers` and register themselves in the model
registry (:func:`register_model` / :func:`get_model`) that
``simulation.build_setup`` resolves ``model_name`` through.  The
transformer definition lives in :mod:`repro.fl.transformer` on the same
abstraction.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.composition import (CompositionSpec, apply_factors,
                                    apply_flops, compose, compose_flops,
                                    conv_rank_overhead, dense_apply_flops,
                                    gather_blocks, init_factors,
                                    rank_space_wins)

Array = jax.Array

FORWARD_IMPLS = ("auto", "materialize", "rank_space")


@dataclasses.dataclass(frozen=True)
class LayerHint:
    """Static per-layer facts feeding the ``auto`` forward-impl choice.

    Attributes:
      apps_per_sample: weight applications per input sample per forward
        — conv output positions, RNN sequence steps, 1 for a head — at
        the model's *reference* input geometry (benchmark tables, or
        when no batch is in scope).  Any *reuse* of one composed weight
        (a scan-carried RNN weight hit T times) is folded in here, so
        the decision correctly amortises the one-off compose against
        the true application count.
      apps_fn: optional ``(data_shape) -> apps_per_sample`` deriving the
        count from the actual traced input shape ``(B, ...)`` (image
        H×W, sequence length), so ``auto`` stays correct when inputs
        differ from the reference geometry.  Preferred over the static
        count whenever a batch is available.
      rank_capable: False pins the layer to materialisation regardless
        of FLOPs — e.g. a scan-carried recurrence weight, which is
        composed once per step and reused T times in the carry loop.
      dense_apply_free: the materialised application costs no FLOPs
        (embedding gathers).
      basis_gather: the rank path's basis projection is also a gather
        (``_apply_embed`` indexes R-length basis rows per token), so
        rank space only pays the R→pO coefficient contraction — it
        beats materialisation exactly when the token count per
        evaluation is below the vocabulary size (``apply_flops``'s
        ``basis_is_gather``).
    """

    apps_per_sample: int = 1
    apps_fn: Optional[Callable[[tuple], int]] = None
    rank_capable: bool = True
    dense_apply_free: bool = False
    basis_gather: bool = False

    def apps(self, data_shape: Optional[tuple] = None) -> int:
        if self.apps_fn is not None and data_shape is not None:
            return max(int(self.apps_fn(data_shape)), 1)
        return self.apps_per_sample


LAYER_KINDS = ("dense", "conv", "embed", "experts")


@dataclasses.dataclass(frozen=True)
class ComposedLayer:
    """One width-scalable layer: spec + application kind + auto-impl hint.

    The reusable unit every model definition is assembled from.  A layer
    knows how to *apply* a weight entry — either a composed dense array
    (the bitwise historical op) or raw ``{"basis", "coeff"}`` factors
    (the rank-space contraction) — and carries the static facts
    (``LayerHint``) the auto forward-impl choice and the rank-aware
    clock model consume.

    Kinds:
      dense  ``x @ W`` on the last axis (any leading shape, so sequence
             inputs ``(B, T, pI)`` work unchanged);
      conv   NHWC SAME conv, ``ksq`` taps, optional stride;
      embed  token gather; the rank path gathers R-length basis rows and
             finishes with the coefficient contraction;
      experts an expert bank (``spec.experts > 1``): the model applies
             its composed ``(E, pI, pO)`` weights (``materialized``) by a
             grouped matmul over rows sorted by expert; ``apply`` refuses.
             Always composed (hint ``rank_capable=False``).
    """

    name: str
    spec: CompositionSpec
    kind: str = "dense"
    stride: int = 1
    hint: LayerHint = LayerHint()

    def __post_init__(self):
        if self.kind not in LAYER_KINDS:
            raise ValueError(f"unknown layer kind {self.kind!r} "
                             f"(expected one of {LAYER_KINDS})")
        if self.kind != "conv" and self.spec.ksq != 1:
            raise ValueError(f"layer {self.name!r}: ksq={self.spec.ksq} "
                             f"requires kind='conv'")
        if self.kind == "embed" and self.spec.mode != "grow_out":
            raise ValueError(f"embed layer {self.name!r} must use "
                             f"mode='grow_out' (vocab-anchored input)")
        if (self.kind == "experts") != (self.spec.experts > 1):
            raise ValueError(f"layer {self.name!r}: kind 'experts' goes "
                             f"with an expert-bank spec and only with one")

    def apply(self, entry, x: Array, width: int) -> Array:
        if self.kind == "conv":
            return _apply_conv(entry, x, width, self.spec, stride=self.stride)
        if self.kind == "embed":
            return _apply_embed(entry, x, width, self.spec)
        if self.kind == "experts":
            raise ValueError(f"expert bank {self.name!r} is applied by the "
                             f"model's grouped matmul over its materialized "
                             f"weights")
        return _apply_dense(entry, x, width, self.spec)

    def materialized(self, entry, width: int) -> Array:
        return _materialized(entry, width, self.spec)


@dataclasses.dataclass(frozen=True, eq=False)
class FLModelDef:
    """A width-scalable FL model.

    ``eq=False`` keeps object-identity hashing: model defs hold dicts and
    closures, and the client/trainer jit caches key on *this exact model
    instance* rather than a lossy string encoding of its constructor args.
    The ``make_*`` factories below are memoized so equal-config models are
    the same instance and still share compiled functions.
    """

    name: str
    specs: Dict[str, CompositionSpec]  # ordered: forward consumption order
    forward: Callable  # (weights: Dict[str, Array|factors], width, batch) -> logits
    flops_per_sample: Callable  # (width) -> flops of fwd+bwd per sample
    num_classes: int
    # static per-layer facts for the auto forward-impl choice; layers
    # without a hint default to LayerHint() (1 application, rank-capable)
    hints: Optional[Dict[str, LayerHint]] = None
    # which batch key carries the input ("x" for images, "tokens" for
    # sequence models) — the engine keys batch assembly off this instead
    # of special-casing model names
    input_key: str = "x"
    # the ComposedLayer dict the forward was assembled from (None for
    # defs built directly on raw specs)
    layers: Optional[Dict[str, ComposedLayer]] = None
    # optional ``(weights, width, batch) -> (logits, stats)``: the same
    # forward, also returning a dict of int arrays counted on the device
    # (the expert model's routed pairs); the client's compiled steps
    # return them beside their results
    forward_stats: Optional[Callable] = None

    @classmethod
    def from_layers(cls, name: str, layers: Dict[str, ComposedLayer],
                    forward: Callable, flops_per_sample: Callable,
                    num_classes: int, *, input_key: str = "x",
                    forward_stats: Optional[Callable] = None
                    ) -> "FLModelDef":
        """Assemble a def from an ordered ComposedLayer dict: the specs
        and hints tables are projections of the layers, so they can
        never drift apart."""
        specs = {n: layer.spec for n, layer in layers.items()}
        hints = {n: layer.hint for n, layer in layers.items()}
        return cls(name, specs, forward, flops_per_sample, num_classes,
                   hints, input_key=input_key, layers=layers,
                   forward_stats=forward_stats)

    def forward_with_stats(self, w, width: int, batch):
        """``(logits, stats)``; ``stats`` is empty for a model that
        counts nothing, so its compiled steps gain no output."""
        if self.forward_stats is None:
            return self.forward(w, width, batch), {}
        return self.forward_stats(w, width, batch)

    # ---- factorized parameterisation -----------------------------------
    def init_factorized(self, key) -> Dict[str, Dict[str, Array]]:
        out = {}
        for k, (name, spec) in zip(
            jax.random.split(key, len(self.specs)), self.specs.items()
        ):
            v, u = init_factors(k, spec)
            out[name] = {"basis": v, "coeff": u}
        return out

    def reduce(self, params, width: int, hidden_ids, anchored_ids):
        """Ship-to-client factors: gather the assigned blocks per layer."""
        out = {}
        for name, spec in self.specs.items():
            ids = hidden_ids if spec.mode == "square" else anchored_ids
            out[name] = {
                "basis": params[name]["basis"],
                "coeff": gather_blocks(params[name]["coeff"], np.asarray(ids)),
            }
        return out

    def compose_all(self, reduced, width: int) -> Dict[str, Array]:
        reduced = _on_one_device(reduced)
        return {
            name: compose(reduced[name]["basis"], reduced[name]["coeff"], width, spec)
            for name, spec in self.specs.items()
        }

    def layer_impls(self, width: int, batch_size: int, forward_impl: str,
                    data_shape: Optional[tuple] = None,
                    calibration=None) -> Dict[str, str]:
        """Per-layer materialize/rank_space/fused_compose choice (static,
        per trace).

        ``auto`` compares, per layer, the rank-space application cost
        against compose + dense application over the layer's total
        application count ``batch_size * hint.apps(data_shape)`` — so a
        bigger batch amortises the compose and a reuse-heavy layer
        (scan recurrence) tilts toward materialisation.  ``data_shape``
        (the input array's shape) lets hints derive true application
        counts from the traced geometry instead of the model's
        reference input size.

        The overheads the FLOPs model cannot see come from the measured
        per-process calibration (:mod:`repro.core.calibration`), or the
        ``calibration`` argument when the engine threads an ``FLConfig``
        override through.  Two consequences beyond the binary choice:
        conv layers use the *measured* ``conv_rank_overhead`` (the fused
        :mod:`repro.kernels.conv_rank` path wins on CPU at high
        FLOPs-ratio shapes, so ``auto`` now enables it there), and a
        rank-capable dense layer that still loses to materialisation is
        labelled ``"fused_compose"`` when the measured
        ``fused_compose_gain < 1`` — same math as materialize, but the
        p-width weight is built and consumed inside one kernel
        (``compose_dense_apply``) instead of round-tripping HBM.
        """
        if forward_impl not in FORWARD_IMPLS:
            raise ValueError(f"unknown forward_impl {forward_impl!r} "
                             f"(expected one of {FORWARD_IMPLS})")
        if forward_impl == "materialize":
            return {name: "materialize" for name in self.specs}
        if forward_impl == "auto" and calibration is None:
            from repro.core.calibration import get_calibration

            calibration = get_calibration()
        hints = self.hints or {}
        out = {}
        for name, spec in self.specs.items():
            hint = hints.get(name, LayerHint())
            if not hint.rank_capable:
                out[name] = "materialize"
            elif forward_impl == "rank_space":
                out[name] = "rank_space"
            else:
                apps = max(batch_size, 1) * hint.apps(data_shape)
                ovh = (conv_rank_overhead(calibration)
                       if spec.ksq > 1 else 1.0)
                if rank_space_wins(
                        width, spec, applications=apps,
                        dense_apply_free=hint.dense_apply_free,
                        basis_is_gather=hint.basis_gather,
                        overhead=ovh):
                    out[name] = "rank_space"
                elif (spec.ksq == 1 and not hint.dense_apply_free
                      and calibration.fused_compose_gain < 1.0):
                    out[name] = "fused_compose"
                else:
                    out[name] = "materialize"
        return out

    def prepare_weights(self, reduced, width: int, batch,
                        forward_impl: str = "materialize",
                        calibration=None) -> Dict[str, Any]:
        """The weight dict ``forward`` consumes, per ``forward_impl``.

        ``materialize`` is exactly :meth:`compose_all` (the bitwise
        reference path).  Otherwise rank-space layers pass their raw
        ``{"basis", "coeff"}`` factors through untouched — the forward
        applies them via rank-space contractions — ``fused_compose``
        layers pass the factors with a static ``"fused"`` marker (the
        forward routes them through ``compose_dense_apply``), and the
        rest compose as usual.  The choice keys on static shapes and
        the (hashable) calibration only, so it is jit-cache-stable per
        (width, batch shape, calibration).
        """
        if forward_impl == "materialize":
            return self.compose_all(reduced, width)
        data = (batch.get(self.input_key, batch.get("x", batch.get("tokens")))
                if isinstance(batch, dict) else None)
        shape = tuple(data.shape) if data is not None else None
        batch_size = shape[0] if shape else 1
        impls = self.layer_impls(width, batch_size, forward_impl, shape,
                                 calibration)
        out = {}
        for name, spec in self.specs.items():
            if impls[name] == "rank_space":
                out[name] = reduced[name]
            elif impls[name] == "fused_compose":
                out[name] = {**reduced[name], "fused": True}
            else:
                out[name] = compose(reduced[name]["basis"],
                                    reduced[name]["coeff"], width, spec)
        return out

    def apply_flops_per_sample(self, width: int, batch_size: int,
                               forward_impl: str,
                               data_shape: Optional[tuple] = None,
                               calibration=None) -> float:
        """Per-sample fwd+bwd FLOPs under the per-layer impl the client
        forward actually takes (the ``clock_model="rank_aware"`` time
        model).

        Rank-space layers charge :func:`apply_flops`; materialised
        layers — including ``fused_compose`` ones, whose fusion saves
        memory traffic, not FLOPs — charge their one-off ``compose``
        amortised over the batch plus the dense application (free for
        embedding gathers).  Backward ~ 2x forward, so the total is 3x
        — the same convention the dense ``flops_per_sample`` tables use.
        """
        impls = self.layer_impls(width, batch_size, forward_impl, data_shape,
                                 calibration)
        hints = self.hints or {}
        bs = max(int(batch_size), 1)
        total = 0.0
        for name, spec in self.specs.items():
            hint = hints.get(name, LayerHint())
            apps = hint.apps(data_shape)
            if impls[name] == "rank_space":
                fwd = apply_flops(width, spec, applications=apps,
                                  basis_is_gather=hint.basis_gather)
            else:
                fwd = compose_flops(width, spec) / bs
                if not hint.dense_apply_free:
                    fwd += dense_apply_flops(width, spec, applications=apps)
            total += 3.0 * fwd
        return total

    def factorized_bytes(self, width: int) -> int:
        return 4 * sum(s.params_factorized(width) for s in self.specs.values())

    # ---- dense parameterisation ------------------------------------------
    def init_dense(self, key) -> Dict[str, Array]:
        out = {}
        for k, (name, spec) in zip(
            jax.random.split(key, len(self.specs)), self.specs.items()
        ):
            ksq, i, o = spec.weight_shape(spec.max_width)
            out[name] = (1.0 / math.sqrt(ksq * i)) * jax.random.normal(k, (ksq, i, o))
        return out

    def slice_dense(self, params: Dict[str, Array], width: int) -> Dict[str, Array]:
        """HeteroFL-style sub-model: leading slices of each weight."""
        out = {}
        for name, spec in self.specs.items():
            ksq, i, o = spec.weight_shape(width)
            out[name] = params[name][:, :i, :o]
        return out

    def dense_bytes(self, width: int) -> int:
        return 4 * sum(s.params_materialized(width) for s in self.specs.values())


def _on_one_device(tree):
    """Concrete arrays spread over several devices, moved to one of them.

    Server-side composition (evaluation, serving) runs eagerly on the
    merged state, which the collective merge leaves replicated (or
    block-sharded) over the cohort mesh.  XLA cannot partition a Pallas
    kernel over a mesh, so a compose on such arrays must run on one
    device; a replicated array already holds a full copy there.  Traced
    values (compose inside a jitted or ``shard_map``ped loss) pass
    through.
    """
    def one(x):
        if isinstance(x, jax.core.Tracer) or not isinstance(x, jax.Array):
            return x
        devs = x.sharding.device_set
        if len(devs) < 2:
            return x
        return jax.device_put(x, min(devs, key=lambda d: d.id))

    return jax.tree_util.tree_map(one, tree)


# ---------------------------------------------------------------------------
# model registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelEntry:
    """Registry row: the builder plus the data modality it expects.

    ``build(max_width, meta, **overrides)`` receives the dataset's
    metadata dict and returns the (memoized) ``FLModelDef``.
    """

    name: str
    modality: str  # "image" | "text"
    build: Callable[..., FLModelDef]


MODEL_REGISTRY: Dict[str, ModelEntry] = {}


def register_model(name: str, *, modality: str = "image"):
    """Decorator registering a ``build(max_width, meta, **kw)`` factory
    under ``name`` so ``simulation.build_setup`` can resolve it."""
    def deco(build):
        if name in MODEL_REGISTRY:
            raise ValueError(f"model {name!r} already registered")
        MODEL_REGISTRY[name] = ModelEntry(name, modality, build)
        return build
    return deco


def get_model(name: str) -> ModelEntry:
    try:
        return MODEL_REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown model {name!r}; registered: {sorted(MODEL_REGISTRY)}"
        ) from None


# ---------------------------------------------------------------------------
# forward helpers
# ---------------------------------------------------------------------------


def _conv(x: Array, w3: Array, k: int, stride: int = 1) -> Array:
    """x NHWC, w3 (k*k, I, O) -> conv with SAME padding."""
    kk, i, o = w3.shape
    w = w3.reshape(k, k, i, o)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


# Parameterisation-aware layer application: a composed array runs the
# exact historical dense op (bitwise); a {"basis","coeff"} factor dict
# runs the rank-space contraction.  The isinstance dispatch is static at
# trace time — the weight dict's pytree structure is fixed per jit.


def _apply_conv(entry, x: Array, width: int, spec: CompositionSpec,
                stride: int = 1) -> Array:
    if isinstance(entry, dict):
        return apply_factors(x, entry["basis"], entry["coeff"], width, spec,
                             "conv", stride=stride)
    return _conv(x, entry, int(round(spec.ksq ** 0.5)), stride=stride)


def _apply_dense(entry, x: Array, width: int, spec: CompositionSpec) -> Array:
    if isinstance(entry, dict):
        if entry.get("fused"):
            # "fused_compose" impl: materialize-path math, but the
            # p-width weight is built and consumed inside one kernel
            # (the marker is a static Python bool prepare_weights sets
            # at trace time, so this branch is trace-static too)
            from repro.kernels.compose import compose_dense_apply

            return compose_dense_apply(x, entry["basis"], entry["coeff"],
                                       width, spec.mode)
        return apply_factors(x, entry["basis"], entry["coeff"], width, spec,
                             "dense")
    return x @ entry[0]


def _apply_embed(entry, tokens: Array, width: int,
                 spec: CompositionSpec) -> Array:
    """Embedding lookup: gather the composed rows, or gather the R-dim
    basis rows and finish with the coefficient contraction."""
    if isinstance(entry, dict):
        emb_r = jnp.take(entry["basis"][0], tokens, axis=0)  # (..., R)
        y = jnp.einsum("...r,bro->...bo", emb_r, entry["coeff"])
        return y.reshape(y.shape[:-2] + (width * spec.base_out,))
    return jnp.take(entry[0], tokens, axis=0)


def _materialized(entry, width: int, spec: CompositionSpec) -> Array:
    """Force-compose a layer the forward needs as a dense array (the
    RNN's scan-carried recurrence weight: composed once per evaluation,
    reused T times in the carry loop)."""
    if isinstance(entry, dict):
        return compose(entry["basis"], entry["coeff"], width, spec)
    return entry


# ---------------------------------------------------------------------------
# CNN (paper's 4-layer CNN, reduced input 8x8)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def make_cnn(max_width: int = 3, base: int = 8, rank: int = 8,
             num_classes: int = 10, in_ch: int = 3) -> FLModelDef:
    layers = {
        "conv1": ComposedLayer(
            "conv1",
            CompositionSpec(max_width, rank, in_ch, base, ksq=9, mode="grow_out"),
            kind="conv",
            hint=LayerHint(64, lambda s: s[1] * s[2])),
        "conv2": ComposedLayer(
            "conv2", CompositionSpec(max_width, rank, base, base, ksq=9),
            kind="conv", stride=2,
            hint=LayerHint(16, lambda s: -(-s[1] // 2) * (-(-s[2] // 2)))),
        "conv3": ComposedLayer(
            "conv3", CompositionSpec(max_width, rank, base, base, ksq=9),
            kind="conv", stride=2,
            hint=LayerHint(4, lambda s: -(-s[1] // 4) * (-(-s[2] // 4)))),
        "fc": ComposedLayer(
            "fc",
            CompositionSpec(max_width, rank, base, num_classes, ksq=1,
                            mode="grow_in"),
            hint=LayerHint(apps_per_sample=1)),
    }

    def forward(w: Dict[str, Any], width: int, batch) -> Array:
        x = batch["x"]
        x = jax.nn.relu(layers["conv1"].apply(w["conv1"], x, width))
        x = jax.nn.relu(layers["conv2"].apply(w["conv2"], x, width))
        x = jax.nn.relu(layers["conv3"].apply(w["conv3"], x, width))
        x = jnp.mean(x, axis=(1, 2))  # GAP
        return layers["fc"].apply(w["fc"], x, width)

    def flops(width: int, hw: int = 8) -> int:
        p = width
        f = 0
        f += 2 * 9 * in_ch * (p * base) * hw * hw
        f += 2 * 9 * (p * base) ** 2 * (hw // 2) ** 2
        f += 2 * 9 * (p * base) ** 2 * (hw // 4) ** 2
        f += 2 * (p * base) * num_classes
        return 3 * f  # fwd + bwd ~ 3x

    return FLModelDef.from_layers("cnn", layers, forward, flops, num_classes)


# ---------------------------------------------------------------------------
# ResNet-ish (reduced stand-in for the paper's ResNet-18)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def make_resnet(max_width: int = 3, base: int = 8, rank: int = 8,
                num_classes: int = 10, in_ch: int = 3) -> FLModelDef:
    conv_hint = LayerHint(64, lambda s: s[1] * s[2])  # stride-1 convs
    layers = {
        "stem": ComposedLayer(
            "stem",
            CompositionSpec(max_width, rank, in_ch, base, ksq=9, mode="grow_out"),
            kind="conv", hint=conv_hint),
        **{name: ComposedLayer(
            name, CompositionSpec(max_width, rank, base, base, ksq=9),
            kind="conv", hint=conv_hint)
           for name in ("b1a", "b1b", "b2a", "b2b")},
        "fc": ComposedLayer(
            "fc",
            CompositionSpec(max_width, rank, base, num_classes, ksq=1,
                            mode="grow_in"),
            hint=LayerHint(apps_per_sample=1)),
    }

    def forward(w, width, batch):
        x = batch["x"]
        x = jax.nn.relu(layers["stem"].apply(w["stem"], x, width))
        h = jax.nn.relu(layers["b1a"].apply(w["b1a"], x, width))
        x = jax.nn.relu(x + layers["b1b"].apply(w["b1b"], h, width))
        h = jax.nn.relu(layers["b2a"].apply(w["b2a"], x, width))
        x = jax.nn.relu(x + layers["b2b"].apply(w["b2b"], h, width))
        x = jnp.mean(x, axis=(1, 2))
        return layers["fc"].apply(w["fc"], x, width)

    def flops(width, hw: int = 8):
        p = width
        f = 2 * 9 * in_ch * (p * base) * hw * hw
        f += 4 * 2 * 9 * (p * base) ** 2 * hw * hw
        f += 2 * (p * base) * num_classes
        return 3 * f

    return FLModelDef.from_layers("resnet", layers, forward, flops,
                                  num_classes)


# ---------------------------------------------------------------------------
# RNN (Shakespeare stand-in: next-token prediction)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def make_rnn(max_width: int = 3, base: int = 16, rank: int = 8,
             vocab: int = 64) -> FLModelDef:
    seq_len = lambda s: s[1]  # noqa: E731 — tokens (B, T)
    layers = {
        # embedding application is a gather on BOTH paths: materialised
        # rows cost ~0, and the rank path gathers R-length basis rows
        # then pays only the coefficient contraction per token
        "embed": ComposedLayer(
            "embed",
            CompositionSpec(max_width, rank, vocab, base, ksq=1,
                            mode="grow_out"),
            kind="embed",
            hint=LayerHint(32, seq_len, dense_apply_free=True,
                           basis_gather=True)),
        "wx": ComposedLayer(
            "wx", CompositionSpec(max_width, rank, base, base, ksq=1),
            hint=LayerHint(32, seq_len)),
        # scan recurrence: composed once, reused T times per evaluation
        "wh": ComposedLayer(
            "wh", CompositionSpec(max_width, rank, base, base, ksq=1),
            hint=LayerHint(32, seq_len, rank_capable=False)),
        "out": ComposedLayer(
            "out",
            CompositionSpec(max_width, rank, base, vocab, ksq=1,
                            mode="grow_in"),
            hint=LayerHint(32, seq_len)),
    }

    def forward(w, width, batch):
        tokens = batch["tokens"]  # (B, T)
        emb = layers["embed"].apply(w["embed"], tokens, width)  # (B,T,pE)
        # the scan-carried recurrence weight is materialised ONCE per
        # evaluation and reused T times in the carry loop — rank-space
        # application would redo two contractions per step for a weight
        # whose compose is amortised T-fold (see LayerHint.rank_capable)
        wh = layers["wh"].materialized(w["wh"], width)[0]

        if isinstance(w["wx"], dict):
            # input projection in rank space, hoisted out of the scan:
            # all T steps contract through R in one shot
            xp = layers["wx"].apply(w["wx"], emb, width)

            def step(h, x):
                h = jnp.tanh(x + h @ wh)
                return h, h

            xs = jnp.moveaxis(xp, 1, 0)
        else:
            wx = w["wx"][0]

            def step(h, x):
                h = jnp.tanh(x @ wx + h @ wh)
                return h, h

            xs = jnp.moveaxis(emb, 1, 0)

        h0 = jnp.zeros((emb.shape[0], wh.shape[0]), emb.dtype)
        _, hs = jax.lax.scan(step, h0, xs)
        hs = jnp.moveaxis(hs, 0, 1)  # (B,T,pH)
        return layers["out"].apply(w["out"], hs, width)  # (B,T,V)

    def flops(width, seq: int = 32):
        p = width
        per_tok = 2 * vocab * (p * base) + 4 * (p * base) ** 2 + 2 * (p * base) * vocab
        return 3 * per_tok * seq

    return FLModelDef.from_layers("rnn", layers, forward, flops, vocab,
                                  input_key="tokens")


MODELS = {"cnn": make_cnn, "resnet": make_resnet, "rnn": make_rnn}


@register_model("cnn", modality="image")
def _build_cnn(max_width: int, meta: Dict[str, Any], **kw) -> FLModelDef:
    return make_cnn(max_width=max_width, num_classes=meta["num_classes"],
                    in_ch=meta["channels"], **kw)


@register_model("resnet", modality="image")
def _build_resnet(max_width: int, meta: Dict[str, Any], **kw) -> FLModelDef:
    return make_resnet(max_width=max_width, num_classes=meta["num_classes"],
                       in_ch=meta["channels"], **kw)


@register_model("rnn", modality="text")
def _build_rnn(max_width: int, meta: Dict[str, Any], **kw) -> FLModelDef:
    return make_rnn(max_width=max_width, vocab=meta["vocab"], **kw)
